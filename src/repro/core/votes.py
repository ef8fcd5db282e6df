"""Distributed majority-vote transports over the ``data`` (device) axis.

Input: per-device quantities laid out ``[P, D, *leaf]`` (P pods = edges,
D data slices = devices).  Output: per-pod vote ``[P, *leaf]``.

Transport matrix (DESIGN.md Sec. 2 "Vote transport"):

============  ==============  ===========================  =================
transport     wire format     HBM passes per local step    fallback rules
============  ==============  ===========================  =================
``ag_packed`` 1 bit/coord,    per leaf: read g (f32) ->    leaf minor dim
              per leaf        write words (1/256 of g),    % 32 != 0 ->
                              gather, unpack+vote fusion   ``ar_int8``
``ar_int8``   8 bits/coord    read signs, int tally        tally upcasts to
                              all-reduce, sgn              int16 when
                                                           D > 127 voters
``fused``     1 bit/coord,    ONE flat word buffer for     FSDP regime and
              one contiguous  the whole model: per-leaf    per-leaf callers
              word buffer     fused (g + rho*delta) ->     -> ``ag_packed``;
              (flatbuf        sign -> pack, word-level     model axis > 1 or
              layout)         concat (1/32 of the tally),  kernels on >1
                              ONE data-axis gather, ONE    device ->
                              popcount vote + update       shard_map program
                                                           (kernels per rank
                                                           on TPU); off-TPU
                                                           -> pure jnp
                                                           (bit-identical)
``mean`` /    32 bits/coord   full-precision weighted      --
``wmean``                     averaging (HierSGD)
============  ==============  ===========================  =================

``ag_packed``  (paper-faithful) -- each device contributes a bit-packed sign
    row (1 bit/coordinate, exactly the paper's uplink payload); the packed
    rows are all-gathered along ``data`` and every chip computes the same
    popcount vote -- this *is* the paper's "edge broadcasts the vote back",
    with zero additional downlink.

``ar_int8``  (beyond-paper optimized) -- the vote sgn(sum_k sgn g_k) is
    computed distributively via an int8 all-reduce of the sign tally
    (|sum| <= D <= 127 fits int8; larger D upcasts the tally to int16).
    8 bits/coordinate on the wire but a single reduction phase, and under
    FSDP the tally reduce-scatters straight onto the owning shard.

``fused``  (beyond-paper, flat-buffer) -- the whole model is bucketized by
    ``core.flatbuf`` into one 32*128-tile-aligned coordinate space; devices
    emit a single contiguous packed uplink row per step with the DC
    correction fused pre-sign (Alg. 2's device-side step), ONE gather moves
    it, and ONE fused popcount-vote produces the per-pod direction.  On
    TPU the local compute runs the Pallas kernels (``kernels.sign_pack``
    / ``kernels.vote_update``).  They are single-device programs, so on a
    multi-device TPU mesh -- and on every mesh with a >1 model axis,
    whose flatbuf layout is *sharded* (per-model-shard buckets) -- the
    whole chain runs as a per-rank ``shard_map`` program: each rank
    sign-packs its own bucket (Pallas on TPU), the packed words are
    all-gathered over ``data`` INSIDE the program -- the only collective
    -- and each rank votes/updates its local shard, so no whole-leaf
    gather and no unsharded bit tensor ever exist.  Off-TPU without a
    model axis a pure-jnp path with identical arithmetic runs (GSPMD
    partitions it).  All three sign transports are bit-identical
    (ties -> +1) by construction.  Requires the replicated regime.

State layouts (``AlgoConfig.state_layout``, see ``core.flatbuf``):

``tree`` (default) -- the master params are a pytree; every transport's
    vote is unflattened back to leaves and the descent update
    ``v <- v - mu*vote`` is a per-leaf tree map.
``flat`` -- the master params ARE the flat buffer (``flatbuf.FlatState``)
    for the whole run; any transport's direction is applied as ONE
    whole-buffer elementwise update, and ``transport="fused"`` goes
    further through :func:`fused_sign_vote_update`: the vote is never
    materialized -- ONE ``vote_update`` read-modify-write per pod applies
    ``v <- v - mu*MajorityVote(packed)`` over the packed-word buffer
    (in-place when compiled).  On meshes with a >1 model axis the
    buffer uses the SHARDED flatbuf layout (one bucket per model shard)
    and every buffer<->tree move plus the fused chain itself runs under
    ``shard_map`` (``core.shardflat`` / :func:`_fused_shard_map`) --
    the buffer, the packed words and the vote stay model-sharded end to
    end.  Bit-identical in trajectory to ``tree`` under every transport
    (the per-coordinate arithmetic is unchanged; asserted by
    tests/test_parity_matrix.py and the multi-chip
    tests/helpers/sharded_fused_check.py).  Replicated regime only.

All functions are pure jnp + sharding constraints: they lower to data-axis
collectives under GSPMD and degenerate to local arithmetic at P=D=1 (which
is how they are unit-tested against ``repro.core.signs``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import flatbuf, shardflat, signs, stages
from repro.core.topology import Topology
from repro.kernels import ops as kops

PACK = signs.PACK_WIDTH

SIGN_TRANSPORTS = ("ag_packed", "ar_int8", "fused")


def _mask_bcast(mask: jax.Array | None, ndim_leaf: int):
    """[P, D] voter mask/weights -> broadcastable to [P, D, *leaf]."""
    if mask is None:
        return None
    return mask.reshape(mask.shape + (1,) * ndim_leaf)


def _tally_acc(weight_bound: int):
    """Smallest int dtype holding a tally of range ``weight_bound``
    (the weighted-vote generalization of the PR 1 D>127 promotion:
    promote on ``sum(w)``, not on the voter count)."""
    if weight_bound <= 127:
        return jnp.int8
    if weight_bound <= 32767:
        return jnp.int16
    return jnp.int32


def vote_ar_int8(topo: Topology, s_dev: jax.Array,
                 mask: jax.Array | None,
                 weight_bound: int | None = None) -> jax.Array:
    """sgn(sum_k w_k s_k) via an integer tally reduction over the device
    axis.

    mask: optional [P, D] voter mask OR nonnegative integer vote weights
    (``core.clients`` data shares; weight 0 abstains, and an edge whose
    whole quorum abstains returns vote 0).  The tally rides the wire in
    int8 while its range ``sum(w) <= 127`` fits (unit weights: the voter
    count D); wider ranges promote to int16/int32.  ``weight_bound`` is
    the *static* per-edge range ``max_q sum_k w_qk`` -- required for
    weighted masks (traced values cannot pick dtypes); ``None`` means
    unit weights and reproduces the original ``D > 127`` promotion rule
    (regression-tested).  Passing an integer-dtype weight array without
    a bound raises -- silently defaulting to the voter count would
    re-open the wrap this rule exists to prevent.
    """
    if (weight_bound is None and mask is not None
            and jnp.issubdtype(mask.dtype, jnp.integer)):
        raise ValueError(
            "vote_ar_int8: integer vote weights need an explicit static "
            "weight_bound (max per-edge sum(w)) to size the tally dtype; "
            "the voter-count default only covers {0,1} masks")
    bound = weight_bound if weight_bound is not None else s_dev.shape[1]
    acc = _tally_acc(bound)
    tally = s_dev.astype(acc)
    m = _mask_bcast(mask, s_dev.ndim - 2)
    if m is not None:
        tally = tally * m.astype(acc)
    tally = jnp.sum(tally, axis=1, dtype=acc)                  # [P, *leaf]
    # with abstentions the tie rule is 2*pos >= n_eff  <=>  tally >= 0
    vote = signs.sgn(tally.astype(jnp.int32))
    if mask is not None:
        n_eff = jnp.sum(mask.astype(jnp.int32), axis=1)
        n_eff = n_eff.reshape((-1,) + (1,) * (vote.ndim - 1))
        vote = jnp.where(n_eff > 0, vote, jnp.int8(0))
    return vote


def vote_ag_packed(topo: Topology, s_dev: jax.Array,
                   mask: jax.Array | None, leaf_spec: P) -> jax.Array:
    """Bit-packed all-gather + local popcount vote (1 bit/coord wire).

    s_dev: [P, D, *leaf] int8 signs; leaf minor dim % 32 == 0 required;
    mask: optional [P, D] voter mask or integer vote weights (weighted
    popcount; an empty quorum abstains -> vote 0).
    The packed words are constrained to be replicated along ``data`` --
    that resharding is the all-gather whose operand is 1/32 the int8 tally
    (and 1/256 the fp32 gradient) -- then every chip votes locally.
    """
    *lead, minor = s_dev.shape
    assert minor % PACK == 0, "caller guarantees minor % 32 == 0"
    words = signs.pack_signs(s_dev)                            # [P, D, *l, minor/32]
    # device-axis all-gather of the 1-bit payload: keep every other dim's
    # sharding, drop 'data' from dim 1.
    gathered_spec = P(topo.pod_axis, None, *leaf_spec)
    words = topo.constrain(words, gathered_spec)
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)        # [P,D,*l,w,32]
    bits = bits.astype(jnp.int8)
    if mask is not None:
        # mask may carry integer vote weights (weighted popcount): the
        # per-voter product runs in int32 so weights cannot wrap
        m = _mask_bcast(mask, bits.ndim - 2)
        pos = jnp.sum(bits.astype(jnp.int32) * m.astype(jnp.int32),
                      axis=1, dtype=jnp.int32)
        n_eff = jnp.sum(mask.astype(jnp.int32), axis=1)
        n_eff = n_eff.reshape((-1,) + (1,) * (pos.ndim - 1))
    else:
        pos = jnp.sum(bits, axis=1, dtype=jnp.int32)           # [P,*l,w,32]
        n_eff = s_dev.shape[1]
    vote = jnp.where(2 * pos >= n_eff, jnp.int8(1), jnp.int8(-1))
    if mask is not None:   # empty quorum abstains
        vote = jnp.where(n_eff > 0, vote, jnp.int8(0))
    return vote.reshape(s_dev.shape[:1] + s_dev.shape[2:])     # [P, *leaf]


# ---------------------------------------------------------------------------
# Fused flat-buffer transport
# ---------------------------------------------------------------------------

_UNROLL_VOTERS = 64     # static unroll bound for the popcount accumulation


def _popcount_vote_words(words: jax.Array, mask: jax.Array | None,
                         n_dev: int) -> jax.Array:
    """[P, D, W] packed words (+ [P, D] mask/weights) -> [P, W*32] int8 vote.

    ``mask`` may carry integer vote weights (the weighted popcount of
    ``core.clients``): the per-voter bit-plane is scaled by its weight
    in int32 and the tie rule compares against the participating weight
    sum; an empty quorum abstains (vote 0).

    For small static D the voter axis is unrolled into an add chain of
    per-voter unpacks, so the [P, D, W, 32] bit tensor (an 8x HBM blow-up
    of the wire payload) never materializes -- XLA fuses the chain into
    one sweep whose operand is the packed words themselves.  Large D
    falls back to the reduction form.
    """
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    d = words.shape[1]

    def bits_of(w_d):                                          # [P,W] words
        return ((w_d[..., None] >> shifts) & jnp.uint32(1)
                ).astype(jnp.int32)                            # [P,W,32]

    if d <= _UNROLL_VOTERS:
        pos = None
        for k in range(d):
            b = bits_of(words[:, k])
            if mask is not None:
                b = b * mask[:, k].astype(jnp.int32)[:, None, None]
            pos = b if pos is None else pos + b
    else:
        bits = (words[..., None] >> shifts) & jnp.uint32(1)    # [P,D,W,32]
        if mask is not None:
            m = mask.astype(jnp.int32)[:, :, None, None]
            pos = jnp.sum(bits.astype(jnp.int32) * m, axis=1,
                          dtype=jnp.int32)
        else:
            pos = jnp.sum(bits.astype(jnp.int8), axis=1,
                          dtype=jnp.int32)                     # [P,W,32]
    if mask is not None:
        n_eff = jnp.sum(mask.astype(jnp.int32), axis=1)[:, None, None]
    else:
        n_eff = n_dev
    vote = jnp.where(2 * pos >= n_eff, jnp.int8(1), jnp.int8(-1))
    if mask is not None:   # empty quorum abstains
        vote = jnp.where(n_eff > 0, vote, jnp.int8(0))
    return vote.reshape(vote.shape[0], -1)                     # [P, W*32]


# ---------------------------------------------------------------------------
# Streamed virtual-client tally (ClientConfig.mode="stream")
# ---------------------------------------------------------------------------
#
# The streamed client sweep never widens the voter axis: each client's
# signs are folded into a persistent SIGNED tally  t += w_c * sgn(u_c)
# (in the ``_tally_acc(weight_bound)`` dtype -- every partial sum is
# bounded by the running participating-weight sum, so the accumulator
# can never transiently overflow), and the sign threshold is DEFERRED
# until after the client loop:  t = 2*pos - n_eff, so ``t >= 0`` is
# exactly the merged path's ``2*pos >= n_eff`` tie rule and the two
# modes are bitwise identical by integer associativity.

def tally_dtype(weight_bound: int):
    """Accumulator dtype of the streamed tally -- the SAME promotion
    rule as ``vote_ar_int8`` (``_tally_acc``): the signed tally has
    range ``sum(w)``, so it promotes on the weight bound, not on the
    client count."""
    return _tally_acc(weight_bound)


def tally_add_signs(tally: jax.Array, s: jax.Array,
                    weights: jax.Array) -> jax.Array:
    """One client's weighted sign contribution: ``tally + w * s``.

    tally: [P, D, *leaf] signed tally (``tally_dtype`` ints); s:
    [P, D, *leaf] int8 signs of ONE client; weights: [P, D] nonnegative
    integer vote weights of that client this round (0 = abstains).
    The product runs in int32 and narrows back to the tally dtype --
    exact, since every partial tally is bounded by ``weight_bound``.
    """
    w = weights.astype(jnp.int32).reshape(
        weights.shape + (1,) * (s.ndim - 2))
    return tally + (s.astype(jnp.int32) * w).astype(tally.dtype)


def tally_accumulate_words(words: jax.Array, weights: jax.Array,
                           tally: jax.Array) -> jax.Array:
    """Tally-accumulate variant of ``_popcount_vote_words``: fold ONE
    client's packed sign words into the signed tally.

    words: [P, D, W] uint32 (the client's 1-bit uplink payload);
    weights: [P, D] integer vote weights; tally: [P, D, W*32] signed
    tally, or any shape of [P, D, ...] in the same coordinate order.  Per coordinate ``tally += w * (2*bit - 1)`` -- the same
    weighted popcount as the merged transports, deferred: summing these
    contributions over clients gives ``t = 2*pos - n_eff``.
    """
    shifts = jnp.arange(PACK, dtype=jnp.uint32)
    bits = ((words[..., None] >> shifts) & jnp.uint32(1)).astype(jnp.int32)
    sgn_c = 2 * bits - 1                                       # [P,D,W,32]
    add = sgn_c * weights.astype(jnp.int32)[:, :, None, None]
    return tally + add.reshape(tally.shape).astype(tally.dtype)


def tally_vote(tally: jax.Array, n_eff: jax.Array) -> jax.Array:
    """Deferred threshold of the streamed sweep: signed tally -> vote.

    tally: [P, *leaf] edge tally (summed over devices; int); n_eff:
    [P] int32 participating weight sum.  ``t >= 0 -> +1`` is exactly
    the merged tie rule ``2*pos >= n_eff`` (t = 2*pos - n_eff), so
    weighted ties still resolve to sgn(0) = +1; an empty quorum
    (n_eff == 0) abstains with vote 0.
    """
    t = tally.astype(jnp.int32)
    vote = jnp.where(t >= 0, jnp.int8(1), jnp.int8(-1))
    n = n_eff.reshape((-1,) + (1,) * (vote.ndim - 1))
    return jnp.where(n > 0, vote, jnp.int8(0))


def _fused_kernel_bufs(layout, u_dev, delta_tree, delta_buf, rho):
    """Fold rule + flat views for the Pallas route (shared by the vote-
    only and the flat-state vote+update entry points; the correction may
    arrive as a pytree or as a flat buffer).

    The sign_pack kernel adds rho*delta in f32; folding it there is
    exact only when the reference per-leaf arithmetic is f32 too.
    Mixed/low-precision trees pre-add in each leaf's own dtype
    (identical to the tree path) to keep the transports bit-identical
    at ULP sign boundaries.
    """
    leaves = layout.treedef.flatten_up_to(u_dev)
    have_delta = (delta_tree is not None or delta_buf is not None) and rho
    fold_in_kernel = (have_delta
                      and all(leaf.dtype == jnp.float32 for leaf in leaves))
    if have_delta and not fold_in_kernel:
        if delta_tree is None:
            delta_tree = flatbuf.unflatten_tree(layout, delta_buf,
                                                batch_dims=1, cast=False)
        with stages.scope("correction"):
            u_dev = jax.tree.map(
                lambda u, dl: u + rho * dl[:, None].astype(u.dtype),
                u_dev, delta_tree)
    # flatten in the promoted dtype over the u leaves: widening casts
    # never move a value across zero, so the signs stay bit-identical to
    # pack_tree's per-leaf-dtype arithmetic
    dt = leaves[0].dtype
    for leaf in leaves[1:]:
        dt = jnp.promote_types(dt, leaf.dtype)
    with stages.scope("relayout"):
        u_buf = flatbuf.flatten_tree(layout, u_dev, batch_dims=2, dtype=dt)
        if not jnp.issubdtype(u_buf.dtype, jnp.floating):
            # EF hands pre-signed int8 trees in; the kernels take float
            # blocks (int8 VMEM tiling differs), and +-1 casts exactly.
            u_buf = u_buf.astype(jnp.float32)
        d_buf = None
        if fold_in_kernel:
            d_buf = (delta_buf.astype(u_buf.dtype) if delta_buf is not None
                     else flatbuf.flatten_tree(layout, delta_tree,
                                               batch_dims=1,
                                               dtype=u_buf.dtype))
    return u_buf, d_buf


def _per_rank(topo: Topology, layout: flatbuf.FlatLayout, mode: str) -> bool:
    """Run the fused chain as the per-rank ``shard_map`` program?

    Always for a model-sharded layout; and whenever the kernels run on
    a multi-device mesh -- they are single-device programs, so every
    rank must run its own (GSPMD cannot partition a Pallas call)."""
    return layout.shards > 1 or (topo.mesh.size > 1 and mode != "jnp")


def _packed_vote(topo, layout, u_dev, delta_tree, rho, mask):
    """jnp route: per-leaf fused pack (correction pre-sign), ONE
    data-axis gather of the 1-bit payload, one popcount -> [P, n_pad]."""
    n_dev = layout.treedef.flatten_up_to(u_dev)[0].shape[1]
    words = flatbuf.pack_tree(layout, u_dev, batch_dims=2,
                              delta=delta_tree, rho=rho,
                              delta_batch_dims=1)
    # the device->edge uplink: all-gather the 1-bit payload over 'data'
    with stages.scope("vote_exchange"):
        words = topo.constrain(words,
                               P(topo.pod_axis, topo.data_axis, None))
        words = topo.constrain(words, P(topo.pod_axis, None, None))
    with stages.scope("vote_update"):
        return _popcount_vote_words(words, mask, n_dev)


def _fused_shard_map(topo: Topology, layout: flatbuf.FlatLayout, u_dev,
                     delta_tree, delta_buf, rho: float,
                     mask: jax.Array | None, v_buf: jax.Array | None,
                     mu, mu_static: float | None):
    """The multi-chip fused transport: ONE shard_map program per step.

    Per rank (pod p, device d, model shard m): fuse the DC correction
    pre-sign and pack the rank's own bucket of the sharded flatbuf
    layout (Pallas ``sign_pack`` on TPU, pure-jnp elsewhere -- same
    arithmetic as the unsharded path per coordinate), all-gather the
    packed words over the ``data`` axis -- the only collective in the
    program, 1 bit/coordinate of the LOCAL shard -- then popcount-vote
    and (when ``v_buf`` is given) apply ``v <- v - mu*vote`` on the
    local bucket via the ``vote_update`` read-modify-write.  No leaf is
    ever gathered over ``model`` and no unsharded bit tensor exists.

    Returns the updated [P, n_pad] buffer when ``v_buf`` is given, else
    the per-pod vote as a [P, *leaf] int8 pytree (unflattened inside
    the program; sharded leaves come back model-sharded on their
    ``shard_dim``, per-bucket copies replicated -- every rank computes
    the identical vote for them by construction).

    Uneven sharded leaves enter and leave the program in their padded
    shapes (``flatbuf.pad_tree`` / ``unpad_tree``): the zero tail packs
    to +1 sign bits -- the standard don't-care padding -- and is sliced
    off any returned vote tree, so callers only ever see logical
    extents.
    """
    bucket = layout.bucket()
    u_dev = flatbuf.pad_tree(layout, u_dev, 2)
    if delta_tree is not None:
        delta_tree = flatbuf.pad_tree(layout, delta_tree, 1)
    mode = kops.fused_kernel_mode()
    use_kernel = mode in ("pallas", "interpret")
    interpret = mode == "interpret"
    want_update = v_buf is not None
    fold_mu = (want_update and use_kernel and mu_static is not None
               and v_buf.dtype == jnp.float32)

    names = ["u"]
    args = [u_dev]
    in_specs = [shardflat.leaf_specs(topo, layout, 2)]
    if delta_tree is not None and rho:
        names.append("dt")
        args.append(delta_tree)
        in_specs.append(shardflat.leaf_specs(topo, layout, 1))
    if delta_buf is not None and rho:
        names.append("db")
        args.append(delta_buf)
        in_specs.append(shardflat.buf_spec(topo, layout, 1))
    if mask is not None:
        names.append("mask")
        args.append(mask)
        in_specs.append(P(topo.pod_axis, None))
    if want_update:
        names.append("v")
        args.append(v_buf)
        in_specs.append(shardflat.buf_spec(topo, layout, 1))
        if not fold_mu:
            names.append("mu")
            args.append(mu)
            in_specs.append(P())

    def program(*local):
        kw = dict(zip(names, local))
        u_l, dt_l, db_l = kw["u"], kw.get("dt"), kw.get("db")
        m_l, v_l = kw.get("mask"), kw.get("v")
        if use_kernel:
            u2, d2 = _fused_kernel_bufs(bucket, u_l, dt_l, db_l, rho)
            words = kops.fused_pack_flat(u2, d2, rho, interpret=interpret)
        else:
            if db_l is not None:
                dt_l = flatbuf.unflatten_tree(bucket, db_l, batch_dims=1,
                                              cast=False)
            words = flatbuf.pack_tree(bucket, u_l, batch_dims=2,
                                      delta=dt_l, rho=rho,
                                      delta_batch_dims=1)
        # the device->edge uplink: gather the 1-bit payload over 'data'
        with stages.scope("vote_exchange"):
            words = jax.lax.all_gather(words, topo.data_axis, axis=1,
                                       tiled=True)
        if fold_mu:
            return kops.fused_vote_update_words(
                words, v_l, m_l, float(mu_static), interpret=interpret)
        with stages.scope("vote_update"):
            if use_kernel:
                vote = kops.fused_vote_update_words(
                    words, None, m_l, -1.0, interpret=interpret
                ).astype(jnp.int8)
            else:
                # post-gather the voter axis holds every (virtual)
                # client: its extent is the correct unmasked quorum size
                vote = _popcount_vote_words(words, m_l, words.shape[1])
            if want_update:
                return v_l - kw["mu"] * vote.astype(v_l.dtype)
        return flatbuf.unflatten_tree(bucket, vote, batch_dims=1,
                                      cast=False)

    out_specs = (shardflat.buf_spec(topo, layout, 1) if want_update
                 else shardflat.leaf_specs(topo, layout, 1))
    fn = jax.shard_map(program, mesh=topo.mesh, in_specs=tuple(in_specs),
                       out_specs=out_specs, check_vma=False)
    out = fn(*args)
    if want_update:
        return out
    return flatbuf.unpad_tree(layout, out, 1)


def fused_sign_vote(topo: Topology, u_dev, delta=None, rho: float = 0.0,
                    mask: jax.Array | None = None, specs=None):
    """Whole-model fused sign transport: pytree in, vote pytree out.

    u_dev: pytree of [P, D, *leaf] pre-sign directions (gradients after
    momentum/EF; the voter axis may be the merged virtual-client axis
    [P, D*K, *leaf] of ``core.clients``); delta: optional pytree of
    [P, *leaf] DC corrections, fused pre-sign as ``u + rho * delta``
    exactly like the per-leaf path; mask: optional [P, D] voter mask or
    integer vote weights (weighted popcount, empty quorum abstains).
    Returns the per-pod vote pytree ([P, *leaf] int8), bit-identical to
    ``ag_packed``/``ar_int8`` applied leaf-wise (ties -> +1).

    Chain: per-leaf fused sign+pack into ONE contiguous word buffer
    (``flatbuf`` layout; the f32 flat buffer is never materialized on the
    jnp path), one data-axis gather of the packed words, one popcount
    vote.  On TPU the local sweeps instead run the Pallas kernels over
    the flat view (``kernels.ops``), per rank under ``shard_map`` when
    the mesh has more than one device (:func:`_fused_shard_map`).

    specs: optional per-leaf PartitionSpec pytree (leaf dims).  On a
    mesh with a >1 model axis this switches to the sharded flatbuf
    layout + shard_map program: TP-sharded leaves stay sharded end to
    end.
    """
    mode = kops.fused_kernel_mode()
    layout = None
    if specs is not None and topo.model_shards > 1:
        layout = flatbuf.make_layout(
            u_dev, batch_dims=2,
            sharding=shardflat.model_sharding(topo, specs))
    if layout is None or layout.shards == 1:
        layout = flatbuf.make_layout(u_dev, batch_dims=2)
    if _per_rank(topo, layout, mode):
        return _fused_shard_map(topo, layout, u_dev, delta, None, rho,
                                mask, None, None, None)
    if mode in ("pallas", "interpret"):
        u_buf, d_buf = _fused_kernel_bufs(layout, u_dev, delta, None, rho)
        vote = kops.fused_sign_vote_flat(
            u_buf, d_buf, rho, mask, interpret=(mode == "interpret"))
    else:
        vote = _packed_vote(topo, layout, u_dev, delta, rho, mask)
    vote = topo.constrain(vote, P(topo.pod_axis, None))
    return flatbuf.unflatten_tree(layout, vote, batch_dims=1, cast=False)


def fused_sign_vote_update(topo: Topology, layout: flatbuf.FlatLayout,
                           u_dev, delta_buf: jax.Array | None,
                           rho: float, mask: jax.Array | None,
                           v_buf: jax.Array, mu,
                           mu_static: float | None = None) -> jax.Array:
    """Flat-state fused transport: ``v_buf <- v_buf - mu * vote`` whole-model.

    u_dev: pytree of [P, D, *leaf] pre-sign directions (uniform dtype;
    D may be the merged virtual-client axis D*K); delta_buf: optional
    [P, n_pad] DC correction buffer (delta dtype); mask: optional
    [P, D] voter mask or integer vote weights (weighted popcount, empty
    quorum abstains -> that edge's buffer is untouched this step);
    v_buf: [P, n_pad] master buffer; mu: traced step-size scalar;
    mu_static: the Python value of mu when it is step-independent -- lets
    the Pallas route fold the update into the ``vote_update`` kernel
    (ONE read-modify-write HBM pass over the whole model, no per-leaf
    dispatch).  Votes are bit-identical to :func:`fused_sign_vote` and
    the update arithmetic matches the tree-state per-leaf
    ``v - mu*vote.astype(v.dtype)`` exactly.

    A sharded ``layout`` (``layout.shards > 1``, from
    ``flatbuf.make_layout(..., sharding=...)``) routes through the
    shard_map program (:func:`_fused_shard_map`): the buffer stays
    model-axis sharded for the whole read-modify-write.
    """
    mode = kops.fused_kernel_mode()
    if _per_rank(topo, layout, mode):
        new_v = _fused_shard_map(topo, layout, u_dev, None, delta_buf,
                                 rho, mask, v_buf, mu, mu_static)
        return topo.constrain(new_v, shardflat.buf_spec(topo, layout, 1))
    if mode in ("pallas", "interpret"):
        u_buf, d_buf = _fused_kernel_bufs(layout, u_dev, None, delta_buf,
                                          rho)
        interpret = (mode == "interpret")
        if mu_static is not None and v_buf.dtype == jnp.float32:
            # the kernel updates in f32: exact vs the tree path only for
            # f32 masters (mu_static rounds identically)
            new_v = kops.fused_vote_update_flat(
                u_buf, d_buf, rho, mask, v_buf, float(mu_static),
                interpret=interpret)
        else:
            vote = kops.fused_sign_vote_flat(u_buf, d_buf, rho, mask,
                                             interpret=interpret)
            with stages.scope("vote_update"):
                new_v = v_buf - mu * vote.astype(v_buf.dtype)
    else:
        delta_tree = (flatbuf.unflatten_tree(layout, delta_buf,
                                             batch_dims=1, cast=False)
                      if delta_buf is not None and rho else None)
        vote = _packed_vote(topo, layout, u_dev, delta_tree, rho, mask)
        with stages.scope("vote_update"):
            new_v = v_buf - mu * vote.astype(v_buf.dtype)
    return topo.constrain(new_v, P(topo.pod_axis, None))


def majority_vote_dev(topo: Topology, s_dev: jax.Array,
                      mask: jax.Array | None, transport: str,
                      leaf_spec: P,
                      weight_bound: int | None = None) -> jax.Array:
    """Vote [P, D, *leaf] -> [P, *leaf]; dispatch on transport + leaf shape.

    ``mask`` may carry integer vote weights (see the per-transport
    docs); ``weight_bound`` is the static per-edge tally range for the
    int-tally transport's dtype promotion (None = unit weights).

    Per-leaf callers (FSDP lift) route ``fused`` to ``ag_packed`` -- the
    flat-buffer chain only pays off when the whole tree is bucketized.
    """
    if (transport in ("ag_packed", "fused")
            and s_dev.shape[-1] % PACK == 0):
        return vote_ag_packed(topo, s_dev, mask, leaf_spec)
    return vote_ar_int8(topo, s_dev, mask, weight_bound=weight_bound)


def weighted_mean_dev(topo: Topology, g_dev: jax.Array,
                      dev_weights: jax.Array, clients: int = 1) -> jax.Array:
    """Full-precision edge aggregation  sum_k (|D_qk|/D_q) g_k  -> [P, *leaf].

    clients: with K > 1 merged virtual clients the voter-axis reduction
    is re-associated as a zeros-initialized ``fori_loop`` fold over each
    slice's K clients (multiply INSIDE the loop body, so XLA emits the
    same mul+add per iteration) followed by the device sum -- the EXACT
    float op order the streamed client sweep
    (``ClientConfig.mode="stream"``) produces with its ``fori_loop``
    accumulator, so the two modes stay bitwise identical on the
    full-precision aggregations (anchor pass, mean methods) too.  A
    Python-unrolled chain is NOT equivalent: XLA compiles the unrolled
    adds (and a hoisted multiply) with different rounding than the loop
    body.  ``clients=1`` is the original single ``jnp.sum``.
    """
    if clients <= 1:
        w = dev_weights.reshape(dev_weights.shape + (1,) * (g_dev.ndim - 2))
        return jnp.sum(g_dev * w.astype(g_dev.dtype), axis=1)
    p, dk = g_dev.shape[:2]
    g3 = g_dev.reshape((p, dk // clients, clients) + g_dev.shape[2:])
    w3 = dev_weights.reshape(p, dk // clients, clients)

    def body(c, acc):
        g_c = jax.lax.dynamic_index_in_dim(g3, c, axis=2, keepdims=False)
        w_c = jax.lax.dynamic_index_in_dim(w3, c, axis=2, keepdims=False)
        w_c = w_c.reshape(w_c.shape + (1,) * (g_c.ndim - 2))
        return acc + g_c * w_c.astype(g_c.dtype)

    acc = jax.lax.fori_loop(
        0, clients, body,
        jnp.zeros(g3.shape[:2] + g3.shape[3:], g_dev.dtype))
    return jnp.sum(acc, axis=1)


# ---------------------------------------------------------------------------
# Streamed client sweep: per-leaf and fused tally entry points
# ---------------------------------------------------------------------------

def tally_vote_dev(topo: Topology, tally: jax.Array, n_eff: jax.Array,
                   leaf_spec: P) -> jax.Array:
    """[P, D, *leaf] streamed per-device tally -> [P, *leaf] int8 vote.

    The data-axis reduction of the streamed sweep: the int tally is the
    per-step uplink payload (ONE device-axis reduction per local step,
    not per client), summed in int32 and thresholded by
    :func:`tally_vote`.  Integer associativity makes the result bitwise
    identical to the merged-axis weighted popcount of any transport.
    """
    t = topo.constrain(tally, topo.dev_spec(*leaf_spec))
    ts = jnp.sum(t.astype(jnp.int32), axis=1)                  # [P, *leaf]
    return tally_vote(ts, n_eff)


def fused_sign_tally_accumulate(topo: Topology, layout: flatbuf.FlatLayout,
                                u_dev, delta_tree, delta_buf,
                                rho: float, weights: jax.Array,
                                tally: jax.Array) -> jax.Array:
    """Streamed-client device-side half of the fused transport: fold ONE
    client's (DC-corrected) signs into the persistent tally buffer.

    u_dev: pytree of [P, D, *leaf] pre-sign directions of the CURRENT
    client (physical device axis D, never the merged D*K); delta_tree /
    delta_buf: optional DC correction ([P, *leaf] tree or [P, n_pad]
    buffer), fused pre-sign exactly like :func:`fused_sign_vote`;
    weights: [P, D] integer vote weights of this client this round;
    tally: lane-row [P, D, n_pad/128, 128] signed tally
    (``tally_dtype(weight_bound)``).  Returns the updated tally in the
    same shape.  No collective runs here -- the data
    exchange of the streamed sweep happens once per local step in
    :func:`fused_tally_finish`.

    On TPU the pack -> weighted sign -> tally read-modify-write is ONE
    Pallas sweep (``kernels.tally_acc``, aliased in place when
    compiled); elsewhere the bit-identical jnp route packs via
    ``flatbuf.pack_tree`` and accumulates with
    :func:`tally_accumulate_words`.  A sharded layout (``layout.shards
    > 1``) runs the same per-rank program under shard_map on each
    rank's bucket.
    """
    mode = kops.fused_kernel_mode()
    if _per_rank(topo, layout, mode):
        return _tally_acc_shard_map(topo, layout, u_dev, delta_tree,
                                    delta_buf, rho, weights, tally)
    if mode in ("pallas", "interpret"):
        u_buf, d_buf = _fused_kernel_bufs(layout, u_dev, delta_tree,
                                          delta_buf, rho)
        return kops.fused_tally_acc_flat(u_buf, d_buf, rho, weights, tally,
                                         interpret=(mode == "interpret"))
    if delta_buf is not None and rho:
        delta_tree = flatbuf.unflatten_tree(layout, delta_buf, batch_dims=1,
                                            cast=False)
    words = flatbuf.pack_tree(layout, u_dev, batch_dims=2, delta=delta_tree,
                              rho=rho, delta_batch_dims=1)
    with stages.scope("tally_acc"):
        return tally_accumulate_words(words, weights, tally)


def _tally_acc_shard_map(topo: Topology, layout: flatbuf.FlatLayout, u_dev,
                         delta_tree, delta_buf, rho: float,
                         weights: jax.Array, tally: jax.Array) -> jax.Array:
    """Per-client accumulate of the sharded streamed fused path.

    One shard_map program with ZERO collectives: rank (p, d, m) packs
    its own model-axis bucket of this client's directions and folds the
    weighted signs into its local [1, 1, bucket_pad/128, 128] tally block.
    """
    bucket = layout.bucket()
    u_dev = flatbuf.pad_tree(layout, u_dev, 2)
    if delta_tree is not None:
        delta_tree = flatbuf.pad_tree(layout, delta_tree, 1)
    mode = kops.fused_kernel_mode()
    use_kernel = mode in ("pallas", "interpret")
    interpret = mode == "interpret"

    names = ["u", "t", "w"]
    args = [u_dev, tally, weights]
    in_specs = [shardflat.leaf_specs(topo, layout, 2),
                shardflat.buf_spec(topo, layout, 2),
                P(topo.pod_axis, topo.data_axis)]
    if delta_tree is not None and rho:
        names.append("dt")
        args.append(delta_tree)
        in_specs.append(shardflat.leaf_specs(topo, layout, 1))
    if delta_buf is not None and rho:
        names.append("db")
        args.append(delta_buf)
        in_specs.append(shardflat.buf_spec(topo, layout, 1))

    def program(*local):
        kw = dict(zip(names, local))
        u_l, t_l, w_l = kw["u"], kw["t"], kw["w"]
        dt_l, db_l = kw.get("dt"), kw.get("db")
        if use_kernel:
            u2, d2 = _fused_kernel_bufs(bucket, u_l, dt_l, db_l, rho)
            return kops.fused_tally_acc_flat(u2, d2, rho, w_l, t_l,
                                             interpret=interpret)
        if db_l is not None:
            dt_l = flatbuf.unflatten_tree(bucket, db_l, batch_dims=1,
                                          cast=False)
        words = flatbuf.pack_tree(bucket, u_l, batch_dims=2, delta=dt_l,
                                  rho=rho, delta_batch_dims=1)
        with stages.scope("tally_acc"):
            return tally_accumulate_words(words, w_l, t_l)

    fn = jax.shard_map(program, mesh=topo.mesh, in_specs=tuple(in_specs),
                       out_specs=shardflat.buf_spec(topo, layout, 2),
                       check_vma=False)
    return fn(*args)


def fused_tally_finish(topo: Topology, layout: flatbuf.FlatLayout,
                       tally: jax.Array, n_eff: jax.Array,
                       v_buf: jax.Array | None, mu):
    """Edge-side half of the streamed fused transport: reduce the
    per-device tallies over ``data`` ONCE per local step, defer-threshold
    into the vote, and optionally apply ``v <- v - mu*vote``.

    tally: lane-row [P, D, n_pad/128, 128] accumulated signed tallies (all K clients folded in); n_eff: [P]
    int32 participating weight sum of the round.
    With ``v_buf`` (flat state) returns the updated [P, n_pad] buffer;
    without it returns the vote as a [P, *leaf] int8 pytree -- mirroring
    :func:`fused_sign_vote_update` / :func:`fused_sign_vote`.

    A sharded layout runs as ONE shard_map program whose only
    collective is the data-axis all-gather of the (already
    client-reduced) local tallies -- the streamed analogue of the
    merged path's packed-word gather.
    """
    if layout.shards > 1:
        bucket = layout.bucket()
        want_update = v_buf is not None
        names = ["t", "n"]
        args = [tally, n_eff]
        in_specs = [shardflat.buf_spec(topo, layout, 2), P(topo.pod_axis)]
        if want_update:
            names += ["v", "mu"]
            args += [v_buf, mu]
            in_specs += [shardflat.buf_spec(topo, layout, 1), P()]

        def program(*local):
            kw = dict(zip(names, local))
            # the ONE per-step collective of the streamed sweep
            with stages.scope("vote_exchange"):
                t = jax.lax.all_gather(kw["t"], topo.data_axis, axis=1,
                                       tiled=True)
            with stages.scope("tally_finish"):
                ts = jnp.sum(t.astype(jnp.int32), axis=1)
                vote = tally_vote(ts, kw["n"]).reshape(ts.shape[0], -1)
                if want_update:
                    return kw["v"] - kw["mu"] * vote.astype(kw["v"].dtype)
            return flatbuf.unflatten_tree(bucket, vote, batch_dims=1,
                                          cast=False)

        out_specs = (shardflat.buf_spec(topo, layout, 1) if want_update
                     else shardflat.leaf_specs(topo, layout, 1))
        fn = jax.shard_map(program, mesh=topo.mesh,
                           in_specs=tuple(in_specs), out_specs=out_specs,
                           check_vma=False)
        out = fn(*args)
        if want_update:
            return topo.constrain(out, shardflat.buf_spec(topo, layout, 1))
        return flatbuf.unpad_tree(layout, out, 1)
    # the device->edge uplink: gather the int tallies over 'data'
    with stages.scope("vote_exchange"):
        t = topo.constrain(tally, P(topo.pod_axis, topo.data_axis, None))
        t = topo.constrain(t, P(topo.pod_axis, None, None))
    with stages.scope("tally_finish"):
        ts = jnp.sum(t.astype(jnp.int32), axis=1)
        vote = tally_vote(ts, n_eff).reshape(ts.shape[0], -1)  # [P, n_pad]
        vote = topo.constrain(vote, P(topo.pod_axis, None))
        if v_buf is not None:
            return topo.constrain(v_buf - mu * vote.astype(v_buf.dtype),
                                  P(topo.pod_axis, None))
    return flatbuf.unflatten_tree(layout, vote, batch_dims=1, cast=False)


# ---------------------------------------------------------------------------
# Pod (edge -> cloud) tier
# ---------------------------------------------------------------------------

def pod_weighted_average(topo: Topology, v: jax.Array,
                         edge_weights: jax.Array) -> jax.Array:
    """Cloud aggregation  w = sum_q (D_q/N) v_q, broadcast back to [P, ...].

    v: [P, *leaf].  Lowers to a pod-axis all-reduce (the edge->cloud model
    exchange, every T_E steps).
    """
    w = edge_weights.reshape((-1,) + (1,) * (v.ndim - 1)).astype(v.dtype)
    glob = jnp.sum(v * w, axis=0, keepdims=True)               # [1, *leaf]
    return jnp.broadcast_to(glob, v.shape)
