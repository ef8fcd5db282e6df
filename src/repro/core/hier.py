"""Distributed HierSignSGD / DC-HierSignSGD train steps (the paper's core).

Step semantics (bit-equivalent to Algorithms 1/2, validated against
``repro.core.ref_fed``): each ``train_step`` call is one local step tau.
At a round boundary (step % T_E == 0) a prologue first runs

  1. cloud aggregation  v_q <- sum_q (D_q/N) v_q   (pod-axis all-reduce) --
     this is Alg. 1/2's end-of-round step folded into the next step's
     prologue (identical trajectory, single uniform step function), and
  2. (DC only) the anchor pass: c_q = sum_k (|D_qk|/D_q) grad f_qk(w),
     c = sum_q (D_q/N) c_q, delta_q = c - c_q.  With
     ``anchor_staleness=1`` (paper's pipelined variant) the freshly
     computed delta is *staged* and the previous round's delta is used, so
     devices at round t correct with c^(t-1) - c_q^(t-1) exactly as in
     Alg. 2; ``anchor_staleness=0`` is the fresh variant (extra cross-pod
     sync before local steps, no staging buffer).

The WHEN of step 1 is the cloud sync schedule (``core.schedule``,
selected by ``AlgoConfig.cloud_overlap``): ``"sync"`` issues and
commits the aggregate at the same boundary (the paper's barrier,
above); ``"overlap"`` commits the aggregate issued at the PREVIOUS
boundary and stages the fresh one in ``TrainState.agg_next`` -- edges
keep local-stepping on their local models while the cross-pod mean is
in flight, and the DC/SCAFFOLD/MTGC anchors refresh at the committed
(one-round-stale) aggregate.  Commit weights are pinned to issue-time
membership, so churn mid-flight is well-defined.

Then the local step: per-device grads -> (+ rho*delta, + EF residual) ->
sign -> majority vote over the ``data`` axis -> v_q <- v_q - mu * vote.
With an *active* ``AlgoConfig.clients`` (``core.clients``) the voter
axis is the merged virtual-client axis [P, D*K, ...]: batches are
carved per client, a per-round sampled participation mask and integer
data shares |D_qk| turn the vote into a weighted popcount (empty quorum
abstains), and the anchor/mean aggregations reweight to the
participating shares.  The inactive default is bitwise the legacy step.
``ClientConfig.mode="stream"`` runs the same round as a ``fori_loop``
over clients inside the step (``local_step_stream``): each client's
weighted sign plane folds into a persistent integer tally
(``votes.tally_*``) and the majority threshold is deferred until after
the loop -- O(model/32 + tally) live sign-plane memory instead of
O(K*model), bitwise identical to the merged axis on every cell.
With ``transport="fused"`` the sign/vote chain runs over ONE contiguous
flat buffer (``core.flatbuf`` layout, DC correction fused pre-sign,
Pallas kernels on TPU) instead of per-leaf tree maps -- bit-identical
votes, one gather (see the transport matrix in ``core.votes``).

Methods: hier_signsgd | dc_hier_signsgd | scaffold_hier_signsgd |
mtgc_hier_signsgd | hier_sgd | hier_local_qsgd, plus beyond-paper
options (error feedback, sign-momentum) in the replicated regime.
The scaffold/mtgc methods put alternative drift corrections in the same
pre-sign slot as DC: SCAFFOLD per-client control variates
(sgn(g + rho*(c_global - c_local_qk))) and MTGC's multi-timescale terms
(sgn(g + rho*(gamma_qk + eta_q)), edge term every round / cloud term
every ``cloud_period`` rounds) -- state in the corr_cl/corr_edge slots,
refreshed fresh at each round boundary (``compute_corrections``),
replicated regime only.

Regimes:
  * replicated: per-device grads are explicit ([P, D, ...] arrays) --
    supports every method + EF + momentum.
  * fsdp: the vote happens inside backprop via ``fsdp_lift`` and autodiff
    returns per-pod directions directly (sign methods + hier_sgd).

State layouts (``AlgoConfig.state_layout``): ``tree`` keeps the master
params as a pytree and applies updates per leaf; ``flat`` stores the
master (and delta / EF / momentum) AS the ``core.flatbuf`` buffer for the
entire run, materializing leaf views only at the loss boundary -- the
whole-model update is then one elementwise sweep, and under
``transport="fused"`` a single ``vote_update`` read-modify-write.  On a
mesh with a >1 model axis the flat buffer uses the *sharded* layout
(per-model-shard buckets) and every tree<->buffer move runs as a
``shard_map`` program (``core.shardflat``), so TP-sharded leaves are
never gathered -- the buffer lives model-axis sharded end to end, and
uneven extents (a model-sharded dim that does not divide the axis)
stay sharded too via the layout's padded blocks (``flatbuf`` padded-
shard rule; the zero tail is don't-care).  Both layouts are
bit-identical in trajectory (tests/test_parity_matrix.py, including
the uneven-leaf cell of the 8-device tier).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import clients as vclients
from repro.core import (device_axis, flatbuf, schedule, shardflat, signs,
                        stages, votes)
from repro.core.device_axis import LiftCfg
from repro.core.topology import Topology

PyTree = Any

SIGN_METHODS = ("hier_signsgd", "dc_hier_signsgd", "scaffold_hier_signsgd",
                "mtgc_hier_signsgd")
# methods whose clients apply a per-client control-variate / multi-
# timescale correction in the pre-sign slot (state: corr_cl + corr_edge)
CLIENT_CORRECTION_METHODS = ("scaffold_hier_signsgd", "mtgc_hier_signsgd")
ALL_METHODS = SIGN_METHODS + ("hier_sgd", "hier_local_qsgd")


@dataclasses.dataclass(frozen=True)
class AlgoConfig:
    method: str = "dc_hier_signsgd"
    mu: float = 1e-3                  # sign-step size
    mu_sgd: float = 0.1               # full-precision baseline step size
    t_e: int = 15                     # local steps per global round
    rho: float = 0.2                  # correction strength (DC)
    transport: str = "ag_packed"      # ag_packed (faithful) | ar_int8
                                      # | fused (flat-buffer, Pallas-backed)
    state_layout: str = "tree"        # tree (pytree master) | flat (master
                                      # lives AS the core.flatbuf buffer;
                                      # replicated regime only)
    anchor_staleness: int = 1         # 1 = paper's pipelined delta, 0 = fresh
                                      # (DC only; scaffold/mtgc corrections
                                      # are always refreshed fresh at the
                                      # round boundary)
    cloud_period: int = 2             # MTGC slow timescale: the cloud-level
                                      # eta term refreshes every cloud_period
                                      # rounds (the edge-level gamma term
                                      # refreshes every round)
    cloud_overlap: str = "sync"       # cloud sync schedule (core.schedule):
                                      # "sync" = issue+commit at the same
                                      # round boundary (the paper's barrier);
                                      # "overlap" = edges keep local-stepping
                                      # on their local models while the
                                      # cross-pod mean is in flight, commit
                                      # one boundary later (staged agg_next
                                      # slot; anchors refresh at the
                                      # committed, one-round-stale aggregate)
    clients: vclients.ClientConfig = vclients.ClientConfig()
                                      # virtual-client scale-out: K clients
                                      # per data slice, per-round sampling,
                                      # |D_qk| vote weights (replicated
                                      # regime only; the inactive default
                                      # is bitwise the legacy step)
    error_feedback: bool = False      # beyond-paper (replicated regime only)
    momentum: float = 0.0             # beyond-paper signum-style momentum
    compute_dtype: Any = jnp.bfloat16
    master_dtype: Any = jnp.float32
    delta_dtype: Any = jnp.bfloat16
    decay: bool = False               # mu_t = mu / sqrt(round + 1)

    def __post_init__(self):
        if self.method not in ALL_METHODS:
            raise ValueError(
                f"unknown method {self.method!r} (choose from "
                f"{', '.join(ALL_METHODS)})")
        if self.transport not in votes.SIGN_TRANSPORTS:
            raise ValueError(f"unknown transport {self.transport!r}")
        if self.state_layout not in ("tree", "flat"):
            raise ValueError(f"unknown state_layout {self.state_layout!r}")
        if self.cloud_period < 1:
            raise ValueError(
                f"cloud_period must be >= 1, got {self.cloud_period}")
        if self.cloud_overlap not in schedule.CLOUD_OVERLAP_MODES:
            raise ValueError(
                f"unknown cloud_overlap {self.cloud_overlap!r} (choose "
                f"from {', '.join(schedule.CLOUD_OVERLAP_MODES)})")

    @property
    def is_sign(self) -> bool:
        return self.method in SIGN_METHODS

    @property
    def is_dc(self) -> bool:
        return self.method == "dc_hier_signsgd"

    @property
    def is_scaffold(self) -> bool:
        return self.method == "scaffold_hier_signsgd"

    @property
    def is_mtgc(self) -> bool:
        return self.method == "mtgc_hier_signsgd"

    @property
    def has_client_correction(self) -> bool:
        """Per-client correction state in the pre-sign slot (corr_cl +
        corr_edge buffers): SCAFFOLD control variates or MTGC's
        multi-timescale terms."""
        return self.method in CLIENT_CORRECTION_METHODS

    @property
    def is_overlap(self) -> bool:
        return self.cloud_overlap == "overlap"

    @property
    def cloud_schedule(self) -> schedule.CloudSchedule:
        """The cloud sync schedule (issue/commit latency) this config
        selects -- the SAME object the ``ref_fed`` oracle consumes."""
        return schedule.CloudSchedule.from_mode(self.cloud_overlap)


class TrainState(NamedTuple):
    """Training state.  With ``state_layout="flat"`` the params / delta /
    ef / mom / corr entries are ``flatbuf.FlatState`` buffers ([P, n_pad]
    and [P, D, n_pad]) instead of pytrees; each optional entry is ``None``
    whenever the method / options do not read it (DC correction only for
    ``dc_hier_signsgd`` or the FSDP regime's lift plumbing; corr_cl /
    corr_edge only for the scaffold/mtgc client-correction methods)."""
    step: jax.Array                   # global step counter (t * T_E + tau)
    params: PyTree                    # [P, ...] per-pod edge models v_q
    agg_next: PyTree | None           # [P, ...] staged in-flight cloud
                                      #   aggregate (cloud_overlap=
                                      #   "overlap" only: issued at the
                                      #   previous boundary, committed at
                                      #   the next; FlatState [P, n_pad]
                                      #   under state_layout="flat")
    delta: PyTree | None              # [P, ...] active correction c - c_q
    delta_next: PyTree | None         # staged delta (anchor_staleness=1)
    ef: PyTree | None                 # [P, D*K, ...] error-feedback residual
    mom: PyTree | None                # [P, D*K, ...] sign-momentum buffer
    corr_cl: PyTree | None            # [P, D*K, ...] per-client correction:
                                      #   scaffold c_local / mtgc gamma_qk
    corr_edge: PyTree | None          # [P, ...] per-edge correction term:
                                      #   scaffold c_global (one pod-
                                      #   replicated copy) / mtgc eta_q
    rng: jax.Array                    # (K = clients per slice; K=1 default)


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    """What a model must provide to train under the hierarchy.

    loss(params, batch, rng) -> scalar  -- mean loss of ONE replica on ONE
        device batch (no leading P/D dims); cotangents through it are the
        paper's per-device gradients.
    compute_specs -- per-leaf PartitionSpec of the *leaf* dims during
        compute (TP layout).
    master_specs  -- per-leaf PartitionSpec of the master storage (equal to
        compute_specs in the replicated regime; includes 'data' for FSDP).
    loss_master(params, delta, batch, rngs, lift) -> (sum_loss, aux) --
        FSDP regime only: model applies ``lift`` per layer inside its scan.
    """
    loss: Callable[[PyTree, Any, jax.Array], jax.Array] | None
    compute_specs: PyTree
    master_specs: PyTree
    loss_master: Callable | None = None
    param_mode: str = "replicated"    # replicated | fsdp


def _bcast_pd(topo: Topology, tree: PyTree, specs: PyTree, dtype,
              devices: int | None = None) -> PyTree:
    return device_axis.broadcast_devices(topo, tree, specs, dtype,
                                         devices=devices)


def make_hier_step(topo: Topology, algo: AlgoConfig, bundle: ModelBundle,
                   sync: str = "cond"):
    """Build (init_fn, train_step).

    train_step(state, batch, edge_weights, dev_weights, dev_mask)
        -> (state, metrics)

    batch: {'train': pytree of [P, D, b, ...], 'anchor': optional same}.
    edge_weights: [P] = D_q/N;  dev_weights: [P, D] = |D_qk|/D_q;
    dev_mask: [P, D] float in {0,1} -- vote quorum / straggler mask --
        or, with an ACTIVE ``algo.clients``, optionally [P, D, K] per
        virtual client (the elastic Membership's client-granular
        liveness; multiplied into the per-round participation mask, so
        churn is a runtime value change, never a retrace).

    Virtual clients (``algo.clients``, replicated regime only): when the
    ClientConfig is *active*, each physical slice hosts K virtual
    clients -- the device batch is carved into K per-client shards and
    the client dim merges into the voter axis ([P, D*K, b/K, ...], a
    local reshape; ``core.clients``).  A per-round participation mask
    (pinned to (seed, step // T_E)) combines with ``dev_mask`` and with
    the config's integer data shares |D_qk| into (a) the weighted
    majority-vote weights -- tally range sum(w), empty quorum abstains
    -- and (b) the anchor/mean aggregation shares, renormalized to the
    participating clients each round (``dev_weights`` contributes the
    physical-slice factor).  The inactive default runs the exact legacy
    step: K=1 / full participation / unit weights is bitwise identical
    to the pre-virtual-client trajectory.

    sync: 'cond'  -- prologue under lax.cond on step % T_E (the driver);
          'always'/'never' -- statically include/skip the prologue (used by
          the dry-run so cost_analysis sees straight-line programs: a
          global round costs (T_E-1) x never + 1 x always).

    metrics: ``loss``, ``loss_per_pod`` [P], ``mu``, and two int32
    counts of the voter passes: ``voter_passes`` (forward and backward
    passes the step computed, P*D*K) and ``votes_cast`` (those whose
    vote weight is non-zero).  The step's work is scoped by stage
    (``core.stages``).
    """
    t_e = algo.t_e
    fsdp = bundle.param_mode == "fsdp"
    flat = algo.state_layout == "flat"
    if flat and fsdp:
        raise ValueError(
            "state_layout='flat' requires the replicated regime (the FSDP "
            "lift votes per layer shard, so the whole-model buffer never "
            "forms)")
    cc = algo.clients
    virtual = cc.active
    if virtual and fsdp:
        raise ValueError(
            "virtual clients (clients count/participation/weights) require "
            "the replicated regime: the FSDP lift votes per layer shard "
            "with physical-device masks")
    if algo.has_client_correction and fsdp:
        raise ValueError(
            f"{algo.method} requires the replicated regime: its per-client "
            "correction state (corr_cl) rides the explicit voter axis, "
            "which the FSDP lift never materializes")
    if algo.is_overlap and fsdp:
        raise ValueError(
            "cloud_overlap='overlap' requires the replicated regime: the "
            "staged in-flight aggregate (agg_next) is a whole-model master "
            "snapshot, which the FSDP lift's per-layer-shard vote never "
            "materializes")
    if algo.is_overlap and sync == "never":
        raise ValueError(
            "cloud_overlap='overlap' needs the round prologue (issue + "
            "commit run there), which sync='never' statically removes; "
            "lower the local-step phase with a cloud_overlap='sync' config "
            "instead -- the local step is schedule-independent, so the "
            "program is identical")
    cloud_sched = algo.cloud_schedule
    # the merged voter axis: K virtual clients per physical data slice
    # (d_virtual == devices_per_pod on the inactive legacy path)
    d_virtual = topo.devices_per_pod * cc.count
    # streamed client sweep: loop the K clients inside the step instead
    # of widening the voter axis -- O(model/32 + tally) live memory,
    # bitwise identical to merged (the deferred-threshold tally
    # contract, see core.votes)
    stream = virtual and cc.mode == "stream"
    # merged full-precision aggregations re-associate their voter-axis
    # reduction to the streamed fold order (weighted_mean_dev clients=),
    # so BOTH modes share one trajectory per config
    k_merge = cc.count if virtual else 1
    vote_bound = (cc.weight_bound(topo.pods, topo.devices_per_pod)
                  if virtual else None)
    # DC correction state only exists where it is read: the DC method's
    # pre-sign correction, or the FSDP lift plumbing (which threads delta
    # through the loss for every method).
    needs_delta = fsdp or algo.is_dc
    vmap2 = lambda f: jax.vmap(jax.vmap(f))

    # ---------------- gradient machinery -------------------------------
    @stages.in_stage("grads")
    def per_device_grads(params, batch, rngs, devices=None):
        """Replicated regime: explicit [P, D, ...] per-(virtual-)device
        grads (the voter axis is the merged D*K extent when virtual
        clients are active -- the batch arrives already carved; the
        streamed sweep instead passes ``devices=devices_per_pod`` and a
        single client's [P, D, b/K, ...] batch slice)."""
        v_dev = _bcast_pd(topo, params, bundle.compute_specs,
                          algo.compute_dtype,
                          devices=d_virtual if devices is None else devices)

        def tot(vd):
            losses = vmap2(bundle.loss)(vd, batch, rngs)
            return jnp.sum(losses), losses

        g_dev, losses = jax.grad(tot, has_aux=True)(v_dev)
        return g_dev, losses

    def pod_direction_fsdp(params, delta, batch, rngs, maskf, devwf,
                           transport, rho):
        """FSDP regime: autodiff returns per-pod directions (vote/wmean)."""
        cfg = LiftCfg(topo=topo, transport=transport, rho=rho,
                      compute_dtype=algo.compute_dtype)
        lift = functools.partial(device_axis.fsdp_lift_tree, cfg,
                                 maskf=maskf, devwf=devwf)

        def tot(p):
            return bundle.loss_master(p, delta, batch, rngs, lift)

        direction, losses = jax.grad(tot, has_aux=True)(params)
        return direction, losses

    def pod_avg(tree, edge_w):
        return jax.tree.map(
            lambda v: votes.pod_weighted_average(topo, v, edge_w), tree)

    # shared per-leaf pieces of the local step -- used verbatim by BOTH
    # state layouts, so the bit-identical-trajectory contract between
    # them is maintained in one place
    def quantize_dev(g_dev, rngs):
        """Per-leaf unbiased ternary quantization (leaf-indexed rngs)."""
        leaves, treedef = jax.tree.flatten(g_dev)
        qleaves = []
        for i, g in enumerate(leaves):
            rr_pd = jax.vmap(jax.vmap(
                lambda k: jax.random.fold_in(k, i)))(rngs)
            qleaves.append(jax.vmap(jax.vmap(signs.ternary_quantize))(
                g.astype(jnp.float32), rr_pd))
        return treedef.unflatten(qleaves)

    def ef_residual(u_dev, s_dev, part=None):
        """e' = u - sent, scale = per-device mean |u| per leaf.

        A participating client transmitted ``scale * s``; a client
        masked out of the round (``part`` 0, virtual path only)
        transmitted NOTHING, so its residual carries the full
        direction forward (e' = u) -- the EF compensation contract."""
        def ef_upd(u, s):
            scale = jnp.mean(jnp.abs(u), axis=tuple(range(2, u.ndim)),
                             keepdims=True)
            sent = scale * s.astype(u.dtype)
            if part is not None:
                sent = sent * part.reshape(
                    part.shape + (1,) * (u.ndim - 2)).astype(u.dtype)
            return (u - sent).astype(jnp.float32)
        return jax.tree.map(ef_upd, u_dev, s_dev)

    def vote_direction(s_dev, vote_w):
        """Per-pod vote of a pre-signed tree via the configured
        transport; ``vote_w`` is the [P, D(*K)] voter mask (legacy) or
        the combined participation x |D_qk| integer weights."""
        if algo.transport == "fused":
            return votes.fused_sign_vote(topo, s_dev, None, 0.0, vote_w,
                                         specs=bundle.compute_specs)
        return jax.tree.map(
            lambda s, cs: votes.majority_vote_dev(
                topo, s, vote_w, algo.transport, cs,
                weight_bound=vote_bound),
            s_dev, bundle.compute_specs)

    # ---------------- anchor (DC) pass ----------------------------------
    # Parity contract note: the anchor is the one FULL-PRECISION
    # statistic the state layouts share.  On multi-chip TP meshes XLA
    # fuses the (large, scanned) gradient program differently around
    # the two layouts' consumers, so real archs can pick up f32-ULP
    # differences in delta between tree and flat state -- float-level
    # equivalence, same class as the FSDP-regime tolerance.  The toy
    # parity matrix (every mesh, incl. 2x2x2 TP) is exactly bitwise:
    # per-coordinate arithmetic is identical in both layouts, only XLA
    # fusion of the backward differs (an optimization_barrier on the
    # anchor grads was tried and does not pin it).
    def compute_delta(params, delta_shaped, batch, rngs, edge_w, dev_w,
                      maskf):
        if fsdp:
            # delta_shaped: values ignored (rho=0.0 in the anchor pass);
            # only its shapes matter to the model's lift plumbing.
            c_q, _ = pod_direction_fsdp(params, delta_shaped, batch,
                                        rngs, maskf, dev_w.astype(jnp.float32),
                                        "wmean", 0.0)
        elif stream:
            # streamed anchor: the same zeros-init K-term fold as the
            # local sweep (and as merged's weighted_mean_dev clients=
            # re-association), one client's grads live at a time.
            # dev_w arrives UNmerged here: [P, D, K] participating shares.
            pt = master_views(params) if flat else params
            p, d = topo.pods, topo.devices_per_pod
            rngs3 = rngs.reshape((p, d, cc.count) + rngs.shape[2:])
            acc0 = jax.tree.map(
                lambda v, cs: topo.constrain(
                    jnp.zeros((p, d) + v.shape[1:], jnp.float32),
                    topo.dev_spec(*cs)),
                pt, bundle.compute_specs)

            def abody(c_idx, acc):
                b_c = vclients.client_slice(batch, cc.count, c_idx)
                r_c = jax.lax.dynamic_index_in_dim(rngs3, c_idx, axis=2,
                                                   keepdims=False)
                g_c, _ = per_device_grads(pt, b_c, r_c, devices=d)
                sh_c = jax.lax.dynamic_index_in_dim(dev_w, c_idx, axis=2,
                                                    keepdims=False)
                return jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) * sh_c.reshape(
                        sh_c.shape + (1,) * (g.ndim - 2)), acc, g_c)

            acc = jax.lax.fori_loop(0, cc.count, abody, acc0)
            c_q = jax.tree.map(lambda a: jnp.sum(a, axis=1), acc)
        else:
            g_dev, _ = per_device_grads(
                master_views(params) if flat else params, batch, rngs)
            c_q = jax.tree.map(
                lambda g: votes.weighted_mean_dev(
                    topo, g.astype(jnp.float32), dev_w, clients=k_merge),
                g_dev)
        c = pod_avg(c_q, edge_w)
        delta = jax.tree.map(lambda a, b: (a - b).astype(algo.delta_dtype),
                             c, c_q)
        if flat:
            # the anchor's f32 statistics stay per leaf in both layouts
            # (no f32 whole-model buffer beside the gradients); only the
            # delta the local steps fold pre-sign lands in a flat buffer
            return constrain_master(flatbuf.FlatState(
                flatten_buf(params.layout, delta, 1, algo.delta_dtype),
                flatbuf.with_dtype(params.layout, algo.delta_dtype)))
        return constrain_master(delta)

    # ---------------- scaffold / mtgc correction refresh -----------------
    def compute_corrections(params, corr_cl, corr_edge, batch, rngs,
                            edge_w, dev_w, part, rnd_index):
        """Round-boundary refresh of the pre-sign client-correction state
        at the freshly aggregated params (always fresh -- the DC staging
        knob does not apply).  Every quantity below is built from the
        anchor gradients a_qk = grad f_qk(w^t) in f32, stored back in
        ``delta_dtype``.

        scaffold (option-I control variates): a participating client sets
          c_local_qk <- a_qk
        and the shared variate absorbs the weighted drift
          c_global <- c_global + sum_q ew_q sum_k sh_qk (a_qk - c_local_qk)
        -- telescoping under full participation.  An abstaining client
        carries c_local forward (the EF contract) and its zero
        participating share drops it from the drift sum.

        mtgc (multi-timescale): the edge-level term refreshes every round,
          gamma_qk <- c_q - a_qk,   c_q = sum_k sh_qk a_qk,
        the cloud-level term only every ``cloud_period`` rounds,
          eta_q <- c - c_q,         c = sum_q ew_q c_q.
        An edge whose whole quorum abstains keeps BOTH its terms for the
        round (its c_q is the empty sum); like DC's delta, c still sums
        the abstained edges' zero c_q -- documented semantics.

        ``dev_w``/``part`` arrive like ``compute_delta``'s: merged
        [P, D*K] participating shares / vote gate, or UNmerged [P, D, K]
        on the streamed path.  ``part=None`` (legacy, non-virtual) updates
        unconditionally, mirroring EF's carry-forward contract.
        """
        dd = algo.delta_dtype
        do_cloud = (rnd_index % algo.cloud_period) == 0

        if stream:
            return _corrections_stream(params, corr_cl, corr_edge, batch,
                                       rngs, edge_w, dev_w, part, do_cloud)

        # merged voter axis: all [P, D*K, ...] anchor grads at once; the
        # flat layout runs the SAME per-coordinate arithmetic on the
        # whole-model buffer (array leaves under the same tree.maps)
        pt = master_views(params) if flat else params
        g_dev, _ = per_device_grads(pt, batch, rngs)
        if flat:
            layout = params.layout
            a32 = flatten_buf(layout, g_dev, 2, jnp.float32)
            cl_old, ce_old = corr_cl.buf, corr_edge.buf
        else:
            a32 = jax.tree.map(lambda g: g.astype(jnp.float32), g_dev)
            cl_old, ce_old = corr_cl, corr_edge
        live = (jnp.ones((topo.pods,), bool) if part is None
                else jnp.any(part, axis=1))

        def gate(fresh, old):
            if part is None:
                return fresh
            return jax.tree.map(
                lambda f, o: jnp.where(
                    part.reshape(part.shape + (1,) * (f.ndim - 2)), f, o),
                fresh, old)

        def wmean(t):
            return jax.tree.map(
                lambda x: votes.weighted_mean_dev(topo, x, dev_w,
                                                  clients=k_merge), t)

        if algo.is_scaffold:
            upd_q = wmean(jax.tree.map(
                lambda a, c: a - c.astype(jnp.float32), a32, cl_old))
            drift = pod_avg(upd_q, edge_w)
            ce_new = jax.tree.map(
                lambda e, dr: (e.astype(jnp.float32) + dr).astype(dd),
                ce_old, drift)
            cl_new = gate(jax.tree.map(lambda a: a.astype(dd), a32), cl_old)
        else:  # mtgc
            c_q = wmean(a32)
            c = pod_avg(c_q, edge_w)
            eta = jax.tree.map(lambda u, v: (u - v).astype(dd), c, c_q)
            sel = do_cloud & live
            ce_new = jax.tree.map(
                lambda f, o: jnp.where(
                    sel.reshape((topo.pods,) + (1,) * (f.ndim - 1)), f, o),
                eta, ce_old)
            cl_new = gate(jax.tree.map(
                lambda cq, a: (cq[:, None] - a).astype(dd), c_q, a32),
                cl_old)
        if flat:
            return (corr_cl.replace(
                        topo.constrain(cl_new, flat_spec(layout, 2))),
                    constrain_master(corr_edge.replace(ce_new)))
        cl_new = jax.tree.map(
            lambda x, cs: topo.constrain(x, topo.dev_spec(*cs)),
            cl_new, bundle.compute_specs)
        return cl_new, constrain_master(ce_new)

    def _corrections_stream(params, corr_cl, corr_edge, batch, rngs,
                            edge_w, dev_w, part, do_cloud):
        """Streamed refresh: a fori_loop over clients folds the
        share-weighted anchor sums in the exact ``weighted_mean_dev
        clients=`` re-association (one client's grads live at a time) and
        writes per-client state in place.  MTGC needs c_q before gamma,
        so it recomputes the (deterministic) anchor grads in a second
        loop instead of stashing K f32 gradient copies -- live anchor
        memory stays O(model)."""
        dd = algo.delta_dtype
        p, d, k = topo.pods, topo.devices_per_pod, cc.count
        layout = params.layout if flat else None
        pt = master_views(params) if flat else params
        rngs3 = rngs.reshape((p, d, k) + rngs.shape[2:])
        live = (jnp.ones((p,), bool) if part is None
                else jnp.any(part, axis=(1, 2)))

        def grads_c(c_idx):
            b_c = vclients.client_slice(batch, k, c_idx)
            r_c = jax.lax.dynamic_index_in_dim(rngs3, c_idx, axis=2,
                                               keepdims=False)
            g_c, _ = per_device_grads(pt, b_c, r_c, devices=d)
            if flat:
                return flatten_buf(layout, g_c, 2, jnp.float32)
            return jax.tree.map(lambda g: g.astype(jnp.float32), g_c)

        def wmul(x, sh):          # x: [P, D, ...], sh: [P, D]
            return x * sh.reshape(sh.shape + (1,) * (x.ndim - 2))

        def sh_of(c_idx):
            return jax.lax.dynamic_index_in_dim(dev_w, c_idx, axis=2,
                                                keepdims=False)

        def gate_c(c_idx, fresh, old):
            if part is None:
                return fresh
            g = jax.lax.dynamic_index_in_dim(part, c_idx, axis=2,
                                             keepdims=False)
            return jax.tree.map(
                lambda f, o: jnp.where(
                    g.reshape(g.shape + (1,) * (f.ndim - 2)), f, o),
                fresh, old)

        # [P, D, K, ...] views of the per-client slot (array leaf for the
        # flat layout -- the tree.maps below treat both uniformly)
        if flat:
            cl3 = corr_cl.buf.reshape(p, d, k, layout.n_pad)
            acc0 = topo.constrain(
                jnp.zeros((p, d, layout.n_pad), jnp.float32),
                flat_spec(layout, 2))
        else:
            cl3 = jax.tree.map(
                lambda x: x.reshape((p, d, k) + x.shape[2:]), corr_cl)
            acc0 = jax.tree.map(
                lambda v, cs: topo.constrain(
                    jnp.zeros((p, d) + v.shape[1:], jnp.float32),
                    topo.dev_spec(*cs)),
                pt, bundle.compute_specs)

        def take3(t3, c_idx):
            return jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, c_idx, axis=2, keepdims=False), t3)

        def put3(t3, tc, c_idx):
            return jax.tree.map(
                lambda x3, xc: jax.lax.dynamic_update_index_in_dim(
                    x3, xc, c_idx, axis=2), t3, tc)

        ce_old = corr_edge.buf if flat else corr_edge
        if algo.is_scaffold:
            # one pass: fold the share-weighted drift (a - c_local) and
            # refresh participating clients' c_local in place
            def body(c_idx, carry):
                acc2, cl3_c = carry
                a_c = grads_c(c_idx)
                sh = sh_of(c_idx)
                cl_c = take3(cl3_c, c_idx)
                acc2 = jax.tree.map(
                    lambda a2, a, cv: a2 + wmul(
                        a - cv.astype(jnp.float32), sh),
                    acc2, a_c, cl_c)
                fresh = gate_c(c_idx,
                               jax.tree.map(lambda a: a.astype(dd), a_c),
                               cl_c)
                return acc2, put3(cl3_c, fresh, c_idx)

            acc2, cl3 = jax.lax.fori_loop(0, k, body, (acc0, cl3))
            upd_q = jax.tree.map(lambda a: jnp.sum(a, axis=1), acc2)
            drift = pod_avg(upd_q, edge_w)
            ce_new = jax.tree.map(
                lambda e, dr: (e.astype(jnp.float32) + dr).astype(dd),
                ce_old, drift)
        else:  # mtgc: pass 1 folds c_q, pass 2 writes gamma per client
            def body(c_idx, acc):
                return jax.tree.map(
                    lambda a0, a: a0 + wmul(a, sh_of(c_idx)),
                    acc, grads_c(c_idx))

            acc = jax.lax.fori_loop(0, k, body, acc0)
            c_q = jax.tree.map(lambda a: jnp.sum(a, axis=1), acc)
            c = pod_avg(c_q, edge_w)
            eta = jax.tree.map(lambda u, v: (u - v).astype(dd), c, c_q)
            sel = do_cloud & live
            ce_new = jax.tree.map(
                lambda f, o: jnp.where(
                    sel.reshape((p,) + (1,) * (f.ndim - 1)), f, o),
                eta, ce_old)

            def body2(c_idx, cl3_c):
                a_c = grads_c(c_idx)
                fresh = jax.tree.map(
                    lambda cq, a: (cq[:, None] - a).astype(dd), c_q, a_c)
                fresh = gate_c(c_idx, fresh, take3(cl3_c, c_idx))
                return put3(cl3_c, fresh, c_idx)

            cl3 = jax.lax.fori_loop(0, k, body2, cl3)

        cl_t = jax.tree.map(
            lambda x: x.reshape((p, d * k) + x.shape[3:]), cl3)
        if flat:
            return (corr_cl.replace(
                        topo.constrain(cl_t, flat_spec(layout, 2))),
                    constrain_master(corr_edge.replace(ce_new)))
        cl_t = jax.tree.map(
            lambda x, cs: topo.constrain(x, topo.dev_spec(*cs)),
            cl_t, bundle.compute_specs)
        return cl_t, constrain_master(ce_new)

    def client_correction_dev(corr_cl, corr_edge):
        """[P, D*K, ...] per-client pre-sign correction in delta_dtype:
        scaffold q = c_global - c_local ; mtgc q = gamma + eta -- the
        merged-voter-axis analogue of DC's shared delta broadcast.  Never
        folded into the fused kernel (the kernel's fold is one SHARED
        delta); instead it pre-adds into u_dev like the DC non-fold path.
        """
        cl = (shardflat.tree_views(topo, corr_cl, cast=False)
              if flat else corr_cl)
        ce = (shardflat.tree_views(topo, corr_edge, cast=False)
              if flat else corr_edge)
        ce_dev = _bcast_pd(topo, ce, bundle.compute_specs, None,
                           devices=d_virtual)
        if algo.is_scaffold:
            return jax.tree.map(lambda e, cv: e - cv, ce_dev, cl)
        return jax.tree.map(lambda cv, e: cv + e, cl, ce_dev)

    def flat_spec(layout, lead: int = 1):
        """Buffer spec (model-axis sharded iff the layout is) -- the
        single source of truth is ``shardflat.buf_spec`` so train-state
        placement can never diverge from the shard_map in/out specs."""
        return shardflat.buf_spec(topo, layout, batch_dims=lead)

    def constrain_master(tree):
        if flat:   # FlatState: [P, n_pad] buffer (sharded iff its layout)
            return tree.replace(
                topo.constrain(tree.buf, flat_spec(tree.layout)))
        return jax.tree.map(
            lambda x, s: topo.constrain(x, topo.pod_spec(*s)),
            tree, bundle.master_specs)

    @stages.in_stage("relayout")
    def master_views(fs):
        """Flat state -> leaf views, re-constrained to the per-leaf master
        layout so the loss compiles to the SAME partitioned compute as the
        tree layout (keeps flat bit-identical to tree under TP sharding).
        Sharded layouts slice the views inside shard_map -- no model-axis
        gather; the re-constrain is then a no-op for sharded leaves."""
        return jax.tree.map(
            lambda x, s: topo.constrain(x, topo.pod_spec(*s)),
            shardflat.tree_views(topo, fs), bundle.master_specs)

    def gather_leafdims(tree, lead):
        """Replicate every leaf's non-leading dims before an *unsharded*
        flat-buffer concat: uniform operand shardings keep XLA's concat
        partitioner out of the mixed minor-/major-dim-sharded case it
        miscompiles.  Sharded layouts never come through here -- their
        concats are rank-local inside shard_map (``flatten_buf``)."""
        spec = topo.dev_spec if lead == 2 else topo.pod_spec
        return jax.tree.map(
            lambda x: topo.constrain(x, spec(*([None] * (x.ndim - lead)))),
            tree)

    @stages.in_stage("relayout")
    def flatten_buf(layout, tree, batch_dims, dtype=None):
        """tree -> flat buffer without unsharding TP leaves: per-bucket
        shard_map writes for sharded layouts, the ``gather_leafdims``
        dodge for the unsharded one."""
        if layout.shards > 1:
            return shardflat.flatten(topo, layout, tree, batch_dims, dtype)
        return flatbuf.flatten_tree(layout, gather_leafdims(tree, batch_dims),
                                    batch_dims=batch_dims, dtype=dtype)

    # ---------------- local step direction ------------------------------
    def local_direction(state, params, delta, corr_cl, corr_edge, batch,
                        rngs, dev_w, vote_w, maskf):
        """-> (direction [P,...], new_ef, new_mom, losses).

        dev_w: [P, D(*K)] aggregation shares (participating shares when
        virtual); vote_w: voter mask / integer vote weights; maskf: the
        physical [P, D] float mask (FSDP regime only)."""
        if fsdp:
            transport = (algo.transport if algo.is_sign else "wmean")
            rho = algo.rho if algo.is_dc else 0.0
            direction, losses = pod_direction_fsdp(
                params, delta, batch, rngs, maskf,
                dev_w.astype(jnp.float32), transport, rho)
            return direction, state.ef, state.mom, losses

        g_dev, losses = per_device_grads(params, batch, rngs)
        new_ef, new_mom = state.ef, state.mom

        if algo.method == "hier_sgd":
            direction = jax.tree.map(
                lambda g: votes.weighted_mean_dev(
                    topo, g.astype(jnp.float32), dev_w, clients=k_merge),
                g_dev)
        elif algo.method == "hier_local_qsgd":
            direction = jax.tree.map(
                lambda g: votes.weighted_mean_dev(topo, g, dev_w,
                                                  clients=k_merge),
                quantize_dev(g_dev, rngs))
        else:  # sign methods
            u_dev = g_dev
            if algo.momentum > 0.0:
                new_mom = jax.tree.map(
                    lambda m, g: algo.momentum * m
                    + (1.0 - algo.momentum) * g.astype(m.dtype),
                    state.mom, g_dev)
                u_dev = new_mom
            if algo.error_feedback:
                u_dev = jax.tree.map(
                    lambda u, e: u.astype(jnp.float32) + e, u_dev, state.ef)
            # the fused flat-buffer transport folds the DC correction
            # pre-sign into its single device-side sweep (Alg. 2's
            # sgn(g + rho*delta), same arithmetic => bit-identical); the
            # EF update needs the explicit per-leaf signs, so EF runs
            # the tree path up to the vote.
            fold_dc = (algo.transport == "fused" and algo.is_dc
                       and not algo.error_feedback)
            if algo.is_dc and not fold_dc:
                with stages.scope("correction"):
                    d_dev = _bcast_pd(topo, delta, bundle.compute_specs,
                                      None, devices=d_virtual)
                    u_dev = jax.tree.map(
                        lambda u, dl: u + algo.rho * dl.astype(u.dtype),
                        u_dev, d_dev)
            if algo.has_client_correction:
                with stages.scope("correction"):
                    q_dev = client_correction_dev(corr_cl, corr_edge)
                    u_dev = jax.tree.map(
                        lambda u, ql: u + algo.rho * ql.astype(u.dtype),
                        u_dev, q_dev)
            if algo.transport == "fused" and not algo.error_feedback:
                direction = votes.fused_sign_vote(
                    topo, u_dev, delta if fold_dc else None,
                    algo.rho if fold_dc else 0.0, vote_w,
                    specs=bundle.compute_specs)
                return direction, new_ef, new_mom, losses
            s_dev = jax.tree.map(signs.sgn, u_dev)
            if algo.error_feedback:
                new_ef = ef_residual(u_dev, s_dev,
                                     part=(vote_w > 0) if virtual else None)
            direction = vote_direction(s_dev, vote_w)
        return direction, new_ef, new_mom, losses

    # ---------------- flat-state local step -----------------------------
    def local_step_flat(state, params, delta, corr_cl, corr_edge, batch,
                        rngs, dev_w, vote_w, mu):
        """state_layout='flat': whole-buffer update, no per-leaf loops.

        params/delta are ``flatbuf.FlatState``; returns the *updated*
        params (the fused transport applies v <- v - mu*vote inside its
        single ``vote_update`` read-modify-write; every other direction
        is flattened once and applied as one elementwise sweep).
        Per-coordinate arithmetic matches the tree path exactly, so the
        trajectory is bit-identical leaf-for-leaf.
        """
        layout = params.layout
        g_dev, losses = per_device_grads(master_views(params), batch, rngs)
        new_ef, new_mom = state.ef, state.mom

        def descend(vote_tree):
            # flatten the int8 vote (exact in any float dtype) and cast
            # inside the update: no f32 copy of the direction
            dir_buf = flatten_buf(layout, vote_tree, 1, jnp.int8)
            with stages.scope("vote_update"):
                return params.replace(
                    params.buf - mu * dir_buf.astype(params.buf.dtype))

        if algo.method == "hier_sgd":
            g_buf = flatten_buf(layout, g_dev, 2, jnp.float32)
            dir_buf = votes.weighted_mean_dev(topo, g_buf, dev_w,
                                              clients=k_merge)
            new_params = params.replace(
                params.buf - mu * dir_buf.astype(params.buf.dtype))
            return new_params, new_ef, new_mom, losses
        if algo.method == "hier_local_qsgd":
            # quantize per leaf BEFORE flattening (identical fold_in
            # indices AND identical norm-reduction sharding to the tree
            # path), then one whole-buffer weighted mean + update
            q_buf = flatten_buf(layout, quantize_dev(g_dev, rngs), 2,
                                jnp.float32)
            dir_buf = votes.weighted_mean_dev(topo, q_buf, dev_w,
                                              clients=k_merge)
            new_params = params.replace(
                params.buf - mu * dir_buf.astype(params.buf.dtype))
            return new_params, new_ef, new_mom, losses

        # sign methods
        u_dev = g_dev
        if algo.momentum > 0.0:
            g_buf = flatten_buf(layout, g_dev, 2, jnp.float32)
            new_mom = state.mom.replace(
                algo.momentum * state.mom.buf
                + (1.0 - algo.momentum) * g_buf)
            u_dev = shardflat.tree_views(topo, new_mom, cast=False)
        if algo.error_feedback:
            # the EF scale is a per-leaf mean: constrain u to the tree
            # path's compute sharding so the reduction order (and hence
            # the residual) stays bitwise identical
            u_dev = jax.tree.map(
                lambda u, e, cs: topo.constrain(
                    u.astype(jnp.float32) + e, topo.dev_spec(*cs)),
                u_dev, shardflat.tree_views(topo, state.ef, cast=False),
                bundle.compute_specs)
        fold_dc = (algo.transport == "fused" and algo.is_dc
                   and not algo.error_feedback)
        if algo.is_dc and not fold_dc:
            with stages.scope("correction"):
                d_dev = _bcast_pd(topo, shardflat.tree_views(topo, delta,
                                                             cast=False),
                                  bundle.compute_specs, None,
                                  devices=d_virtual)
                u_dev = jax.tree.map(
                    lambda u, dl: u + algo.rho * dl.astype(u.dtype),
                    u_dev, d_dev)
        if algo.has_client_correction:
            with stages.scope("correction"):
                q_dev = client_correction_dev(corr_cl, corr_edge)
                u_dev = jax.tree.map(
                    lambda u, ql: u + algo.rho * ql.astype(u.dtype),
                    u_dev, q_dev)
        if algo.transport == "fused" and not algo.error_feedback:
            # the whole-model v <- v - mu*vote is ONE vote_update
            # read-modify-write over the packed-word buffer (mu folded
            # into the kernel when it is step-independent)
            new_buf = votes.fused_sign_vote_update(
                topo, layout, u_dev,
                delta.buf if fold_dc else None,
                algo.rho if fold_dc else 0.0, vote_w, params.buf, mu,
                mu_static=None if algo.decay else algo.mu)
            return params.replace(new_buf), new_ef, new_mom, losses
        s_dev = jax.tree.map(signs.sgn, u_dev)
        if algo.error_feedback:
            new_ef = state.ef.replace(flatten_buf(
                layout,
                ef_residual(u_dev, s_dev,
                            part=(vote_w > 0) if virtual else None),
                2, jnp.float32))
        return descend(vote_direction(s_dev, vote_w)), new_ef, new_mom, losses

    # ---------------- streamed-client local step ------------------------
    def local_step_stream(state, params, delta, corr_cl, corr_edge, batch,
                          rngs, shares3, vote_w3, mu):
        """ClientConfig.mode='stream': fori_loop over the K virtual
        clients with only ONE client's gradient live at a time.

        Per client the (DC-corrected) direction is sign-compressed and
        accumulated into a persistent signed tally (``votes`` tally
        machinery, Pallas ``tally_acc`` RMW on the fused path); the sign
        threshold is deferred to after the loop, where ``t >= 0``
        reproduces merged's ``2*pos >= n_eff`` tie rule exactly --
        integer tallies, so the trajectory is bitwise identical to the
        merged voter-axis step in BOTH state layouts.  shares3/vote_w3
        arrive UNmerged: [P, D, K].  Returns the *updated* params like
        ``local_step_flat``.
        """
        k = cc.count
        p, d = topo.pods, topo.devices_per_pod
        layout = params.layout if flat else None
        params_tree = master_views(params) if flat else params
        rngs3 = rngs.reshape((p, d, k) + rngs.shape[2:])
        fuse = (algo.is_sign and algo.transport == "fused"
                and not algo.error_feedback)
        fold_dc = fuse and algo.is_dc
        acc_dt = votes.tally_dtype(vote_bound)

        # the shared DC correction broadcasts ONCE (physical device axis
        # only); clients re-read it each iteration
        delta_tree = None
        if algo.is_dc and not fold_dc and algo.is_sign:
            with stages.scope("correction"):
                dt = (shardflat.tree_views(topo, delta, cast=False)
                      if flat else delta)
                delta_tree = _bcast_pd(topo, dt, bundle.compute_specs, None,
                                       devices=d)
        # ... and so does the scaffold/mtgc edge-level term; the
        # per-client term (corr3) is sliced per client inside the loop
        ce_tree = corr3 = None
        if algo.has_client_correction:
            with stages.scope("correction"):
                ce = (shardflat.tree_views(topo, corr_edge, cast=False)
                      if flat else corr_edge)
                ce_tree = _bcast_pd(topo, ce, bundle.compute_specs, None,
                                    devices=d)

        # per-voter state views sliced per client inside the loop
        @stages.in_stage("relayout")
        def views3(fs_or_tree):
            t = (shardflat.tree_views(topo, fs_or_tree, cast=False)
                 if flat else fs_or_tree)
            return jax.tree.map(
                lambda x: x.reshape((p, d, k) + x.shape[2:]), t)

        ef3 = views3(state.ef) if algo.error_feedback else None
        mom3 = views3(state.mom) if algo.momentum > 0.0 else None
        if algo.has_client_correction:
            corr3 = views3(corr_cl)

        def take_c(tree, c_idx):
            return jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, c_idx, axis=2, keepdims=False), tree)

        def put_c(tree3, tree_c, c_idx):
            return jax.tree.map(
                lambda x3, xc: jax.lax.dynamic_update_index_in_dim(
                    x3, xc, c_idx, axis=2), tree3, tree_c)

        # the persistent accumulator: an integer sign tally for sign
        # methods (flat words buffer on the pure-fused path, per-leaf
        # otherwise), an f32 share-weighted sum for the mean methods
        tally_flat = tally_tree = acc = None
        vlayout = None
        if not algo.is_sign:
            if flat:
                acc = topo.constrain(
                    jnp.zeros((p, d, layout.n_pad), jnp.float32),
                    flat_spec(layout, 2))
            else:
                acc = jax.tree.map(
                    lambda v, cs: topo.constrain(
                        jnp.zeros((p, d) + v.shape[1:], jnp.float32),
                        topo.dev_spec(*cs)),
                    params_tree, bundle.compute_specs)
        elif fuse:
            if flat:
                vlayout = layout
            else:
                # a layout over the per-device direction shapes (only
                # shapes matter -- packing is dtype-blind past the sign)
                template = jax.tree.map(
                    lambda v: jax.ShapeDtypeStruct(
                        (p, d) + v.shape[1:], jnp.float32), params_tree)
                if topo.model_shards > 1:
                    lay = flatbuf.make_layout(
                        template, batch_dims=2,
                        sharding=shardflat.model_sharding(
                            topo, bundle.compute_specs))
                    vlayout = lay if lay.shards > 1 else None
                if vlayout is None:
                    vlayout = flatbuf.make_layout(template, batch_dims=2)
            # lane rows [P, D, n_pad/128, 128]: the kernels' own view,
            # and an 8-bit [P, D, n_pad] array would take 4x its bytes
            tally_flat = topo.constrain(
                jnp.zeros((p, d, vlayout.n_pad // flatbuf.LANES,
                           flatbuf.LANES), acc_dt),
                shardflat.buf_spec(topo, vlayout, 2))
        else:
            tally_tree = jax.tree.map(
                lambda v, cs: topo.constrain(
                    jnp.zeros((p, d) + v.shape[1:], acc_dt),
                    topo.dev_spec(*cs)),
                params_tree, bundle.compute_specs)

        losses0 = jnp.zeros((p, d, k), jnp.float32)

        def body(c_idx, carry):
            tally_f, tally_t, acc_c, ef_c, mom_c, loss_c = carry
            b_c = vclients.client_slice(batch, k, c_idx)
            r_c = jax.lax.dynamic_index_in_dim(rngs3, c_idx, axis=2,
                                               keepdims=False)
            g_c, losses = per_device_grads(params_tree, b_c, r_c, devices=d)
            loss_c = jax.lax.dynamic_update_index_in_dim(
                loss_c, losses.astype(jnp.float32), c_idx, axis=2)
            sh_c = jax.lax.dynamic_index_in_dim(shares3, c_idx, axis=2,
                                                keepdims=False)
            w_c = jax.lax.dynamic_index_in_dim(vote_w3, c_idx, axis=2,
                                               keepdims=False)

            if not algo.is_sign:
                if algo.method == "hier_local_qsgd":
                    g_c = quantize_dev(g_c, r_c)
                if flat:
                    g_buf = flatten_buf(layout, g_c, 2, jnp.float32)
                    acc_c = acc_c + g_buf * sh_c[:, :, None]
                else:
                    acc_c = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32)
                        * sh_c.reshape(sh_c.shape + (1,) * (g.ndim - 2)),
                        acc_c, g_c)
                return (tally_f, tally_t, acc_c, ef_c, mom_c, loss_c)

            u_c = g_c
            if algo.momentum > 0.0:
                m_new = jax.tree.map(
                    lambda m, g: algo.momentum * m
                    + (1.0 - algo.momentum) * g.astype(m.dtype),
                    take_c(mom_c, c_idx), g_c)
                mom_c = put_c(mom_c, m_new, c_idx)
                u_c = m_new
            if algo.error_feedback:
                e_c = take_c(ef_c, c_idx)
                if flat:
                    u_c = jax.tree.map(
                        lambda u, e, cs: topo.constrain(
                            u.astype(jnp.float32) + e, topo.dev_spec(*cs)),
                        u_c, e_c, bundle.compute_specs)
                else:
                    u_c = jax.tree.map(
                        lambda u, e: u.astype(jnp.float32) + e, u_c, e_c)
            if delta_tree is not None:
                with stages.scope("correction"):
                    u_c = jax.tree.map(
                        lambda u, dl: u + algo.rho * dl.astype(u.dtype),
                        u_c, delta_tree)
            if ce_tree is not None:
                with stages.scope("correction"):
                    cl_c = take_c(corr3, c_idx)
                    if algo.is_scaffold:
                        q_c = jax.tree.map(lambda e, cv: e - cv, ce_tree,
                                           cl_c)
                    else:
                        q_c = jax.tree.map(lambda cv, e: cv + e, cl_c,
                                           ce_tree)
                    u_c = jax.tree.map(
                        lambda u, ql: u + algo.rho * ql.astype(u.dtype),
                        u_c, q_c)
            if fuse:
                tally_f = votes.fused_sign_tally_accumulate(
                    topo, vlayout, u_c,
                    delta if (fold_dc and not flat) else None,
                    delta.buf if (fold_dc and flat) else None,
                    algo.rho if fold_dc else 0.0, w_c, tally_f)
            else:
                s_c = jax.tree.map(signs.sgn, u_c)
                if algo.error_feedback:
                    ef_c = put_c(ef_c,
                                 ef_residual(u_c, s_c, part=(w_c > 0)),
                                 c_idx)
                with stages.scope("tally_acc"):
                    tally_t = jax.tree.map(
                        lambda t, s: votes.tally_add_signs(t, s, w_c),
                        tally_t, s_c)
            return (tally_f, tally_t, acc_c, ef_c, mom_c, loss_c)

        tally_flat, tally_tree, acc, ef3, mom3, losses3 = jax.lax.fori_loop(
            0, k, body, (tally_flat, tally_tree, acc, ef3, mom3, losses0))
        losses = losses3.reshape(p, d * k)

        new_ef, new_mom = state.ef, state.mom
        if ef3 is not None:
            ef_t = jax.tree.map(
                lambda x: x.reshape((p, d * k) + x.shape[3:]), ef3)
            new_ef = (state.ef.replace(
                flatten_buf(layout, ef_t, 2, jnp.float32))
                if flat else ef_t)
        if mom3 is not None:
            mom_t = jax.tree.map(
                lambda x: x.reshape((p, d * k) + x.shape[3:]), mom3)
            new_mom = (state.mom.replace(
                flatten_buf(layout, mom_t, 2, jnp.float32))
                if flat else mom_t)

        if not algo.is_sign:
            if flat:
                dir_buf = jnp.sum(acc, axis=1)
                new_params = params.replace(
                    params.buf - mu * dir_buf.astype(params.buf.dtype))
            else:
                direction = jax.tree.map(lambda a: jnp.sum(a, axis=1), acc)
                new_params = jax.tree.map(
                    lambda v, s: v - mu * s.astype(v.dtype), params,
                    direction)
            return new_params, new_ef, new_mom, losses

        # deferred threshold: t >= 0 -> +1 (== merged's 2*pos >= n_eff),
        # empty quorum (n_eff == 0) abstains
        n_eff = jnp.sum(vote_w3.astype(jnp.int32), axis=(1, 2))
        if fuse:
            if flat:
                new_buf = votes.fused_tally_finish(
                    topo, vlayout, tally_flat, n_eff, params.buf, mu)
                new_params = params.replace(new_buf)
            else:
                direction = votes.fused_tally_finish(
                    topo, vlayout, tally_flat, n_eff, None, None)
                new_params = jax.tree.map(
                    lambda v, s: v - mu * s.astype(v.dtype), params,
                    direction)
        else:
            with stages.scope("tally_finish"):
                direction = jax.tree.map(
                    lambda t, cs: votes.tally_vote_dev(topo, t, n_eff, cs),
                    tally_tree, bundle.compute_specs)
            if flat:
                dir_buf = flatten_buf(layout, direction, 1,
                                      params.buf.dtype)
                new_params = params.replace(params.buf - mu * dir_buf)
            else:
                new_params = jax.tree.map(
                    lambda v, s: v - mu * s.astype(v.dtype), params,
                    direction)
        return new_params, new_ef, new_mom, losses

    # ---------------- the step ------------------------------------------
    def train_step(state: TrainState, batch, edge_weights, dev_weights,
                   dev_mask):
        rng, r_local, r_anchor = jax.random.split(state.rng, 3)
        pd = (topo.pods, d_virtual)
        rngs_l = jax.random.split(r_local, pd[0] * pd[1])
        rngs_l = rngs_l.reshape(pd + rngs_l.shape[1:])
        rngs_a = jax.random.split(r_anchor, pd[0] * pd[1])
        rngs_a = rngs_a.reshape(pd + rngs_a.shape[1:])
        maskf = dev_mask.astype(jnp.float32)
        if maskf.ndim == 3 and not virtual:
            raise ValueError(
                "a client-granular [P, D, K] dev_mask requires an ACTIVE "
                "AlgoConfig.clients (the virtual-client path); the legacy "
                "path takes the [P, D] device mask")
        rnd_index = state.step // t_e
        if virtual:
            # per-round participation (pinned to (seed, round), so the
            # anchor pass and every local step of round t -- and a
            # checkpoint restored mid-round -- see the same quorum),
            # combined with the caller's membership mask: [P, D] device
            # granularity, or [P, D, K] per virtual client (elastic
            # Membership churn -- a value change, never a retrace)
            if maskf.ndim == 3 and maskf.shape[2] != cc.count:
                raise ValueError(
                    f"dev_mask client dim {maskf.shape[2]} != K={cc.count}")
            maskf3 = maskf if maskf.ndim == 3 else maskf[:, :, None]
            part = vclients.participation_mask(
                cc, topo.pods, topo.devices_per_pod, rnd_index)
            part = topo.constrain(part * maskf3,
                                  topo.client_spec())         # [P, D, K]
            w_arr = cc.weight_array(topo.pods, topo.devices_per_pod)
            # weighted popcount weights: pure int32 arithmetic, so
            # |D_qk| shares above 2^24 never round through float ...
            vote_w3 = (jnp.asarray(w_arr, jnp.int32)
                       * part.astype(jnp.int32))                # [P, D, K]
            vote_w = vote_w3.reshape(pd)
            # ... and participating aggregation shares for anchor/means
            shares = vclients.participating_shares(
                dev_weights, jnp.asarray(w_arr, jnp.float32), part)
            if stream:
                # the streamed sweep slices clients itself -- the batch
                # stays [P, D, b, ...] and weights stay [P, D, K]
                shares3 = shares.reshape(
                    topo.pods, topo.devices_per_pod, cc.count)
                carve = lambda b: b
            else:
                carve = lambda b: vclients.carve_batch(b, cc.count)
            # participation gate for the correction-state refresh --
            # same contract as EF: only clients with a live vote update
            corr_part = (vote_w3 > 0) if stream else (vote_w > 0)
        else:
            vote_w = maskf > 0.5
            shares = dev_weights
            carve = lambda b: b
            corr_part = None          # legacy path updates unconditionally
        # useful work of the voter passes: the step runs a forward and
        # backward pass for every voter, absent ones included, and only
        # a pass with a non-zero vote weight casts a vote
        voter_passes = jnp.int32(topo.pods * d_virtual)
        votes_cast = jnp.sum(vote_w > 0, dtype=jnp.int32)
        train_batch = carve(batch["train"])
        anchor_batch = carve(batch.get("anchor", batch["train"]))
        agg_shares = shares3 if stream else shares

        # -- prologue: cloud issue/commit + anchor/correction refresh at
        # round start.  The schedule layer (core.schedule) decides what
        # "issue" and "commit" mean: sync commits the freshly issued
        # aggregate at the same boundary (today's barrier, bitwise);
        # overlap commits the aggregate issued at the PREVIOUS boundary
        # and stages this one in agg_next, so the anchors below refresh
        # at the committed (one-round-stale) model.
        def prologue(op):
            params, agg_next, delta, delta_next, corr_cl, corr_edge = op
            with stages.scope("cloud_mean"):
                issued = constrain_master(pod_avg(params, edge_weights))
                params, agg_next = cloud_sched.commit(issued, agg_next)
            if algo.is_dc:
                with stages.scope("anchor"):
                    fresh = compute_delta(params, delta, anchor_batch,
                                          rngs_a, edge_weights, agg_shares,
                                          maskf)
                if algo.anchor_staleness == 1:
                    delta, delta_next = delta_next, fresh
                else:
                    delta = fresh
            if algo.has_client_correction:
                with stages.scope("anchor"):
                    corr_cl, corr_edge = compute_corrections(
                        params, corr_cl, corr_edge, anchor_batch, rngs_a,
                        edge_weights, agg_shares, corr_part, rnd_index)
            return params, agg_next, delta, delta_next, corr_cl, corr_edge

        def no_op(op):
            return op

        operand = (state.params, state.agg_next, state.delta,
                   state.delta_next, state.corr_cl, state.corr_edge)
        if sync == "cond":
            (params, agg_next, delta, delta_next, corr_cl,
             corr_edge) = jax.lax.cond(
                state.step % t_e == 0, prologue, no_op, operand)
        elif sync == "always":
            (params, agg_next, delta, delta_next, corr_cl,
             corr_edge) = prologue(operand)
        else:  # 'never'
            (params, agg_next, delta, delta_next, corr_cl,
             corr_edge) = operand

        mu = jnp.asarray(
            algo.mu if algo.is_sign else algo.mu_sgd, algo.master_dtype)
        if algo.decay:
            mu = mu / jnp.sqrt(rnd_index.astype(algo.master_dtype) + 1.0)

        # -- local sign step
        if stream:
            params, new_ef, new_mom, losses = local_step_stream(
                state, params, delta, corr_cl, corr_edge, train_batch,
                rngs_l, shares3, vote_w3, mu)
        elif flat:
            params, new_ef, new_mom, losses = local_step_flat(
                state, params, delta, corr_cl, corr_edge, train_batch,
                rngs_l, shares, vote_w, mu)
        else:
            direction, new_ef, new_mom, losses = local_direction(
                state, params, delta, corr_cl, corr_edge, train_batch,
                rngs_l, shares, vote_w, maskf)
            params = jax.tree.map(
                lambda v, s: v - mu * s.astype(v.dtype), params, direction)
        params = constrain_master(params)

        new_state = TrainState(
            step=state.step + 1, params=params, agg_next=agg_next,
            delta=delta, delta_next=delta_next, ef=new_ef, mom=new_mom,
            corr_cl=corr_cl, corr_edge=corr_edge, rng=rng)
        metrics = {
            "loss": jnp.mean(losses.astype(jnp.float32)),
            "loss_per_pod": jnp.mean(losses.astype(jnp.float32), axis=1),
            "mu": mu,
            "voter_passes": voter_passes,
            "votes_cast": votes_cast,
        }
        return new_state, metrics

    # ---------------- init ----------------------------------------------
    def init_fn(params_single: PyTree, rng: jax.Array) -> TrainState:
        """params_single: one replica's params (no leading dims)."""
        p = topo.pods

        def rep(x, s):
            xp = jnp.broadcast_to(x[None], (p,) + x.shape)
            return topo.constrain(
                xp.astype(algo.master_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else xp,
                topo.pod_spec(*s))

        params_tree = jax.tree.map(rep, params_single, bundle.master_specs)
        if flat:
            # on a >1 model axis the buffer is laid out as per-shard
            # buckets and stays model-sharded for the whole run
            sharding = (shardflat.model_sharding(topo, bundle.master_specs)
                        if topo.model_shards > 1 else None)
            layout = flatbuf.make_layout(params_tree, batch_dims=1,
                                         sharding=sharding)
            buf = flatten_buf(layout, params_tree, 1)
            params = flatbuf.FlatState(
                topo.constrain(buf, flat_spec(layout)), layout)
            zeros_m = lambda dt: flatbuf.FlatState(
                topo.constrain(jnp.zeros((p, layout.n_pad), dt),
                               flat_spec(layout)),
                flatbuf.with_dtype(layout, dt))
            # per-voter buffers (EF / momentum) span the merged
            # virtual-client axis
            zeros_pd = lambda dt: flatbuf.FlatState(
                topo.constrain(jnp.zeros((p, d_virtual, layout.n_pad), dt),
                               flat_spec(layout, 2)),
                flatbuf.with_dtype(layout, dt), batch_dims=2)
        else:
            params = params_tree
            zeros_m = lambda dt: constrain_master(jax.tree.map(
                lambda v: jnp.zeros_like(v, dtype=dt), params_tree))
            zeros_pd = lambda dt: _bcast_pd(
                topo, jax.tree.map(
                    lambda v: jnp.zeros_like(v, dtype=dt), params_tree),
                bundle.compute_specs, None, devices=d_virtual)
        # the staged in-flight aggregate starts as a copy of the freshly
        # replicated initial model: the step-0 prologue then commits
        # exactly w0 (bitwise), so round 0 runs from the same model the
        # oracle's round 0 does, while the first real aggregate is
        # issued at that boundary and lands one round later
        agg_next = (constrain_master(jax.tree.map(jnp.copy, params))
                    if cloud_sched.staged else None)
        delta = zeros_m(algo.delta_dtype) if needs_delta else None
        delta_next = (zeros_m(algo.delta_dtype)
                      if (algo.is_dc and algo.anchor_staleness == 1) else None)
        ef = mom = None
        if not fsdp and algo.error_feedback:
            ef = zeros_pd(jnp.float32)
        if not fsdp and algo.momentum > 0.0:
            mom = zeros_pd(jnp.float32)
        # correction slots only exist where they are read (scaffold /
        # mtgc): one per-client voter-axis buffer + one master-shaped term
        corr_cl = corr_edge = None
        if algo.has_client_correction:
            corr_cl = zeros_pd(algo.delta_dtype)
            corr_edge = zeros_m(algo.delta_dtype)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          agg_next=agg_next, delta=delta,
                          delta_next=delta_next, ef=ef, mom=mom,
                          corr_cl=corr_cl, corr_edge=corr_edge, rng=rng)

    return init_fn, train_step


def make_global_round(topo: Topology, algo: AlgoConfig, bundle: ModelBundle):
    """One fused global round: prologue + lax.scan over T_E local steps.

    Used by the dry-run/benchmarks so the compiled artifact carries the
    paper's true per-round cost (T_E one-bit local steps + one cloud sync +
    one anchor exchange) with correct 1/T_E amortization.

    batches: pytree of [T_E, P, D, b, ...].
    """
    init_fn, train_step = make_hier_step(topo, algo, bundle)

    def global_round(state: TrainState, batches, edge_weights, dev_weights,
                     dev_mask):
        def body(st, batch_t):
            st, metrics = train_step(st, {"train": batch_t}, edge_weights,
                                     dev_weights, dev_mask)
            return st, metrics["loss"]

        state, losses = jax.lax.scan(body, state, batches)
        return state, {"loss": jnp.mean(losses)}

    return init_fn, global_round


def state_shardings(topo: Topology, algo: AlgoConfig, bundle: ModelBundle,
                    abstract_state: TrainState) -> TrainState:
    """NamedSharding tree for a TrainState (dry-run / checkpoint layouts)."""
    rep = topo.sharding(jax.sharding.PartitionSpec())

    def master(tree):
        if tree is None:
            return None
        if isinstance(tree, flatbuf.FlatState):   # [P, n_pad] buffer
            spec = shardflat.buf_spec(topo, tree.layout, 1)
            return jax.tree.map(lambda _: topo.sharding(spec), tree)
        return jax.tree.map(
            lambda _, s: topo.sharding(topo.pod_spec(*s)),
            tree, bundle.master_specs)

    def dev(tree):
        if tree is None:
            return None
        if isinstance(tree, flatbuf.FlatState):   # [P, D, n_pad] buffer
            spec = shardflat.buf_spec(topo, tree.layout, 2)
            return jax.tree.map(lambda _: topo.sharding(spec), tree)
        return jax.tree.map(
            lambda _, s: topo.sharding(topo.dev_spec(*s)),
            tree, bundle.compute_specs)

    return TrainState(
        step=rep,
        params=master(abstract_state.params),
        agg_next=master(abstract_state.agg_next),
        delta=master(abstract_state.delta),
        delta_next=master(abstract_state.delta_next),
        ef=dev(abstract_state.ef),
        mom=dev(abstract_state.mom),
        corr_cl=dev(abstract_state.corr_cl),
        corr_edge=master(abstract_state.corr_edge),
        rng=rep,
    )
