"""Public jit'd wrappers around the Pallas kernels.

Handles arbitrary pytree/shape inputs (flatten -> pad -> lane-row view
-> kernel -> unpad) and **backend-detects** instead of hardcoding a
mode:

  * on TPU (``jax.default_backend() == "tpu"``) the compiled Pallas
    kernels run by default (``interpret=False``);
  * elsewhere the pure-jnp reference runs by default (interpret-mode
    Pallas is available on request for validation -- it is far slower
    than the reference, so it is never the silent default).

Pass ``use_pallas=``/``interpret=`` explicitly to override (the kernel
tests force ``use_pallas=True, interpret=True`` on CPU).

The kernels see [slabs, n/128, 128] lane rows (``kernels.sign_pack``):
for ``core.flatbuf`` buffers (n a multiple of 4096) that is a pure
reshape -- for f32 the very bytes of the flat buffer -- and the packed
words come back in the kernels' lane-plane bit order, which only
``fused_vote_update_words`` reads.  The (rows, pad) arithmetic of the
any-shape wrappers is computed once per process (``_pad_layout``).

``fused_sign_vote_flat`` is the vote-only local compute of the fused
transport; ``fused_vote_update_flat`` (state_layout="flat") additionally
applies ``v <- v - mu*vote`` inside the single ``vote_update``
read-modify-write, so the whole-model update is one HBM pass (aliased
in place when compiled).  Both are compositions of the two halves the
multi-chip shard_map program calls directly with the data-axis gather
in between: ``fused_pack_flat`` (device-side sign+pack, pre-gather) and
``fused_vote_update_words`` (edge-side vote+update on the gathered
words) -- see ``core.votes``.

Padding contract: the flat views these wrappers sweep may contain
don't-care coordinates BETWEEN real leaves, not just at the buffer
tail -- slot tail padding and, in per-rank bucket buffers of a sharded
layout, the zero shard tail of an uneven TP leaf's last block
(``flatbuf.LeafSlot.shard_pad``).  All of them are zero floats, so the
kernels see ``sgn(0) = +1`` and update them like any coordinate; no
view ever reads them back, which is what makes the whole-buffer sweep
legal without per-leaf masks.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref, sign_pack as _sp, tally_acc as _ta
from repro.kernels import ternary_quant as _tq, vote_update as _vu

PACK = _sp.PACK
LANES = _sp.LANES
TILE = _sp.TILE


# ---------------------------------------------------------------------------
# Backend detection
# ---------------------------------------------------------------------------

def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(use_pallas: bool | None, interpret: bool | None):
    """None -> backend defaults: compiled Pallas on TPU, jnp ref elsewhere."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if interpret is None:
        interpret = not on_tpu()
    return use_pallas, interpret


def fused_kernel_mode() -> str:
    """How the fused flat-buffer transport should run its local compute.

    Returns ``"pallas"`` (compiled), ``"interpret"`` or ``"jnp"``: the
    compiled kernels on TPU, the pure-jnp route elsewhere.  The kernels
    are single-device programs, so on a multi-device mesh ``core.votes``
    runs them per rank inside its ``shard_map`` program (every rank is
    one device) whatever the model-axis extent.  ``REPRO_FUSED_PALLAS``
    overrides: ``off`` forces jnp, ``interpret`` forces interpret-mode
    Pallas (used by tests to exercise the kernel route on CPU).
    """
    env = os.environ.get("REPRO_FUSED_PALLAS", "auto").lower()
    if env in ("0", "off", "jnp"):
        return "jnp"
    if env == "interpret":
        return "interpret"
    return "pallas" if on_tpu() else "jnp"


# ---------------------------------------------------------------------------
# Layout-cached 2D views
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pad_layout(n: int, block_r: int, block_c: int):
    """Static (rows, pad) so that rows % block_r == 0, rows*block_c >= n."""
    rows = -(-n // block_c)
    rows = -(-rows // block_r) * block_r
    return rows, rows * block_c - n


def _to_2d(x: jax.Array, block_r: int, block_c: int):
    """Flatten to an [R, C] view divisible by the block.

    Block-aligned inputs (flatbuf buffers) reshape in place; ragged tails
    get one zero-pad (sgn(0) = +1: bit-identical to the old ones-padding).
    """
    flat = x.reshape(-1)
    n = flat.shape[0]
    rows, pad = _pad_layout(n, block_r, block_c)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, block_c), n


# ---------------------------------------------------------------------------
# N-d kernel wrappers
# ---------------------------------------------------------------------------

def _lane_rows(x: jax.Array):
    """Any-shape x -> its [1, L, 128] lane-row view (zero-padded to a
    whole 4096-coordinate word row), plus the real coordinate count."""
    x2, n = _to_2d(x, 1, TILE)
    return x2.reshape(1, -1, LANES), n


def sign_pack_nd(g: jax.Array, delta: jax.Array | None = None,
                 rho: float = 0.0, *, use_pallas: bool | None = None,
                 interpret: bool | None = None, block_r: int = _sp.BLOCK_R):
    """Any-shape g (+delta) -> (packed [n_words] uint32, n_coords).

    Words are in the kernels' lane-plane bit order
    (``kernels.sign_pack``); ``vote_update_nd`` consumes them."""
    use_pallas, interpret = _resolve(use_pallas, interpret)
    g3, n = _lane_rows(g)
    d3 = None if delta is None else _lane_rows(delta.astype(g.dtype))[0]
    if use_pallas:
        packed = _sp.sign_pack(g3, d3, rho, block_r=block_r,
                               interpret=interpret)
    else:
        packed = ref.sign_pack_ref(g3, d3, rho)
    return packed.reshape(-1), n


def vote_update_nd(packed_rows: jax.Array, v: jax.Array,
                   mask: jax.Array | None = None, *, mu: float,
                   use_pallas: bool | None = None,
                   interpret: bool | None = None,
                   block_r: int = _vu.BLOCK_R):
    """packed_rows: [K, n_words] (from sign_pack_nd on each device);
    v: any-shape model tensor; mask: optional [K].  Returns updated v."""
    use_pallas, interpret = _resolve(use_pallas, interpret)
    k = packed_rows.shape[0]
    v3, n = _lane_rows(v)
    packed = packed_rows.reshape(1, k, -1, LANES)
    m = None if mask is None else mask[None]
    if use_pallas:
        out = _vu.vote_update(packed, v3, m, mu=mu, block_r=block_r,
                              interpret=interpret)
    else:
        out = ref.vote_update_ref(packed, v3, mu, m)
    return out.reshape(-1)[:n].reshape(v.shape)


def ternary_quant_nd(x: jax.Array, rng: jax.Array, *,
                     use_pallas: bool | None = None,
                     interpret: bool | None = None,
                     block_r: int = _tq.BLOCK_R, block_c: int = _tq.BLOCK_C):
    """Any-shape unbiased ternary quantization (baseline compressor)."""
    use_pallas, interpret = _resolve(use_pallas, interpret)
    x2, n = _to_2d(x, block_r, block_c)
    # _to_2d zero-pads, so the padding cannot influence the norm
    norm = jnp.linalg.norm(x2.astype(jnp.float32))
    u = jax.random.uniform(rng, x2.shape, jnp.float32)
    if use_pallas:
        out = _tq.ternary_quant(x2, u, norm, block_r=block_r,
                                block_c=block_c, interpret=interpret)
    else:
        out = ref.ternary_quant_ref(x2, u, norm)
    return out.reshape(-1)[:n].reshape(x.shape)


# ---------------------------------------------------------------------------
# Fused flat-buffer transport (local compute of core.votes "fused")
# ---------------------------------------------------------------------------

def _slabs(u_buf: jax.Array, d_buf: jax.Array | None, rho: float):
    """[P, D, n] directions (+ [P, n] correction) -> the kernels'
    [P*D, n/128, 128] lane-row voter slabs (+ [P, n/128, 128] shared
    slabs).  Pure reshapes: for f32 the (8, 128)-tiled view is the flat
    buffer's own byte order."""
    p, d, n = u_buf.shape
    assert n % TILE == 0, (n, TILE)
    g3 = u_buf.reshape(p * d, n // LANES, LANES)
    d3 = None
    if d_buf is not None and rho:
        d3 = d_buf.astype(u_buf.dtype).reshape(p, n // LANES, LANES)
    return g3, d3


def fused_pack_flat(u_buf: jax.Array, d_buf: jax.Array | None,
                    rho: float, *, interpret: bool) -> jax.Array:
    """Device-side half of the fused transport: flat floats -> packed words.

    u_buf: [P, D, n_pad] float (n_pad % 4096 == 0, from core.flatbuf);
    d_buf: [P, n_pad] correction or None (the caller only folds the DC
    correction here for all-f32 trees -- the kernel adds in f32, which
    is exact iff the reference arithmetic is f32 too).  Returns the
    1-bit uplink payload [P, D, n_pad/32] uint32 (lane-plane bit order,
    read only by :func:`fused_vote_update_words`) via ONE ``sign_pack``
    sweep over all P*D voter slabs (delta re-read per voter through its
    BlockSpec, never broadcast-copied).  This is the pre-gather half
    the multi-chip shard_map program runs per rank before the data-axis
    all-gather of the words (``core.votes``).
    """
    p, d, n = u_buf.shape
    g3, d3 = _slabs(u_buf, d_buf, rho)
    packed = _sp.sign_pack(g3, d3, rho, interpret=interpret)
    return packed.reshape(p, d, n // PACK)


def fused_vote_update_words(words: jax.Array, v_buf: jax.Array | None,
                            mask: jax.Array | None, mu: float, *,
                            interpret: bool) -> jax.Array:
    """Edge-side half: packed voter words -> vote (+ optional update).

    words: [P, D, n_words] uint32 from :func:`fused_pack_flat` (all D
    voters' payloads, e.g. after the data-axis gather; D may be the
    merged virtual-client axis D*K); v_buf: [P, n_pad] float master
    buffer, or None to compute a pure vote (v = 0, mu = -1 makes the
    fused update emit exactly ``MajorityVote``); mask: [P, D] voter
    mask, nonnegative integer vote weights (weighted popcount; an empty
    quorum abstains and leaves v untouched), or None.
    ONE ``vote_update`` read-modify-write over every pod's whole-model
    packed-word buffer.
    """
    p, d, w = words.shape
    n = w * PACK
    assert n % TILE == 0, (n, TILE)
    packed = words.reshape(p, d, n // TILE, LANES)
    v3 = (jnp.zeros((p, n // LANES, LANES), jnp.float32) if v_buf is None
          else v_buf.reshape(p, n // LANES, LANES))
    out = _vu.vote_update(packed, v3, mask, mu=mu, interpret=interpret)
    return out.reshape(p, n)


def fused_sign_vote_flat(u_buf: jax.Array, d_buf: jax.Array | None,
                         rho: float, mask: jax.Array | None, *,
                         interpret: bool) -> jax.Array:
    """Pallas route of the fused transport on a local flat buffer.

    Composition of :func:`fused_pack_flat` and
    :func:`fused_vote_update_words` with v = 0, mu = -1 (pure vote).
    Returns the per-pod vote [P, n_pad] int8.
    """
    words = fused_pack_flat(u_buf, d_buf, rho, interpret=interpret)
    vote = fused_vote_update_words(words, None, mask, -1.0,
                                   interpret=interpret)
    return vote.astype(jnp.int8)


def fused_tally_acc_flat(u_buf: jax.Array, d_buf: jax.Array | None,
                         rho: float, weights: jax.Array,
                         tally: jax.Array, *, interpret: bool) -> jax.Array:
    """Streamed-client local step: fold ONE client's signs into the tally.

    u_buf: [P, D, n_pad] float pre-sign directions of the current
    client (physical device axis, NOT the merged D*K); d_buf: [P, n_pad]
    correction or None (same fold rules as ``fused_pack_flat``);
    weights: [P, D] integer vote weights of this client; tally:
    lane-row [P, D, n_pad/128, 128] signed tally (int8/int16/int32 per
    ``core.votes.tally_dtype``), returned in the same shape.  ONE ``tally_acc`` read-modify-write
    sweep over all P*D voter slabs -- the client's sign plane never
    reaches HBM, and the delta block is re-read per voter through its
    BlockSpec exactly like ``fused_pack_flat``.
    """
    p, d, n = u_buf.shape
    assert tally.shape == (p, d, n // LANES, LANES), (tally.shape,
                                                       u_buf.shape)
    g3, d3 = _slabs(u_buf, d_buf, rho)
    out = _ta.tally_acc(g3, d3, weights.reshape(p * d),
                        tally.reshape(g3.shape), rho=rho,
                        interpret=interpret)
    return out.reshape(tally.shape)


def fused_vote_update_flat(u_buf: jax.Array, d_buf: jax.Array | None,
                           rho: float, mask: jax.Array | None,
                           v_buf: jax.Array, mu: float, *,
                           interpret: bool) -> jax.Array:
    """Flat-state fused local step: ``v <- v - mu * vote`` on the buffer.

    u_buf: [P, D, n_pad] float pre-sign directions; d_buf: [P, n_pad]
    correction or None (same fold rules as ``fused_sign_vote_flat``);
    v_buf: [P, n_pad] master buffer; mu: static step size.  One
    ``sign_pack`` sweep over all P*D voter slabs, then exactly ONE
    ``vote_update`` read-modify-write over the whole-model packed-word
    buffer -- the vote never materializes, the update is the kernel's
    single HBM pass over v (aliased in place when compiled).
    """
    p, d, n = u_buf.shape
    assert v_buf.shape == (p, n), (v_buf.shape, (p, n))
    words = fused_pack_flat(u_buf, d_buf, rho, interpret=interpret)
    return fused_vote_update_words(words, v_buf, mask, mu,
                                   interpret=interpret)
