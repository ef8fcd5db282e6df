"""Sign-compression primitives (pure jnp reference ops).

These are the coordinate-wise building blocks of HierSignSGD /
DC-HierSignSGD (Kazemi et al., 2026):

  * ``sgn``           -- the paper's element-wise sign operator (maps to {-1,+1}).
  * ``pack_signs``    -- 1 bit/coordinate wire format (uint32 words), the
                         faithful device->edge uplink payload.
  * ``unpack_signs``  -- inverse of ``pack_signs``.
  * ``majority_vote`` -- s_q = sgn(sum_k sgn(g_k)), with optional voter
                         masking (straggler/fault quorum).
  * ``ternary_quantize`` -- the unbiased stochastic ternary quantizer used
                         by the Hier-Local-QSGD baseline (paper Sec. V-B).

Conventions
-----------
``sgn(0) = +1`` so that every coordinate is representable in one bit.  Vote
ties (possible with an even voter count, with masked voters, or with
weighted tallies that cancel exactly) therefore resolve to +1
deterministically; the packed and integer transports are bit-identical by
construction (tested in tests/test_signs.py).

Weighted votes: the voter ``mask`` generalizes to nonnegative *integer*
vote weights (the data shares ``|D_qk|`` of ``core.clients``) -- the vote
becomes the weighted popcount ``sgn(sum_k w_k sgn(g_k))`` with the same
tie rule.  A weight of 0 abstains; an edge whose whole quorum abstains
(all weights 0) returns vote 0, so the descent step leaves ``v_q``
unchanged for that round.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PACK_WIDTH = 32  # sign bits per uint32 word


def sgn(x: jax.Array) -> jax.Array:
    """Element-wise sign into {-1, +1} (int8); sgn(0) = +1."""
    return jnp.where(x >= 0, jnp.int8(1), jnp.int8(-1))


def _pad_to_multiple(flat: jax.Array, m: int) -> jax.Array:
    pad = (-flat.shape[-1]) % m
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.ones(flat.shape[:-1] + (pad,), flat.dtype)], axis=-1
        )
    return flat


def packed_size(n: int) -> int:
    """Number of uint32 words used to carry ``n`` sign bits."""
    return (n + PACK_WIDTH - 1) // PACK_WIDTH


def pack_signs(signs: jax.Array) -> jax.Array:
    """Pack {-1,+1} signs into uint32 words along the last axis.

    signs: (..., n) int8 in {-1, +1}  ->  (..., ceil(n/32)) uint32.
    Positive sign -> bit 1.  Padding bits are 1 (+1 sign).
    """
    flat = _pad_to_multiple(signs, PACK_WIDTH)
    bits = (flat > 0).astype(jnp.uint32)
    bits = bits.reshape(bits.shape[:-1] + (-1, PACK_WIDTH))
    shifts = jnp.arange(PACK_WIDTH, dtype=jnp.uint32)
    # a reduction over the 32 shifted bits (disjoint: sum == OR).  Not
    # an OR chain of 32 strided slices: the TPU compiler (jaxlib 0.9.0)
    # gets bits 16-22 of every word wrong for leaves of a few thousand
    # coordinates (e.g. a [1152] norm scale)
    return jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)


def unpack_signs(words: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`pack_signs`; returns (..., n) int8 in {-1,+1}."""
    shifts = jnp.arange(PACK_WIDTH, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    bits = bits.reshape(words.shape[:-1] + (-1,))[..., :n]
    return jnp.where(bits == 1, jnp.int8(1), jnp.int8(-1))


def majority_vote(signs: jax.Array, mask: jax.Array | None = None,
                  axis: int = 0) -> jax.Array:
    """Edge-server majority vote  s = sgn(sum_k w_k sgn_k)  over ``axis``.

    signs: int8 {-1,+1} with voter axis ``axis``.
    mask:  optional per-voter weights broadcastable to ``signs`` --
           {0,1} masks or nonnegative integer data shares ``|D_qk|``
           (the weighted popcount vote); weight 0 abstains (contributes
           0 to the tally).
    Ties resolve to +1 (consistent with ``sgn``); an empty quorum (all
    weights 0) abstains entirely: vote 0.
    """
    tally = signs.astype(jnp.int32)
    if mask is None:
        return sgn(jnp.sum(tally, axis=axis).astype(jnp.float32))
    m = jnp.asarray(mask)
    if m.ndim < tally.ndim:   # [K] voter weights -> broadcast over leaf
        m = m.reshape(m.shape + (1,) * (tally.ndim - m.ndim))
    m = m.astype(jnp.int32)
    vote = sgn(jnp.sum(tally * m, axis=axis).astype(jnp.float32))
    n_eff = jnp.sum(m, axis=axis)
    return jnp.where(n_eff > 0, vote, jnp.int8(0))


def majority_vote_packed(words: jax.Array, n: int,
                         mask: jax.Array | None = None) -> jax.Array:
    """Majority vote from bit-packed per-voter words.

    words: (K, ceil(n/32)) uint32 -- one packed sign row per voter;
    mask: optional (K,) {0,1} voter mask or integer vote weights.
    Returns (n,) int8 vote.  Equivalent to
    ``majority_vote(unpack_signs(words, n), mask, axis=0)`` but computed via
    bit-plane popcount (this is the faithful "edge receives K one-bit
    uplinks and votes" path); weighted tallies and the empty-quorum
    abstention follow the same conventions.
    """
    shifts = jnp.arange(PACK_WIDTH, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)      # (K, w, 32)
    bits = bits.reshape(words.shape[0], -1)[:, :n]           # (K, n)
    if mask is not None:
        m = mask.astype(jnp.int32).reshape(-1, 1)
        pos = jnp.sum(bits.astype(jnp.int32) * m, axis=0)
        k_eff = jnp.sum(m)
    else:
        pos = jnp.sum(bits, axis=0).astype(jnp.int32)
        k_eff = words.shape[0]
    # vote = sgn(2*pos - k_eff); ties (2*pos == k_eff) -> +1.
    vote = jnp.where(2 * pos >= k_eff, jnp.int8(1), jnp.int8(-1))
    if mask is not None:
        vote = jnp.where(k_eff > 0, vote, jnp.int8(0))
    return vote


def ternary_quantize(x: jax.Array, rng: jax.Array) -> jax.Array:
    """Unbiased stochastic ternary quantizer (paper eq. in Sec. V-B).

    Q(x)_i = ||x||_2 * sign(x_i) with prob |x_i|/||x||_2, else 0; Q(0)=0.
    E[Q(x)] = x.  Wire cost ~ sign bit + support bit per coordinate + one
    32-bit scale (Table II row 'Hier-Local-QSGD').
    """
    norm = jnp.linalg.norm(x)
    p = jnp.where(norm > 0, jnp.abs(x) / jnp.maximum(norm, 1e-30), 0.0)
    keep = jax.random.uniform(rng, x.shape) < p
    return jnp.where(keep, norm * jnp.sign(x), 0.0).astype(x.dtype)


# ---------------------------------------------------------------------------
# Wire-cost accounting (Table II of the paper), in bits per device per
# global round, for a d-dimensional model and T_E local steps.
# ---------------------------------------------------------------------------

def uplink_bits(method: str, d: int, t_e: int, clients: int = 1,
                participation_rate: float = 1.0) -> int | float:
    """Device->edge uplink bits per global round (Table II).

    With K virtual clients per physical slice (``core.clients``) each
    PARTICIPATING client sends its own full per-client stream (1 bit
    per coordinate per local step for the sign methods, plus the DC
    anchor) and a masked-out client sends nothing, so the expected
    per-slice uplink is ``clients * participation_rate * base``.  The
    legacy single-client call (``clients=1``, full participation)
    returns the exact integer Table II entry; the virtual-client form
    is an expectation and may be fractional.  Consistency with the
    dry-run pricing (``benchmarks/cost_model.clients_rows``) is pinned
    by tests/test_signs.py.
    """
    if method == "hier_sgd":
        base = 32 * t_e * d
    elif method == "hier_local_qsgd":        # sign+support bits + scale
        base = t_e * (2 * d + 32)
    elif method == "hier_signsgd":
        base = t_e * d
    elif method == "dc_hier_signsgd":        # + one full-precision anchor
        base = t_e * d + 32 * d
    elif method in ("scaffold_hier_signsgd", "mtgc_hier_signsgd"):
        # the control-variate refresh uploads one full-precision anchor
        # gradient per participating client per round, exactly like DC
        base = t_e * d + 32 * d
    else:
        raise ValueError(f"unknown method {method!r}")
    if clients == 1 and participation_rate >= 1.0:
        return base
    return clients * participation_rate * base
