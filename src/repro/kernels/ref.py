"""Pure-jnp oracles for the Pallas kernels (the ground truth in tests).

These mirror ``repro.core.signs`` exactly; kernels are validated
element-wise against them over shape/dtype sweeps (interpret mode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import signs

PACK = signs.PACK_WIDTH
TILE = 4096                      # coordinates per 128-word row


def pack_planes(s: jax.Array) -> jax.Array:
    """[..., n] {-1,+1} signs (n % 4096 == 0) -> [..., n/32] uint32 words
    in the kernels' lane-plane bit order: bit j of word w is the sign of
    coordinate ``w + 128*j`` of the w-th 4096-wide row (1 = +1)."""
    *lead, c = s.shape
    wpb = TILE // PACK
    bits = (s >= 0).astype(jnp.uint32).reshape(*lead, c // TILE, PACK,
                                                wpb)
    shifts = jnp.arange(PACK, dtype=jnp.uint32)[:, None]
    words = jax.lax.reduce(bits << shifts, jnp.uint32(0),
                           jax.lax.bitwise_or, (bits.ndim - 2,))
    return words.reshape(*lead, c // PACK)


def unpack_planes(words: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_planes`: [..., W] words -> [..., W*32]
    int8 signs in coordinate order."""
    *lead, w = words.shape
    wpb = TILE // PACK
    blocks = words.reshape(*lead, w // wpb, 1, wpb)
    shifts = jnp.arange(PACK, dtype=jnp.uint32)[:, None]
    bits = (blocks >> shifts) & jnp.uint32(1)           # [..., b, 32, wpb]
    return jnp.where(bits == 1, jnp.int8(1), jnp.int8(-1)).reshape(
        *lead, w * PACK)


def sign_pack_ref(g: jax.Array, delta: jax.Array | None, rho: float
                  ) -> jax.Array:
    """(g, delta) -> packed uint32 words in the lane-plane bit order.

    g: [S, L, 128] voter slabs; delta: optional [S/reps, L, 128]
    correction shared by ``reps`` consecutive slabs; returns
    [S, L/32, 128] (``kernels.sign_pack``)."""
    u = g.astype(jnp.float32)
    if delta is not None and rho:
        reps = g.shape[0] // delta.shape[0]
        u = u + rho * jnp.repeat(delta.astype(jnp.float32), reps, axis=0)
    s, rows, lanes = g.shape
    words = pack_planes(signs.sgn(u).reshape(s, rows * lanes))
    return words.reshape(s, rows // PACK, lanes)


def vote_update_ref(packed: jax.Array, v: jax.Array, mu: float,
                    mask: jax.Array | None = None) -> jax.Array:
    """packed: [P, K, L/32, 128] lane-plane words; v: [P, L, 128] ->
    v - mu * vote.

    mask: optional [P, K] voter mask or integer vote weights -- the
    weighted-popcount / empty-quorum-abstains conventions come from
    ``signs.majority_vote`` (matching the Pallas kernel)."""
    p, k = packed.shape[:2]
    s = unpack_planes(packed.reshape(p, k, -1))         # [P, K, n]
    m = None if mask is None else mask[:, :, None]
    vote = signs.majority_vote(s, m, axis=1).reshape(v.shape)
    return (v.astype(jnp.float32) - mu * vote.astype(jnp.float32)
            ).astype(v.dtype)


def tally_acc_ref(u_buf: jax.Array, d_buf: jax.Array | None, rho: float,
                  weights: jax.Array, tally: jax.Array) -> jax.Array:
    """Streamed-client tally accumulate oracle (``kernels.tally_acc``).

    u_buf: [P, D, n] float pre-sign directions of ONE client; d_buf:
    [P, n] shared correction or None; weights: [P, D] integer vote
    weights; tally: [P, D, n] signed int tally.  Returns
    ``tally + w * sgn(u + rho*delta)`` with the product in int32 and
    the sign computed in f32 exactly like the kernel (and like
    ``sign_pack_ref``: ``x >= 0 -> +1``)."""
    u = u_buf.astype(jnp.float32)
    if d_buf is not None and rho:
        u = u + rho * d_buf[:, None].astype(jnp.float32)
    s = jnp.where(u >= 0, jnp.int32(1), jnp.int32(-1))
    add = weights.astype(jnp.int32)[:, :, None] * s
    return (tally.astype(jnp.int32) + add).astype(tally.dtype)


def ternary_quant_ref(x: jax.Array, u: jax.Array, norm: jax.Array
                      ) -> jax.Array:
    """Stochastic ternary quantizer given uniforms u and global l2 norm."""
    p = jnp.where(norm > 0, jnp.abs(x) / jnp.maximum(norm, 1e-30), 0.0)
    return jnp.where(u < p, norm * jnp.sign(x), 0.0).astype(x.dtype)
