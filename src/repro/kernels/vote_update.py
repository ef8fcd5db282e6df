"""Fused edge-side vote + model update: v' = v - mu * MajorityVote(packed).

The edge server holds K one-bit uplink payloads (packed uint32 rows, one
per device) and the edge model v.  This kernel unpacks the K bit-planes,
popcount-votes per coordinate (ties -> +1, abstaining voters masked), and
applies the sign-descent update in a single read-modify-write of v --
one HBM pass over the model instead of three (unpack, vote, update).

The voter ``mask`` generalizes to nonnegative integer vote weights (the
``core.clients`` data shares |D_qk|): each bit-plane is scaled by its
weight in the int32 tally, the tie rule compares against the
participating weight sum, and an edge whose whole quorum abstains (all
weights 0) votes 0 -- the read-modify-write then leaves v unchanged.
The [P, K] weights live in SMEM and are read as scalars.

Tiling: grid over [P, cdiv(L/32, BR)] on the [P, L, 128] lane-row view
of v (``kernels.sign_pack``); per step the kernel reads a (K, BR, 128)
uint32 slab + a (32*BR, 128) block of v.  Words are in the lane-plane
bit order of ``kernels.sign_pack``: bit plane j of a (BR, 128) word
block is the sublane-strided row set ``32*r + j`` of the v block, so
unpacking is a shift and a mask per plane -- no reshape, and only one
(BR, 128) int32 tally is live at a time.

Single-device program: on multi-chip meshes it runs per-rank inside the
fused transport's ``shard_map`` program (``core.votes``) on the rank's
bucket of the flat buffer, consuming the K uplink payloads gathered over
the data axis -- the vote never sees (and the mesh never materializes)
an unsharded bit tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sign_pack import BLOCK_R, LANES, PACK, row_block


def _vote_update_kernel(m_ref, p_ref, v_ref, o_ref, *, mu: float):
    k, br, _ = p_ref.shape                          # (K, BR, 128) words
    q = pl.program_id(0)
    weight = (lambda i: 1) if m_ref is None else (lambda i: m_ref[q, i])
    n_eff = (k if m_ref is None else jax.lax.fori_loop(
        0, k, lambda i, acc: acc + weight(i), jnp.int32(0)))
    for j in range(PACK):
        def tally(i, pos, j=j):
            bit = ((p_ref[i] >> jnp.uint32(j)) & jnp.uint32(1)
                   ).astype(jnp.int32)
            return pos + bit * weight(i)

        pos = jax.lax.fori_loop(0, k, tally,
                                jnp.zeros((br, LANES), jnp.int32))
        vote = jnp.where(2 * pos >= n_eff, 1.0, -1.0).astype(jnp.float32)
        if m_ref is not None:   # empty quorum abstains: v is left unchanged
            vote = jnp.where(n_eff > 0, vote, 0.0).astype(jnp.float32)
        plane = pl.ds(j, br, stride=PACK)
        o_ref[plane, :] = (v_ref[plane, :].astype(jnp.float32) - mu * vote
                           ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("mu", "block_r", "interpret"))
def vote_update(packed: jax.Array, v: jax.Array,
                mask: jax.Array | None = None, *, mu: float,
                block_r: int = BLOCK_R, interpret: bool = False
                ) -> jax.Array:
    """packed: [P, K, L/32, 128] uint32 (lane-plane order); v: [P, L, 128]
    float; mask: [P, K] voter mask / integer vote weights, or None.
    Returns the updated v (aliased over the input when compiled)."""
    p, k, wr, lanes = packed.shape
    assert lanes == LANES and v.shape == (p, PACK * wr, LANES), (
        packed.shape, v.shape)
    br = row_block(wr, block_r)
    grid = (p, pl.cdiv(wr, br))
    vblk = pl.BlockSpec((None, PACK * br, LANES), lambda q, i: (q, i, 0))

    in_specs = [pl.BlockSpec((None, k, br, LANES), lambda q, i: (q, 0, i, 0)),
                vblk]
    args = [packed, v]
    if mask is not None:
        assert mask.shape == (p, k), (mask.shape, (p, k))
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, mask.astype(jnp.int32))
        kernel = functools.partial(_vote_update_kernel, mu=mu)
    else:
        kernel = functools.partial(
            lambda p_ref, v_ref, o_ref, *, mu: _vote_update_kernel(
                None, p_ref, v_ref, o_ref, mu=mu), mu=mu)

    # v' aliases v: the kernel is a true read-modify-write (one HBM pass
    # over the model when the caller donates v).  Interpret mode keeps
    # out-of-place semantics -- identical values either way.
    alias = ({} if interpret
             else {"input_output_aliases": {len(args) - 1: 0}})
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=vblk,
        out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype),
        interpret=interpret,
        **alias,
    )(*args)
