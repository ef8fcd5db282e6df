"""Fused device-side compressor: sgn(g + rho*delta) -> 1-bit pack (TPU).

This is the hot elementwise sweep DC-HierSignSGD adds on every local step:
read the gradient (+ stale correction), take the sign, and emit the 1-bit
wire payload.  Fusing sign+pack into one VMEM pass writes d/32 uint32
words instead of a d-byte int8 sign vector -- 8x less HBM write traffic
on a pass that is bandwidth-bound by construction (DESIGN.md Sec. 6).

Tiling: the flattened parameter stream is viewed as [S, L, 128] voter
slabs -- 128-lane rows, L a multiple of 32.  For an f32 buffer this
(8, 128)-tiled view is byte-for-byte the flat array itself, so XLA
feeds the kernel without a relayout copy.  Each grid step reads a
(32*BR, 128) block of one slab and emits a (BR, 128) uint32 word block;
the row grid is ``cdiv(L/32, BR)`` and a ragged last block is masked by
Pallas, so BR is either a multiple of 8 or all of the slab's word rows.

Bit order (lane-aligned bit planes): bit j of word (r, l) holds the
sign of row ``32*r + j``, lane l -- for flat word index w = 128*r + l
that is coordinate ``4096*r + 128*j + l``, i.e. bit j of word w is
coordinate ``w + 128*j`` of the w-th 4096-wide row.  Plane j is a
sublane-strided load (stride 32) of the block's sign bits, so packing needs no reshape and no
reduction: 32 shifted ORs, and the word a coordinate lands in does not
depend on the block size.  This order is private to the kernel route
(``kernels.vote_update`` unpacks it; ``kernels.ref`` mirrors it); the
wire format of the jnp transports (``core.signs.pack_signs``, bit j of
word w = coordinate 32*w + j) is unchanged.

The kernel is a single-device program: on multi-chip meshes it runs
per-rank inside the fused transport's ``shard_map`` program
(``core.votes``), where each rank packs its own bucket of the flat
buffer (``core.flatbuf``) and only the packed words travel (data-axis
all-gather between this kernel and ``vote_update``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PACK = 32
LANES = 128
TILE = PACK * LANES     # coordinates per word row
BLOCK_R = 64            # word rows per block: (2048, 128) f32 in VMEM


def row_block(word_rows: int, block_r: int = BLOCK_R) -> int:
    """Word rows of a kernel block over ``word_rows`` rows: ``block_r``
    (a multiple of 8; the last block may be ragged) or all of them."""
    return word_rows if word_rows <= block_r else block_r


def _sign_pack_kernel(g_ref, d_ref, o_ref, bits_ref, *, rho: float):
    # signs of the whole block first (any input dtype), then the 32
    # sublane-strided planes from the 32-bit scratch (Mosaic strides
    # 32-bit loads only)
    u = g_ref[...].astype(jnp.float32)
    if d_ref is not None:
        u = u + rho * d_ref[...].astype(jnp.float32)
    bits_ref[...] = (u >= 0).astype(jnp.uint32)
    br = o_ref.shape[0]
    word = None
    for j in range(PACK):
        bit = bits_ref[pl.ds(j, br, stride=PACK), :] << jnp.uint32(j)
        word = bit if word is None else word | bit
    o_ref[...] = word


@functools.partial(jax.jit,
                   static_argnames=("rho", "block_r", "interpret"))
def sign_pack(g: jax.Array, delta: jax.Array | None = None,
              rho: float = 0.0, *, block_r: int = BLOCK_R,
              interpret: bool = False) -> jax.Array:
    """g: [S, L, 128] float voter slabs (L % 32 == 0); delta: optional
    [S/reps, L, 128] correction shared by ``reps`` consecutive slabs
    (the flat-buffer transport orders slabs (pod, device) and the
    correction per pod) -- re-read per voter through its BlockSpec, so
    no [P, D, n] broadcast copy of the correction ever exists in HBM.

    Returns packed uint32 [S, L/32, 128] in the lane-plane bit order.
    """
    s, rows, lanes = g.shape
    assert lanes == LANES and rows % PACK == 0, g.shape
    br = row_block(rows // PACK, block_r)
    grid = (s, pl.cdiv(rows // PACK, br))
    blk = (None, PACK * br, LANES)

    in_specs = [pl.BlockSpec(blk, lambda v, i: (v, i, 0))]
    args = [g]
    if delta is not None:
        assert delta.shape[1:] == g.shape[1:] and s % delta.shape[0] == 0, (
            delta.shape, g.shape)
        reps = s // delta.shape[0]             # voters sharing each slab
        in_specs.append(pl.BlockSpec(blk, lambda v, i: (v // reps, i, 0)))
        args.append(delta)
        kernel = functools.partial(_sign_pack_kernel, rho=rho)
    else:
        kernel = functools.partial(
            lambda g_ref, o_ref, bits_ref, *, rho: _sign_pack_kernel(
                g_ref, None, o_ref, bits_ref, rho=rho), rho=rho)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, br, LANES), lambda v, i: (v, i, 0)),
        out_shape=jax.ShapeDtypeStruct((s, rows // PACK, LANES), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((PACK * br, LANES), jnp.uint32)],
        interpret=interpret,
    )(*args)
