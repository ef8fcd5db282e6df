"""Streamed-client tally accumulate: tally += w * sgn(g + rho*delta) (TPU).

The streamed virtual-client sweep (``ClientConfig.mode="stream"``,
``core.hier``) loops clients inside the step instead of widening the
voter axis: per client this kernel fuses the device-side compressor of
``sign_pack`` (gradient + stale correction -> sign bit) with the
edge-side weighted popcount of ``vote_update`` into ONE
read-modify-write of the persistent signed tally -- the client's sign
plane is never materialized in HBM, only the running tally (one int8/
int16/int32 per coordinate, dtype picked from the static weight bound
by ``core.votes.tally_dtype``) is live across the client loop.

The signed tally ``t = sum_c w_c * sgn(u_c) = 2*pos - n_eff`` defers the
sign threshold until after the loop (``core.votes.tally_vote``), where
``t >= 0`` reproduces the merged path's ``2*pos >= n_eff`` tie rule
exactly -- integer arithmetic, so the streamed trajectory is bitwise
identical to the merged-axis transports.

Tiling: grid over [S, cdiv(L, 32*BR)] on the [S, L, 128] lane-row
voter slabs of ``sign_pack``; per step the kernel reads a (32*BR, 128)
f32 block of g (+ the shared correction block, re-read per voter
through its BlockSpec) and read-modify-writes the same block of the
tally in place (aliased when compiled).  The per-voter weights arrive
as one [S] int32 array in SMEM, read as the scalar of the block's slab
-- no scalar re-tracing per client.

Single-device program: on multi-chip meshes it runs per-rank inside the
streamed fused transport's ``shard_map`` program (``core.votes``) on the
rank's model-axis bucket; the data-axis exchange happens once per local
step on the reduced tallies, not per client.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sign_pack import BLOCK_R, LANES, PACK, row_block


def _tally_acc_kernel(w_ref, g_ref, d_ref, t_ref, o_ref, *, rho: float):
    g = g_ref[...].astype(jnp.float32)
    if d_ref is not None:
        g = g + rho * d_ref[...].astype(jnp.float32)
    s = jnp.where(g >= 0, jnp.int32(1), jnp.int32(-1))
    w = w_ref[pl.program_id(0)]                     # this slab's weight
    o_ref[...] = (t_ref[...].astype(jnp.int32) + w * s).astype(o_ref.dtype)


def _tally_acc_kernel_no_delta(w_ref, g_ref, t_ref, o_ref):
    _tally_acc_kernel(w_ref, g_ref, None, t_ref, o_ref, rho=0.0)


@functools.partial(jax.jit,
                   static_argnames=("rho", "block_r", "interpret"))
def tally_acc(g: jax.Array, delta: jax.Array | None, w: jax.Array,
              tally: jax.Array, *, rho: float = 0.0,
              block_r: int = BLOCK_R, interpret: bool = False) -> jax.Array:
    """g, tally: [S, L, 128] lane-row voter slabs (L % 32 == 0); w: [S]
    int32 per-voter weights; delta: optional [S/reps, L, 128] correction
    shared by ``reps`` consecutive slabs, re-read per voter through its
    BlockSpec exactly like ``sign_pack``.  Returns the updated tally
    (int8/int16/int32), aliased over the input when compiled.
    """
    s, rows, lanes = g.shape
    assert lanes == LANES and rows % PACK == 0, g.shape
    assert tally.shape == g.shape, (tally.shape, g.shape)
    assert w.shape == (s,), (w.shape, s)
    br = row_block(rows // PACK, block_r)
    grid = (s, pl.cdiv(rows // PACK, br))
    shape = (None, PACK * br, LANES)
    blk = lambda v, i: (v, i, 0)

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(shape, blk)]
    args = [w.astype(jnp.int32), g]
    if delta is not None:
        assert delta.shape[1:] == g.shape[1:] and s % delta.shape[0] == 0, (
            delta.shape, g.shape)
        reps = s // delta.shape[0]             # voters sharing each slab
        in_specs.append(pl.BlockSpec(shape, lambda v, i: (v // reps, i, 0)))
        args.append(delta)
        kernel = functools.partial(_tally_acc_kernel, rho=rho)
    else:
        kernel = _tally_acc_kernel_no_delta
    in_specs.append(pl.BlockSpec(shape, blk))
    args.append(tally)

    # the tally aliases in place: a true read-modify-write (one HBM pass
    # over the tally when the caller donates it).  Interpret mode keeps
    # out-of-place semantics -- identical values either way.
    alias = ({} if interpret
             else {"input_output_aliases": {len(args) - 1: 0}})
    return pl.pallas_call(
        kernel,
        name="tally_acc",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(shape, blk),
        out_shape=jax.ShapeDtypeStruct(tally.shape, tally.dtype),
        interpret=interpret,
        **alias,
    )(*args)
