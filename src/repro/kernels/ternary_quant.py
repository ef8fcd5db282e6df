"""Fused stochastic ternary quantizer (Hier-Local-QSGD baseline compressor).

Q(x)_i = ||x||_2 * sign(x_i) with prob |x_i| / ||x||_2, else 0 (unbiased).
The global l2 norm is a cheap pre-pass reduction done outside; the kernel
fuses probability computation, Bernoulli draw (from supplied uniforms) and
ternarization into one VMEM sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_R = 64
BLOCK_C = 4096


def _ternary_kernel(x_ref, u_ref, n_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    norm = n_ref[0]
    p = jnp.abs(x) / jnp.maximum(norm, 1e-30)
    q = jnp.where(u < p, norm * jnp.sign(x), 0.0)
    o_ref[...] = jnp.where(norm > 0, q, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_r", "block_c", "interpret"))
def ternary_quant(x: jax.Array, u: jax.Array, norm: jax.Array, *,
                  block_r: int = BLOCK_R, block_c: int = BLOCK_C,
                  interpret: bool = False) -> jax.Array:
    """x, u: [R, C]; norm: scalar ||x||_2 (precomputed)."""
    r, c = x.shape
    assert r % block_r == 0 and c % block_c == 0
    grid = (r // block_r, c // block_c)
    return pl.pallas_call(
        _ternary_kernel,
        name="ternary_quant",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((1,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, u, norm.reshape(1))
