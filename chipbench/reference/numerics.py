"""Plain arithmetic shared by the reference models.

Every contraction goes through :meth:`Numerics.einsum`.  At
``precision="float32"`` it is an f32 einsum at ``Precision.HIGHEST`` (a
TPU otherwise runs f32 matmuls in bf16 passes).  At
``precision="float8"`` -- the control, one step below the bf16 the
configurations state -- both operands are first rounded to
float8_e4m3fn with a per-tensor scale (amax / 448), the way an fp8
training path feeds its matmuls; the gradient passes straight through
the rounding, so the backward contractions read the rounded operands.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "float8")
_FP8_MAX = 448.0


def _round_fp8(x):
    x = x.astype(jnp.float32)
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / _FP8_MAX, 1.0)
    y = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(y - x)


class Numerics:
    def __init__(self, precision: str = "float32"):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision

    def operand(self, x):
        if self.precision == "float8":
            return _round_fp8(x)
        return x.astype(jnp.float32)

    def einsum(self, spec: str, a, b):
        out = jnp.einsum(spec, self.operand(a), self.operand(b),
                         precision=HIGHEST)
        return self.operand(out)


def rms_norm(g, x, eps):
    """x / rms(x) * (1 + g): the scale is stored as g = gain - 1."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + g)


def next_token_loss(logits, tokens, keep_half: bool = False):
    """Mean next-token cross-entropy of [b, t, V] logits; the last
    position has no target.  ``keep_half`` averages over the first half
    of the positions only (a planted fault: half the batch left out)."""
    t = tokens.shape[-1]
    targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    weight = (jnp.arange(t) < t - 1).astype(jnp.float32)
    if keep_half:
        weight = weight * (jnp.arange(t) < t // 2)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * weight
    return jnp.sum(nll) / (jnp.sum(weight) * tokens.shape[0])


def causal_conv(x, w):
    """Depthwise causal convolution: out_t = sum_i w[i] x[t - (k-1) + i]."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + t] * w[i] for i in range(k))
