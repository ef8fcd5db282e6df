"""Plain float32 reference of the StableLM-3B-4E1T decoder
(huggingface.co/stabilityai/stablelm-3b-4e1t, config.json), at the depth
the configuration keeps.

Pre-norm blocks: x += Attn(norm(x)); x += SwiGLU(norm(x)); multi-head
causal attention with rotary position embeddings; an untied output
head.  What the configuration departs from in that config.json is listed
under ``assumed`` in ``configs/stablelm-3b-6l.json`` and followed here.
Attention runs in query blocks, each recomputed in the backward pass,
so no [heads, t, t] score tensor of the whole sequence is ever live.
Imports nothing of the system under test.

The parameter tree uses the names and stacking of the trained model's
tree, so that one set of weights made from the seed feeds both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.numerics import next_token_loss, rms_norm

Q_BLOCK = 512


def param_spec(m) -> dict:
    d, h, hd, ff = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]
    v, n = m["vocab"], m["n_layers"]
    he = lambda fan: ("normal", 1.0 / math.sqrt(fan))
    layer = {
        "n1": ((n, d), ("zeros",)),
        "n2": ((n, d), ("zeros",)),
        "attn": {"wq": ((n, d, h, hd), he(d)), "wk": ((n, d, h, hd), he(d)),
                 "wv": ((n, d, h, hd), he(d)),
                 "wo": ((n, h, hd, d), he(h * hd))},
        "mlp": {"up": ((n, d, ff), he(d)), "gate": ((n, d, ff), he(d)),
                "down": ((n, ff, d), he(ff))},
    }
    return {
        "embed": {"table": ((v, d), ("normal", 0.02))},
        "stacks": {"dense": layer},
        "head": {"norm": ((d,), ("zeros",)), "out": ((d, v), he(d))},
    }


def _rotary(x, theta):
    """Rotate-half rotary embedding over the whole head: x [b, t, h, hd]."""
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(nx, q, k, v):
    """Causal softmax attention in query blocks; q, k, v [b, t, h, hd]."""
    b, t, h, hd = q.shape
    blk = min(Q_BLOCK, t)
    if t % blk:
        raise ValueError(f"sequence {t} is not whole blocks of {blk}")

    @jax.checkpoint
    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, blk, axis=1)
        s = nx.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(hd)
        allowed = (jnp.arange(t)[None, :]
                   <= start + jnp.arange(blk)[:, None])
        s = jnp.where(allowed[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return nx.einsum("bhqk,bkhd->bqhd", w, v)

    out = jax.lax.map(one, jnp.arange(0, t, blk))       # [n, b, blk, h, hd]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, hd)


def _layer(nx, m, p, x):
    eps = m["norm_eps"]
    a = p["attn"]
    xn = rms_norm(p["n1"], x, eps)
    q = _rotary(nx.einsum("btd,dhk->bthk", xn, a["wq"]), m["rope_theta"])
    k = _rotary(nx.einsum("btd,dhk->bthk", xn, a["wk"]), m["rope_theta"])
    v = nx.einsum("btd,dhk->bthk", xn, a["wv"])
    x = x + nx.einsum("bthk,hkd->btd", _attention(nx, q, k, v), a["wo"])
    xn = rms_norm(p["n2"], x, eps)
    f = p["mlp"]
    hmid = (jax.nn.silu(nx.einsum("btd,df->btf", xn, f["gate"]))
            * nx.einsum("btd,df->btf", xn, f["up"]))
    return x + nx.einsum("btf,fd->btd", hmid, f["down"])


def loss(nx, m, params, tokens, keep_half: bool = False):
    """Mean next-token loss of one voter's [b, t] tokens."""
    x = nx.operand(jnp.take(params["embed"]["table"], tokens, axis=0))
    layer = jax.checkpoint(lambda p, x: _layer(nx, m, p, x))
    x, _ = jax.lax.scan(lambda x, p: (layer(p, x), None), x,
                        params["stacks"]["dense"])
    x = rms_norm(params["head"]["norm"], x, m["norm_eps"])
    logits = nx.einsum("btd,dv->btv", x, params["head"]["out"])
    return next_token_loss(logits, tokens, keep_half)


def flops_per_token(m, seq_len: int) -> float:
    """Training FLOPs per token: 6 x the parameters that multiply
    activations (all but the input embedding table; norms excluded),
    plus causal attention, 6 * t * h * hd per layer (q.k and weights.v,
    each t * h * hd / 2 multiply-adds per token on average).  Recompute
    is not counted."""
    d, h, hd, ff = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]
    per_layer = 4 * d * h * hd + 3 * d * ff
    mult = m["n_layers"] * per_layer + d * m["vocab"]
    return 6.0 * mult + m["n_layers"] * 6.0 * seq_len * h * hd
