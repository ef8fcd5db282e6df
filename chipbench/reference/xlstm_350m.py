"""Plain float32 reference of the xLSTM language model
(Beck et al., "xLSTM: Extended Long Short-Term Memory", arXiv:2405.04517).

Blocks run in the configuration's order: ``m_per_s`` mLSTM blocks, then
one sLSTM block, repeated.  The mLSTM uses the paper's parallel form
(log-space decay matrix with its own row stabilizer; the result does
not depend on the stabilizer); the sLSTM is its recurrence, step by
step.  What the configuration departs from in the paper is listed under
``assumed`` in ``configs/xlstm-350m.json`` and followed here.  Imports
nothing of the system under test.

The parameter tree uses the names and stacking of the trained model's
tree (stacked per block kind, in layer order), so that one set of
weights made from the seed feeds both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.numerics import causal_conv, next_token_loss, rms_norm


def _sizes(m):
    d, h = m["d_model"], m["n_heads"]
    x = m["xlstm"]
    d_in = int(x["proj_factor"] * d)
    ff = int(4 * d / 3)
    period = x["m_per_s"] + 1
    groups = m["n_layers"] // period
    if groups * period != m["n_layers"]:
        raise ValueError("depth must be whole periods of the layer pattern")
    return d, h, d_in, ff, x["conv_kernel"], groups, x["m_per_s"]


def param_spec(m) -> dict:
    """Nested dict of (shape, init) per leaf; init is ("normal", std),
    ("zeros",) or ("const", value)."""
    d, h, d_in, ff, kc, groups, mps = _sizes(m)
    v = m["vocab"]
    nm, ns = groups * mps, groups
    he = lambda fan: ("normal", 1.0 / math.sqrt(fan))
    mlstm = {
        "up": ((nm, d, 2 * d_in), he(d)),
        "conv": ((nm, kc, d_in), he(kc)),
        "wq": ((nm, d_in, d_in), he(d_in)),
        "wk": ((nm, d_in, d_in), he(d_in)),
        "wv": ((nm, d_in, d_in), he(d_in)),
        "wi": ((nm, d_in, h), he(d_in)),
        "wf": ((nm, d_in, h), he(d_in)),
        "fb": ((nm, h), ("const", 3.0)),
        "norm": ((nm, d_in), ("zeros",)),
        "down": ((nm, d_in, d), he(d_in)),
    }
    hd = d // h
    slstm = {
        "wx": ((ns, d, 4 * d), he(d)),
        "wr": ((ns, h, hd, 4 * hd), he(hd)),
        "fb": ((ns, h), ("const", 3.0)),
        "norm": ((ns, d), ("zeros",)),
        "up": ((ns, d, 2 * ff), he(d)),
        "down": ((ns, ff, d), he(ff)),
    }
    return {
        "embed": {"table": ((v, d), ("normal", 0.02))},
        "stacks": {
            "mlstm": {"n1": ((nm, d), ("zeros",)), "mlstm": mlstm},
            "slstm": {"n1": ((ns, d), ("zeros",)), "slstm": slstm},
        },
        "head": {"norm": ((d,), ("zeros",)), "out": ((d, v), he(d))},
    }


def _mlstm_block(nx, m, p, x):
    """x [b, t, d] -> mLSTM block output (to be added to x)."""
    d, h, d_in, *_ = _sizes(m)
    b, t, _ = x.shape
    hd = d_in // h
    xn = rms_norm(p["n1"], x, m["norm_eps"])
    q = p["mlstm"]
    up = nx.einsum("btd,de->bte", xn, q["up"])
    u, z = up[..., :d_in], up[..., d_in:]
    uc = jax.nn.silu(causal_conv(u, q["conv"]))
    qh = nx.einsum("bte,ef->btf", uc, q["wq"]).reshape(b, t, h, hd)
    kh = nx.einsum("bte,ef->btf", uc, q["wk"]).reshape(b, t, h, hd)
    kh = kh / math.sqrt(hd)
    vh = nx.einsum("bte,ef->btf", u, q["wv"]).reshape(b, t, h, hd)
    ig = nx.einsum("bte,eh->bth", uc, q["wi"])
    fg = nx.einsum("bte,eh->bth", uc, q["wf"]) + q["fb"]
    # log D[i, j] = sum_{j < s <= i} log sigmoid(f_s) + i_j  (j <= i)
    logf = jax.nn.log_sigmoid(fg)
    csum = jnp.cumsum(logf, axis=1)
    logd = csum[:, :, None, :] - csum[:, None, :, :] + ig[:, None, :, :]
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    logd = jnp.where(causal, logd, -jnp.inf)
    stab = jnp.max(logd, axis=2, keepdims=True)            # [b, i, 1, h]
    dmat = jnp.exp(logd - stab)
    c = nx.einsum("bihd,bjhd->bijh", qh, kh) * dmat
    norm = jnp.maximum(jnp.abs(jnp.sum(c, axis=2)), jnp.exp(-stab[:, :, 0]))
    hv = nx.einsum("bijh,bjhd->bihd", c, vh) / norm[..., None]
    y = rms_norm(q["norm"], hv.reshape(b, t, d_in), m["norm_eps"])
    y = y * jax.nn.silu(z)
    return nx.einsum("bte,ed->btd", y, q["down"])


def _slstm_block(nx, m, p, x):
    d, h, _, ff, *_ = _sizes(m)
    b, t, _ = x.shape
    hd = d // h
    s = p["slstm"]
    xn = rms_norm(p["n1"], x, m["norm_eps"])
    pre = nx.einsum("btd,de->bte", xn, s["wx"]).reshape(b, t, h, 4 * hd)

    def cell(carry, pre_t):
        c, n, hprev, mst = carry
        g = pre_t + nx.einsum("bhp,hpq->bhq", hprev, s["wr"])
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        i_t = jnp.mean(gi, axis=-1)                         # one per head
        logf = jax.nn.log_sigmoid(jnp.mean(gf, axis=-1) + s["fb"])
        m_t = jnp.maximum(logf + mst, i_t)
        f_s = jnp.exp(logf + mst - m_t)[..., None]
        i_s = jnp.exp(i_t - m_t)[..., None]
        c = f_s * c + i_s * jnp.tanh(gz)
        n = f_s * n + i_s
        hnew = jax.nn.sigmoid(go) * c / jnp.maximum(n, 1.0)
        return (c, n, hnew, m_t), hnew

    zeros = jnp.zeros((b, h, hd), jnp.float32)
    init = (zeros, jnp.ones((b, h, hd), jnp.float32), zeros,
            jnp.zeros((b, h), jnp.float32))
    _, hs = jax.lax.scan(cell, init, jnp.moveaxis(pre, 1, 0))
    y = rms_norm(s["norm"], jnp.moveaxis(hs, 0, 1).reshape(b, t, d),
                 m["norm_eps"])
    uv = nx.einsum("btd,de->bte", y, s["up"])
    y = jax.nn.silu(uv[..., :ff]) * uv[..., ff:]
    return nx.einsum("btf,fd->btd", y, s["down"])


def loss(nx, m, params, tokens, keep_half: bool = False):
    """Mean next-token loss of one voter's [b, t] tokens."""
    *_, groups, mps = _sizes(m)
    x = jnp.take(params["embed"]["table"], tokens, axis=0)
    x = nx.operand(x)
    mst = params["stacks"]["mlstm"]
    sst = params["stacks"]["slstm"]
    mblock = jax.checkpoint(lambda p, x: x + _mlstm_block(nx, m, p, x))
    sblock = jax.checkpoint(lambda p, x: x + _slstm_block(nx, m, p, x))
    for g in range(groups):
        seg = jax.tree.map(lambda a: a[g * mps:(g + 1) * mps], mst)
        x, _ = jax.lax.scan(lambda x, p: (mblock(p, x), None), x, seg)
        x = sblock(jax.tree.map(lambda a: a[g], sst), x)
    x = rms_norm(params["head"]["norm"], x, m["norm_eps"])
    logits = nx.einsum("btd,dv->btv", x, params["head"]["out"])
    return next_token_loss(logits, tokens, keep_half)


def flops_per_token(m, seq_len: int) -> float:
    """Training FLOPs per token: 6 x the parameters that multiply
    activations (all but the input embedding table; norms and biases
    excluded), plus the mLSTM's causal quadratic term, 6 * t * d_in per
    mLSTM block (q.k and scores.v, each t * d_in / 2 multiply-adds per
    token on average).  Recompute is not counted."""
    d, h, d_in, ff, kc, groups, mps = _sizes(m)
    hd = d // h
    mlstm = (d * 2 * d_in + kc * d_in + 3 * d_in * d_in + 2 * d_in * h
             + d_in * d)
    slstm = d * 4 * d + h * hd * 4 * hd + d * 2 * ff + ff * d
    mult = groups * (mps * mlstm + slstm) + d * m["vocab"]
    return 6.0 * mult + groups * mps * 6.0 * seq_len * d_in
