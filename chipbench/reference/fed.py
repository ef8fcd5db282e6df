"""Plain federated arithmetic of DC-HierSignSGD (the paper's Algorithms 1
and 2), for the reference that decides ``correct``.

One global round is T_E local steps.  At a round boundary the cloud
first averages the edge models (weights D_q / N), then the anchor pass
takes every voter's gradient at that model: c_q = sum_k s_qk g_qk per
edge, c = sum_q (D_q / N) c_q, and the fresh correction delta_q = c - c_q
is staged; the correction in use is the one staged a round earlier.
Each local step, every voter k of edge q sends sgn(g_qk + rho * delta_q)
(sgn(0) = +1); the edge takes the weighted majority over the voters that
participate this round (ties to +1, no voter: 0) and steps its model by
-mu times that vote.

Participation follows the pinned counter hash of the configuration
(splitmix32 of the client index, the seed and the round), computed here
in numpy.  Imports nothing of the system under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference.numerics import Numerics

_M32 = np.uint64(0xFFFFFFFF)


def _splitmix32(x):
    x = np.asarray(x, np.uint64) & _M32
    x = ((x ^ (x >> np.uint64(16))) * np.uint64(0x7FEB352D)) & _M32
    x = ((x ^ (x >> np.uint64(15))) * np.uint64(0x846CA68B)) & _M32
    return x ^ (x >> np.uint64(16))


def participation(clients: dict, seed: int, pods: int, devices: int,
                  rnd: int) -> np.ndarray:
    """[P, D, K] {0, 1} participation of round ``rnd``."""
    k = clients["count"]
    shape = (pods, devices, k)
    mode = clients.get("participation", "full")
    if mode == "full":
        return np.ones(shape, np.float32)
    if mode != "bernoulli":
        raise ValueError(f"participation {mode!r} has no reference")
    idx = np.arange(pods * devices * k, dtype=np.uint64).reshape(shape)
    base = _splitmix32(np.uint64(seed) ^ _splitmix32(np.uint64(rnd)))
    words = _splitmix32(idx ^ base)
    thresh = np.uint64(int(round(clients["rate"] * (1 << 24))))
    return ((words >> np.uint64(8)) < thresh).astype(np.float32)


def shares(dev_weights: np.ndarray, part: np.ndarray) -> np.ndarray:
    """[P, D, K] aggregation shares of the participating voters."""
    raw = dev_weights[:, :, None].astype(np.float64) * part
    tot = raw.sum(axis=(1, 2), keepdims=True)
    return np.where(tot > 0, raw / np.where(tot > 0, tot, 1.0), 0.0)


@jax.jit
def _fold_signs(tally, g, delta, rho, w):
    if delta is None:            # no correction staged yet: it is zero
        delta = jax.tree.map(lambda _: 0.0, g)
    return jax.tree.map(
        lambda t, gl, dl: t + (w * jnp.where(gl + rho * dl >= 0, 1, -1)
                               ).astype(t.dtype), tally, g, delta)


def _fold_scaled(acc, g, s: float):
    """acc + s * g on the host (acc None: s * g); g may be on a device."""
    def one(a, gl):
        gl = np.asarray(gl, np.float32)
        if a is None:
            return np.float32(s) * gl
        a += np.float32(s) * gl
        return a
    if acc is None:
        return jax.tree.map(lambda gl: one(None, gl), g)
    return jax.tree.map(one, acc, g)


@jax.jit
def _descend(params, tally, n_eff, mu):
    def one(p, t):
        vote = jnp.where(n_eff > 0, jnp.where(t >= 0, 1, -1), 0)
        return p - mu * vote.astype(p.dtype), vote.astype(jnp.int8)
    out = jax.tree.map(one, params, tally)
    is_pair = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is_pair),
            jax.tree.map(lambda o: o[1], out, is_leaf=is_pair))


def _mean_on(trees: list, weights, dev):
    """sum_q w_q tree_q on ``dev``, moving one leaf at a time."""
    leaves = [jax.tree.leaves(t) for t in trees]
    out = []
    for parts in zip(*leaves):
        acc = None
        for w, x in zip(weights, parts):
            x = x if dev is None else jax.device_put(x, dev)
            acc = w * x if acc is None else acc + w * x
        out.append(acc)
    return jax.tree.unflatten(jax.tree.structure(trees[0]), out)


def run(ref, model: dict, traffic: dict, params0, tokens: np.ndarray,
        edge_weights: np.ndarray, dev_weights: np.ndarray, seed: int, *,
        precision: str = "float32", keep_half: bool = False,
        exchange: bool = True, places: list | None = None) -> dict:
    """Follow ``tokens.shape[0]`` steps from ``params0`` (one replica's
    f32 tree, every edge starts from it).

    tokens: [steps, P, D, b, L]; client c of device d owns rows
    [c*b/K, (c+1)*b/K).  ``precision`` and ``keep_half`` make the control
    and a planted fault; ``exchange=False`` plants another: each edge
    counts only its first device's voters, and no cloud mean is taken.
    ``places``, one device per edge, keeps each edge's trees on its own
    chip (a whole replica per edge does not fit one chip twice over).

    Returns per-pod lists: ``vote1`` (int8 tree, the first step's vote),
    ``dir1`` (f32 tree on the host, the first step's share-weighted
    pre-sign direction), ``params`` (after the last step), ``delta`` (the
    correction staged at step 0), and ``losses`` [steps, P] (mean over
    each edge's voters, present or not).
    """
    steps, pods, devices, b, _ = tokens.shape
    clients = traffic["clients"]
    k = clients["count"]
    rows = b // k
    t_e, rho, mu = traffic["t_e"], traffic["rho"], traffic["mu"]
    delta_dtype = jnp.dtype(traffic["delta_dtype"])
    nx = Numerics(precision)
    lossgrad = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss(nx, model, p, t, keep_half)))
    zeros_like = jax.jit(lambda t, dt: jax.tree.map(
        lambda x: jnp.zeros(x.shape, dt), t), static_argnums=1)
    vmax = devices * k
    tally_dt = jnp.int8 if vmax <= 127 else jnp.int32

    places = places or [None] * pods
    put = lambda t, q: t if places[q] is None else jax.device_put(t, places[q])
    params = [put(params0, q) for q in range(pods)]
    del params0
    delta = [None] * pods            # the correction in use: zero at first
    delta_next = list(delta)         # the staged one
    staged0 = None
    out = {"losses": np.zeros((steps, pods)), "vote1": None, "dir1": None}
    voters = range(devices) if exchange else range(1)
    for s in range(steps):
        part = participation(clients, seed, pods, devices, s // t_e)
        sh = shares(dev_weights, part)
        boundary = s % t_e == 0
        if boundary:
            if exchange and pods > 1:
                avg = _mean_on(params, [float(w) for w in edge_weights],
                               places[0])
                params = [put(avg, q) for q in range(pods)]
                del avg
            # the correction staged a round ago is used from now on; the
            # one this boundary's anchor pass makes is staged below
            delta = delta_next
        accs, votes, new_params = [], [], []
        for q in range(pods):
            tally = zeros_like(params[q], tally_dt)
            # the f32 share-weighted direction (anchor c_q at a boundary)
            # is summed on the host: a second whole-model f32 tree beside
            # the model and its gradient would not fit one chip
            want_acc = boundary or s == 0
            acc = None
            n_eff = 0
            for d in range(devices):
                for c in range(k):
                    toks = put(jnp.asarray(
                        tokens[s, q, d, c * rows:(c + 1) * rows]), q)
                    lval, g = lossgrad(params[q], toks)
                    out["losses"][s, q] += float(lval) / (devices * k)
                    w = int(part[q, d, c])
                    if d in voters and w:
                        tally = _fold_signs(tally, g, delta[q], rho, w)
                        n_eff += w
                    if want_acc and sh[q, d, c]:
                        acc = _fold_scaled(acc, g, float(sh[q, d, c]))
                    del g
            new_p, vote = _descend(params[q], tally, n_eff, mu)
            new_params.append(new_p)
            if want_acc and acc is None:         # nobody participates
                acc = jax.tree.map(
                    lambda p: np.zeros(p.shape, np.float32), params[q])
            accs.append(acc)
            votes.append(vote)
        if s == 0:
            # the first step's direction: shares x (g + rho delta); the
            # correction in use during round 0 is zero
            out["vote1"], out["dir1"] = votes, accs
        if boundary:
            c_all = (_mean_on(accs, [float(w) for w in edge_weights], None)
                     if exchange and pods > 1 else None)
            delta_next = [jax.tree.map(
                lambda a, cq: put(jnp.asarray((a - cq).astype(delta_dtype)),
                                  q),
                c_all if c_all is not None else accs[q], accs[q])
                for q in range(pods)]
            del c_all
            if s == 0:
                staged0 = delta_next
        params = new_params
    out["params"] = params
    out["delta"] = staged0
    return out
