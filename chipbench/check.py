"""The comparison that decides ``correct``.

The reference (``reference/fed.py`` with the configuration's model)
follows the first steps the timed program took, from the same weights
and batches, and these numbers measure how far the program lies from
it.  Every norm is taken per leaf of the parameter tree and per edge,
and each number reports its worst leaf:

  loss_gap        |L_prog - L_ref| / |L_ref| of each edge's mean loss,
                  worst of the first three steps;
  vote_norm_gap   the first step's vote, as the optimizer gets it (the
                  sign vector): |  |v_prog| - |v_ref|  | over the larger
                  of |v_ref| and the median leaf's;
  vote_mismatch   the share of the first step's reference direction
                  (the share-weighted pre-sign vector) whose coordinates
                  the program voted otherwise: sum |dir| where the votes
                  differ / sum |dir|;
  change_gap      the parameters' change after three steps, as the
                  norm gap above;
  delta_gap       the correction staged at step 0, as the norm gap above
                  (only where the reference's correction is not zero,
                  i.e. with more than one edge).

Leaves whose first reference direction is under a thousandth of the
median leaf's are nought to rounding, and are left out of the leaf
numbers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import fed

KEEP_FLOOR = 1e-3


@jax.jit
def _update_stats(p0, p1, vote_r, dir_r, mu):
    v_p = jnp.round((p0 - p1.astype(jnp.float32)) / mu)
    return _vote_stats(v_p, vote_r, dir_r)


@jax.jit
def _vote_stats(v_p, vote_r, dir_r):
    v_p = v_p.astype(jnp.float32)
    v_r = vote_r.astype(jnp.float32)
    mass = jnp.abs(dir_r)
    return jnp.stack([jnp.sqrt(jnp.sum(v_p * v_p)),
                      jnp.sqrt(jnp.sum(v_r * v_r)),
                      jnp.sum(jnp.where(v_p != v_r, mass, 0.0)),
                      jnp.sum(mass)])


@jax.jit
def _change_norm(p, p0):
    d = p.astype(jnp.float32) - p0
    return jnp.sqrt(jnp.sum(d * d))


@jax.jit
def _norm(x):
    x = x.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(x * x))


def _leaves(tree):
    return jax.tree.leaves(tree)


def _on(x, dev):
    """``x`` (a host array, or a device array on any chip) on ``dev``."""
    return jax.device_put(x, dev)


def _gap(prog: list, ref: list, keep: list) -> float:
    """Worst |prog - ref| / max(ref, median ref) over the kept leaves."""
    med = float(np.median(ref))
    return max((abs(p - r) / max(r, med, 1e-30)
                for p, r, k in zip(prog, ref, keep) if k), default=0.0)


def numbers(side: dict, ref: dict, p0, mu: float,
            detail: list | None = None) -> dict:
    """The compared numbers of one side (the program, the control or a
    planted fault) against the reference's ``fed.run`` output.

    side: ``losses`` [steps, P]; per-edge leaf trees ``p3`` (after three
    steps) and either ``p1`` (after one step) or ``vote1``; ``delta``
    (staged at step 0) or None.  ``p0`` is one replica's initial tree.
    ``detail``, when given, receives the per-step losses and one record
    per edge and leaf.
    """
    out = {}
    lr = ref["losses"]
    out["loss_gap"] = float(np.max(np.abs(side["losses"] - lr)
                                   / np.abs(lr)))
    p0l = _leaves(p0)
    dev = next(iter(p0l[0].devices()))
    if detail is not None:
        detail.append({"losses": np.asarray(side["losses"]).tolist(),
                       "ref_losses": np.asarray(lr).tolist()})
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(p0)[0]]
    vote_norm, mism, change, dgap = [], [], [], []
    for q in range(len(ref["params"])):
        dir_r = _leaves(ref["dir1"][q])
        vote_r = _leaves(ref["vote1"][q])
        dnorm = [float(_norm(x)) for x in dir_r]
        floor = KEEP_FLOOR * float(np.median(dnorm))
        keep = [n >= floor for n in dnorm]
        stats = []
        for i, (a, vr, dr) in enumerate(zip(p0l, vote_r, dir_r)):
            vr, dr = _on(vr, dev), _on(dr, dev)
            if side.get("vote1") is not None:
                s = _vote_stats(_on(_leaves(side["vote1"][q])[i], dev),
                                vr, dr)
            else:
                s = _update_stats(a, _on(_leaves(side["p1"][q])[i], dev),
                                  vr, dr, mu)
            stats.append([float(x) for x in s])
            del vr, dr
        vote_norm.append(_gap([s[0] for s in stats], [s[1] for s in stats],
                              keep))
        mism.append(max((s[2] / s[3] for s, k in zip(stats, keep)
                         if k and s[3] > 0), default=0.0))
        ch_p = [float(_change_norm(_on(x, dev), a))
                for x, a in zip(_leaves(side["p3"][q]), p0l)]
        ch_r = [float(_change_norm(_on(x, dev), a))
                for x, a in zip(_leaves(ref["params"][q]), p0l)]
        change.append(_gap(ch_p, ch_r, keep))
        if detail is not None:
            detail.extend(
                {"pod": q, "leaf": paths[i], "keep": keep[i],
                 "dir_norm": dnorm[i], "vote_norm": stats[i][:2],
                 "mismatch": stats[i][2] / max(stats[i][3], 1e-30),
                 "change": [ch_p[i], ch_r[i]]}
                for i in range(len(p0l)))
        if ref.get("delta") is not None:
            d_r = [float(_norm(x)) for x in _leaves(ref["delta"][q])]
            if max(d_r) > 0:
                d_p = [float(_norm(_on(x, dev)))
                       for x in _leaves(side["delta"][q])]
                dgap.append(_gap(d_p, d_r, keep))
    out["vote_norm_gap"] = max(vote_norm)
    out["vote_mismatch"] = max(mism)
    out["change_gap"] = max(change)
    if dgap:
        out["delta_gap"] = max(dgap)
    return out


def reference(cell, program, seed: int, pool: np.ndarray, **fault) -> dict:
    """The reference (or, with ``fault``, the control or a planted
    fault) over the first three batches of ``pool``."""
    from harness import CHECK_STEPS
    return fed.run(cell.ref, cell.config["model"], cell.traffic,
                   initial(program, seed), pool[:CHECK_STEPS],
                   program.edge_weights, program.dev_weights,
                   cell.traffic["clients"].get("seed", 0),
                   places=program.pod_devices, **fault)


def initial(program, seed: int):
    """One replica's initial weights, made again from the seed."""
    from harness import seed_key
    return program.params0(seed_key(seed, 0))


def compare_program(cell, program, keep: dict, seed: int,
                    pool: np.ndarray, detail: list | None = None) -> dict:
    ref = reference(cell, program, seed, pool)
    side = {"losses": keep["losses"], "p1": keep["p1"], "p3": keep["p3"],
            "delta": keep["delta"]}
    return numbers(side, ref, initial(program, seed), cell.traffic["mu"],
                   detail)


def judge(values: dict, limits: dict | None) -> dict:
    """name -> {value, limit, ok} for each number the cell's limits file
    compares.  Without a limits file every number is shown against no
    limit, and none passes."""
    if limits is None:
        return {k: {"value": v, "limit": None, "ok": False}
                for k, v in values.items()}
    out = {}
    for name, limit in limits["limits"].items():
        value = values.get(name)
        ok = value is not None and math.isfinite(value) and value <= limit
        out[name] = {"value": value, "limit": limit, "ok": ok}
    return out
