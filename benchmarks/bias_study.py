"""Head-to-head drift-correction bias study (``BENCH_bias.json``).

Runs the ``ref_fed`` oracle on the synthetic EMNIST-like task under the
paper's SEVERE inter-cluster regime (Dirichlet(alpha=0.1) class skew
across edges) and compares the whole method axis sharing the pre-sign
correction slot:

    hier_sgd              full-precision baseline (no bias to correct)
    hier_signsgd          plain sign-voting (the biased trajectory)
    dc_hier_signsgd       cloud-assisted anchor delta (the paper)
    scaffold_hier_signsgd per-client SCAFFOLD control variates
    mtgc_hier_signsgd     MTGC two-timescale edge/cloud correction

under the PR-5 participation regimes (full quorum / Bernoulli(0.5)
sampling / unequal |D_qk| shares, pinned per-round masks from
``core.clients``).  Each cell records the test-loss trajectory, final
loss/accuracy and the per-round DRIFT NORM

    drift(t) = sqrt( sum_q ew_q || c^(t) - c_q^(t) ||^2 )

measured from the share-weighted anchor gradients at w^(t) -- the
heterogeneity-induced bias the corrections exist to cancel.  The drift
trajectory is method-comparable (same w-independent definition), so the
JSON makes "which correction keeps the model nearest the unbiased
descent direction" directly visible.

A second axis tells the EDGE-ASSIGNMENT story under severe intra+inter
skew (Dirichlet alpha=0.1 across edges AND alpha_client=0.1 within
them): {random, clustered} client->edge assignment x {plain, DC,
SCAFFOLD, MTGC}.  Random scatter mixes the skewed clients so every edge
looks alike (small inter-edge drift, large intra-edge variance);
clustered assignment (``data.cluster``, label-histogram signatures)
concentrates similar clients per edge, maximizing exactly the
inter-cluster bias the corrections cancel -- the 2x2 shows how much of
the correction's win the placement policy can claim.

  PYTHONPATH=src python benchmarks/bias_study.py [--fast] [--out PATH]

The default profile regenerates the checked-in BENCH_bias.json.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import numpy as np

from repro.core import clients as vclients
from repro.core import ref_fed
from repro.data import emnist_like
from repro.launch import compile_cache
from repro.models import mlp

METHODS = ("hier_sgd", "hier_signsgd", "dc_hier_signsgd",
           "scaffold_hier_signsgd", "mtgc_hier_signsgd")
REGIMES = ("full", "sampled", "weighted")
# the assignment story compares the sign-voting family only (hier_sgd
# has no sign bias for the placement policy to move)
ASSIGN_METHODS = ("hier_signsgd", "dc_hier_signsgd",
                  "scaffold_hier_signsgd", "mtgc_hier_signsgd")
ASSIGNS = ("random", "clustered")
ALPHA_CLIENT = 0.1
SCHEMA = "bias_study_v2"

# K virtual clients per physical device slice: the oracle hosts them as
# K more entries per edge (devices_per_edge * K clients under edge q)
K_CLIENTS = 2
SEED = 0


def _profile(fast: bool) -> dict:
    if fast:
        return dict(q_edges=2, devices_per_edge=2, rounds=2, t_e=5,
                    batch=32, n_train=800, n_test=400)
    return dict(q_edges=4, devices_per_edge=5, rounds=6, t_e=10,
                batch=32, n_train=4000, n_test=1000)


def _vote_weights(regime: str, q_edges: int, n: int):
    """Integer |D_qk| vote weights per (edge, client) -- unit for the
    unweighted regimes, deterministic unequal 1..5 for 'weighted'."""
    if regime != "weighted":
        return [[1] * n for _ in range(q_edges)]
    return [[(q + 3 * k) % 5 + 1 for k in range(n)]
            for q in range(q_edges)]


def _mask(regime: str, cc, q_edges: int, devs: int, t: int, n: int):
    if regime != "sampled":
        return [[True] * n for _ in range(q_edges)]
    m = np.asarray(vclients.participation_mask(cc, q_edges, devs, t)) > 0.5
    return [list(m.reshape(q_edges, n)[q]) for q in range(q_edges)]


def _drift_norm(state, shares, ew, anchors) -> float:
    """sqrt(sum_q ew_q ||c - c_q||^2) from the share-weighted anchor
    gradients at the current w (the paper's inter-cluster bias)."""
    c_qs = []
    for q in range(len(anchors)):
        g = [mlp.grad_fn(state.w, anchors[q][k], None)
             for k in range(len(anchors[q]))]
        c_qs.append(ref_fed._tree_weighted_sum(shares[q], g))
    c = ref_fed._tree_weighted_sum(ew, c_qs)
    tot = 0.0
    for q, c_q in enumerate(c_qs):
        sq = sum(float(np.sum((np.asarray(u) - np.asarray(v)) ** 2))
                 for u, v in zip(jax.tree.leaves(c), jax.tree.leaves(c_q)))
        tot += ew[q] * sq
    return float(np.sqrt(tot))


def run_cell(method: str, regime: str, prof: dict,
             assign: str = "fixed",
             alpha_client: float | None = None) -> dict:
    q_edges, devs = prof["q_edges"], prof["devices_per_edge"]
    n = devs * K_CLIENTS                     # clients per edge
    dcfg = emnist_like.FedDataCfg(
        n_train=prof["n_train"], n_test=prof["n_test"], alpha=0.1,
        iid=False, seed=SEED, q_edges=q_edges, devices_per_edge=n,
        alpha_client=alpha_client, edge_assign=assign)
    dev, test, ew, dw = emnist_like.make_federated_data(dcfg)
    rng = np.random.default_rng(SEED)
    cc = vclients.ClientConfig(count=K_CLIENTS, participation="bernoulli",
                               rate=0.5, seed=11)
    vw = _vote_weights(regime, q_edges, n)
    # raw (unnormalized) aggregation shares follow the vote weights in
    # the weighted regime; reweighting renormalizes to the participants
    raw = [[dw[q][k] * vw[q][k] for k in range(n)] for q in range(q_edges)]
    cfg = ref_fed.HierConfig(mu=5e-3, mu_sgd=0.5, t_e=prof["t_e"],
                             rho=0.2, method=method)
    state = ref_fed.init_state(mlp.init_mlp(jax.random.PRNGKey(SEED)),
                               q_edges)
    losses, accs, drifts = [], [], []
    t0 = time.time()
    for t in range(prof["rounds"]):
        batches = [[[emnist_like.device_batches(dev, q, k, prof["batch"],
                                                rng)
                     for _ in range(prof["t_e"])] for k in range(n)]
                   for q in range(q_edges)]
        anchors = [[emnist_like.device_batches(dev, q, k,
                                               2 * prof["batch"], rng)
                    for k in range(n)] for q in range(q_edges)]
        mask = _mask(regime, cc, q_edges, devs, t, n)
        shares = [ref_fed._participating_shares(raw[q], mask[q])
                  for q in range(q_edges)]
        drifts.append(round(_drift_norm(state, shares, ew, anchors), 5))
        state = ref_fed.global_round(
            state, cfg, mlp.grad_fn, batches, anchors, ew, raw,
            jax.random.PRNGKey(1000 + t), device_mask=mask,
            vote_weights=vw, reweight_participation=True)
        losses.append(round(float(mlp.loss_fn(
            state.w, {"x": test["x"][:512], "y": test["y"][:512]})), 5))
        accs.append(round(float(mlp.accuracy(state.w, test)), 4))
    return {
        "method": method, "regime": regime,
        "assign": assign, "alpha_client": alpha_client,
        "loss": losses, "final_loss": losses[-1],
        "acc": accs, "final_acc": accs[-1],
        "drift_norm": drifts,
        "wall_s": round(time.time() - t0, 2),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI profile: 2x2 fleet, 2 rounds")
    ap.add_argument("--out", default=str(
        pathlib.Path(__file__).resolve().parents[1] / "BENCH_bias.json"))
    args = ap.parse_args()
    compile_cache.enable()

    prof = _profile(args.fast)
    cells = []
    print("method,regime,assign,final_loss,final_acc,drift_norm_last")
    for regime in REGIMES:
        for method in METHODS:
            cell = run_cell(method, regime, prof)
            cells.append(cell)
            print(f"{method},{regime},fixed,{cell['final_loss']},"
                  f"{cell['final_acc']},{cell['drift_norm'][-1]}")

    # the 2x2 assignment story: severe intra+inter skew, full quorum
    for assign in ASSIGNS:
        for method in ASSIGN_METHODS:
            cell = run_cell(method, "full", prof, assign=assign,
                            alpha_client=ALPHA_CLIENT)
            cells.append(cell)
            print(f"{method},full,{assign},{cell['final_loss']},"
                  f"{cell['final_acc']},{cell['drift_norm'][-1]}")

    by = {(c["method"], c["regime"]): c for c in cells
          if c["assign"] == "fixed"}
    by_assign = {(c["method"], c["assign"]): c for c in cells
                 if c["assign"] != "fixed"}
    checks = {
        # every correction should end at or below plain sign-voting's
        # loss under the severe non-IID full-quorum regime (recorded,
        # not asserted: the dashboard diff is the regression signal)
        "corrections_beat_plain_full": {
            m: by[(m, "full")]["final_loss"]
            <= by[("hier_signsgd", "full")]["final_loss"]
            for m in ("dc_hier_signsgd", "scaffold_hier_signsgd",
                      "mtgc_hier_signsgd")},
        "final_loss_full": {m: by[(m, "full")]["final_loss"]
                            for m in METHODS},
        "final_loss_sampled": {m: by[(m, "sampled")]["final_loss"]
                               for m in METHODS},
        # placement story: first drift reading per assignment mode --
        # random scatter should START with less inter-edge drift than
        # clustered placement of the same skewed clients
        "drift0_by_assign": {
            a: {m: by_assign[(m, a)]["drift_norm"][0]
                for m in ASSIGN_METHODS} for a in ASSIGNS},
        "final_loss_by_assign": {
            a: {m: by_assign[(m, a)]["final_loss"]
                for m in ASSIGN_METHODS} for a in ASSIGNS},
    }
    report = {
        "schema": SCHEMA,
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "profile": ("fast" if args.fast else "default"),
            **prof,
            "clients_per_device": K_CLIENTS,
            "alpha": 0.1, "alpha_client": ALPHA_CLIENT,
            "rho": 0.2, "mu": 5e-3, "mu_sgd": 0.5,
            "seed": SEED,
            "note": "ref_fed oracle on the synthetic EMNIST-like task, "
                    "Dirichlet(0.1) inter-edge skew; drift_norm is "
                    "sqrt(sum_q ew_q ||c - c_q||^2) from share-weighted "
                    "anchor grads at w^(t) before each round.  assign "
                    "cells add Dirichlet(alpha_client) intra-edge skew "
                    "and regroup clients by data.cluster signatures.",
        },
        "methods": list(METHODS),
        "regimes": list(REGIMES),
        "assignments": list(ASSIGNS),
        "cells": cells,
        "checks": checks,
    }
    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
