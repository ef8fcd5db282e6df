"""Chaos-cell report: run the churn parity cells and emit a JSON
artifact (reports/chaos_cells.json) for the nightly chaos tier.

Each cell runs the deterministic churn schedule (client kill, straggler
demotion, heartbeat loss, fail-open window, recoveries -- the same
``chaos_injector`` schedule the parity matrix pins) through the jitted
hierarchical step and compares the cloud-aggregated model against the
``ref_fed`` oracle driven by the SAME compiled membership arrays:

  * method cells   -- plain/dc/scaffold/mtgc sign cells must be EXACT
                      (bitwise); hier_sgd within float tolerance;
  * transport cells -- every transport x layout x client-mode must be
                      bitwise the reference cell;
  * replay cell    -- nan-loss -> checkpoint restore -> replay must be
                      bitwise the uninterrupted trajectory.

Exit status is nonzero if any cell misses its contract, so the nightly
job both uploads the artifact and fails loudly.

  PYTHONPATH=src python benchmarks/chaos_report.py [--out PATH]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                       / "tests" / "helpers"))

import numpy as np

import parity_harness as H
from repro.core.topology import single_device_topology
from repro.launch import compile_cache

REPORT = (pathlib.Path(__file__).resolve().parents[1] / "reports"
          / "chaos_cells.json")

SIGN_METHODS = ("hier_signsgd", "dc_hier_signsgd",
                "scaffold_hier_signsgd", "mtgc_hier_signsgd")


def max_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(a[k], np.float64)
                                   - np.asarray(b[k], np.float64))))
               for k in a)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(REPORT))
    args = ap.parse_args()
    compile_cache.enable()

    topo = single_device_topology()
    problem = H.make_problem(1, 1)
    cc = H.client_cfg(1, 1, 2, "full")
    inj = H.chaos_injector(1, 1, 2, problem["t_e"])
    arrays = H.chaos_arrays(problem, cc, inj)
    cells, ok = [], True

    def record(name, want_exact, diff, wall, extra=None):
        nonlocal ok
        passed = diff == 0.0 if want_exact else diff < 1e-5
        ok &= passed
        cells.append({"cell": name, "exact": diff == 0.0,
                      "max_abs_diff": diff, "passed": passed,
                      "wall_s": round(wall, 1), **(extra or {})})
        print(f"{'PASS' if passed else 'FAIL'} {name:42s} "
              f"diff={diff:.2e} ({wall:.1f}s)")

    # method cells vs the grown oracle
    ref_dc = None
    for method in SIGN_METHODS + ("hier_sgd",):
        t0 = time.time()
        ref, _ = H.run_hier_chaos(topo, problem, method, clients=cc,
                                  arrays=arrays)
        if method == "dc_hier_signsgd":
            ref_dc = ref
        oracle = H.run_oracle_chaos(problem, method, cc, arrays)
        diff = max_diff(H.aggregate(ref, arrays[-1].edge_weights), oracle)
        record(f"oracle/{method}", method != "hier_sgd", diff,
               time.time() - t0)

    # transport x layout x mode cells, bitwise vs the dc reference
    for transport in H.SIGN_TRANSPORTS:
        for layout in H.LAYOUTS:
            for mode in ("merged", "stream"):
                t0 = time.time()
                ccm = (cc if mode == "merged"
                       else dataclasses.replace(cc, mode="stream"))
                got, _ = H.run_hier_chaos(topo, problem,
                                          "dc_hier_signsgd", transport,
                                          layout, clients=ccm,
                                          arrays=arrays)
                record(f"cross/{transport}/{layout}/{mode}", True,
                       max_diff(ref_dc, got), time.time() - t0)

    # kill-restore-replay: nan event + checkpoint restore, bitwise
    t0 = time.time()
    inj_n = H.chaos_injector(1, 1, 2, problem["t_e"], nan_step=5)
    with tempfile.TemporaryDirectory() as d:
        got, _ = H.run_hier_chaos(topo, problem, "dc_hier_signsgd",
                                  clients=cc, injector=inj_n,
                                  arrays=arrays, ckpt_dir=d,
                                  ckpt_every=problem["t_e"])
    record("kill-restore-replay/dc_hier_signsgd", True,
           max_diff(ref_dc, got), time.time() - t0)

    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(
        {"schedule_events": len(inj.events), "cells": cells,
         "all_passed": ok}, indent=1))
    print(f"{len(cells)} chaos cells -> {out}")
    if not ok:
        raise SystemExit("chaos cells FAILED")


if __name__ == "__main__":
    main()
