"""Benchmark entry point: one function per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV and mirrors it to
reports/bench_results.csv plus machine-readable
reports/bench_results.json (so future PRs can diff perf).
(The transport sweep lives in benchmarks/bench_transports.py and emits
BENCH_transports.json.)

  table2    device->edge uplink bits per round  (paper Table II)
  fig2      4-method accuracy, IID & non-IID    (paper Fig. 2)
  fig3      T_E sweep, DC vs plain              (paper Fig. 3)
  fig4      rho sensitivity at T_E=15           (paper Fig. 4)
  clients   virtual-client scale-out (K=64, p=0.1): participating
            uplink + round cost (always cost-model priced)
  methods   drift-correction method axis: Thm-style loss proxy +
            per-client downlink (dc / scaffold / mtgc accounting)
  overlap   cloud sync schedule: per-round wall-clock sync vs overlap
            as a function of the cloud RTT (always cost-model priced)
  roofline  3-term roofline per dry-run cell    (deliverable g)

Flags: ``--only fig2`` to run a subset; ``--fast`` is the CI profile --
fig2/3/4 are priced by the dry-run cost model (benchmarks/cost_model.py,
Thm 1/2 constants + analytic round cost) instead of real CPU training,
so the whole sweep completes in seconds while emitting the same row
names and JSON schema (cost-model rows are tagged ``src=cost_model``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    choices=["all", "table2", "fig2", "fig3", "fig4",
                             "clients", "methods", "overlap",
                             "roofline"])
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--out-dir", default=None,
                    help="directory for bench_results.{csv,json} "
                         "(default: <repo>/reports)")
    args = ap.parse_args()

    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(root / "src"))
    from benchmarks import cost_model, paper_figs, roofline
    from repro.launch import compile_cache
    compile_cache.enable()

    rows = []
    want = lambda k: args.only in ("all", k)
    if want("table2"):
        rows += paper_figs.table2_uplink_cost()
    if want("fig2"):
        rows += (cost_model.fig2_rows(paper_figs.METHODS) if args.fast
                 else paper_figs.fig2_accuracy(seeds=(0, 1)))
    if want("fig3"):
        rows += (cost_model.fig3_rows(te_values=(5, 15)) if args.fast
                 else paper_figs.fig3_te_sweep(te_values=(5, 15, 30)))
    if want("fig4"):
        rows += (cost_model.fig4_rows(rhos=(0.0, 0.2, 1.0)) if args.fast
                 else paper_figs.fig4_rho_sweep(
                     rhos=(0.0, 0.1, 0.2, 0.5, 1.0)))
    if want("clients"):
        # virtual-client scale-out (always cost-model priced: the row
        # exists to track the participating-uplink accounting)
        rows += cost_model.clients_rows(cells=((64, 0.1),))
    if want("methods"):
        # drift-correction method axis (always cost-model priced): the
        # Thm-style stationarity proxy next to each correction's
        # per-client downlink bytes (dc anchor vs scaffold c_global vs
        # mtgc two-term)
        rows += cost_model.methods_rows()
    if want("overlap"):
        # cloud sync schedule (always cost-model priced): what hiding
        # the cloud RTT behind a round of local stepping buys per round
        rows += cost_model.overlap_rows()
    if want("roofline"):
        try:
            rows += roofline.roofline_rows()
        except Exception as e:
            rows.append(("roofline/ERROR", 0.0, str(e)[:80]))

    out = ["name,us_per_call,derived"]
    for name, us, derived in rows:
        out.append(f"{name},{us:.1f},{derived}")
    csv = "\n".join(out)
    print(csv)
    rep = (pathlib.Path(args.out_dir) if args.out_dir
           else pathlib.Path(__file__).resolve().parents[1] / "reports")
    rep.mkdir(parents=True, exist_ok=True)
    (rep / "bench_results.csv").write_text(csv + "\n")
    (rep / "bench_results.json").write_text(json.dumps({
        "rows": [{"name": name, "us_per_call": us, "derived": derived}
                 for name, us, derived in rows],
    }, indent=2) + "\n")


if __name__ == "__main__":
    main()
