#!/usr/bin/env python3
"""Record two steps of a cell's traced window, for the benchmark's tests.

    python chipbench/record_trace.py --workload <cell> --seed <n> \
        --seconds <s> --out tests/chipbench/data/<name>.json.gz

Runs the cell once with ``--trace 1`` (as ``run.py`` does) and prints
its result line; then keeps the window's first two steps (a round
boundary, then a local step) of the trace as gzipped JSON events that
``devtrace.read_saved`` reads, and beside them (``<out>`` with
``.scopes.json.gz`` in place of ``.json.gz``) the ``op_name`` of each
HLO instruction those steps ran.  Needs a TPU, like a run.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def two_steps(events: list) -> list:
    """The events of the window's first two step programs, inside a
    ``bench.window`` span cut to them."""
    wins = [e for e in events if e[0] == "host" and e[2] == "bench.window"]
    w0, w1 = wins[-1][3], wins[-1][4]
    mods = sorted((e for e in events if e[0] == "module"
                   and "train_step" in e[2] and w0 <= e[3] < w1),
                  key=lambda e: e[3])
    chip = min(e[1] for e in mods)
    mods = [e for e in mods if e[1] == chip][:2]
    s, t = mods[0][3], mods[1][4]
    keep = [("host", -1, "bench.window", s, t)] + mods
    keep += [e for e in events if e[0] == "op" and e[4] > s and e[3] < t]
    keep += [e for e in events if e[0] == "host" and e[2] != "bench.window"
             and e[4] > s and e[3] < t]
    return keep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    import devtrace
    import harness
    from metrics import _stages
    from repro.launch import compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cell = harness.resolve(harness.benchmark(), args.workload)
    with tempfile.TemporaryDirectory(prefix="chipbench-record-") as tdir:
        result, notes = harness.run_cell(cell, args.seed, args.seconds, True,
                                         devices, T_PROCESS, trace_dir=tdir)
        events = two_steps(devtrace.load(tdir))
    print(json.dumps({"notes": notes}), file=sys.stderr)
    print(json.dumps(result), flush=True)

    t_e = cell.traffic["t_e"]
    scopes = _stages.probe(cell, harness.warmup_steps(t_e), t_e)["scopes"]
    names = {e[2] for e in events if e[0] == "op"}
    with gzip.open(args.out, "wt") as f:
        json.dump([list(e) for e in events], f)
    with gzip.open(args.out.replace(".json.gz", ".scopes.json.gz"), "wt") as f:
        json.dump({k: v for k, v in scopes.items() if k in names}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
