#!/usr/bin/env python3
"""Readings from which a cell's limits are set (not part of a run).

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--out chiprun_out/calib.jsonl]

In one process, for each seed of ``--seeds``: the program's first steps
at the cell's own size, through the same compiled step a run drives, and
the numbers ``correct`` compares against the reference (the lower
reading).  For each seed of ``--control-seeds``, the same numbers of the
control (the reference in the program's place, its contractions in
float8) and of each fault the cell can have, planted in the reference
put in the program's place: half of every voter's batch left out (the
mean over the rest), the exchange between chips left out (cells with
more than one voter), and a step that returns its state unchanged.  One
JSON line per reading.  Needs a TPU, like a run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def program_side(harness, program, seed, pool):
    """The program's first three steps from ``seed`` (no warm-up round
    beyond them) and what the check reads of them."""
    import jax
    state = program.fresh_state(seed)
    keep = {"losses": []}
    for i in range(harness.CHECK_STEPS):
        state, metrics = program.step(state, pool[i])
        keep["losses"].append(metrics["loss_per_pod"])
        if i == 0:
            keep["p1"] = harness.Program.host_trees(state.params)
            keep["delta"] = (harness.Program.host_trees(state.delta_next)
                             if state.delta_next is not None else None)
    keep["p3"] = harness.Program.host_trees(state.params)
    keep["losses"] = jax.device_get(keep["losses"])
    del state
    gc.collect()
    import numpy as np
    keep["losses"] = np.stack([np.asarray(x, np.float64)
                               for x in keep["losses"]])
    return keep


def faults(cell):
    out = {"control_float8": {"precision": "float8"},
           "half_batch": {"keep_half": True}}
    tr = cell.traffic
    if tr["mesh"]["pods"] * tr["mesh"]["data"] > 1:
        out["no_exchange"] = {"exchange": False}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import harness
    import check
    import jax
    cell = harness.resolve(harness.benchmark(), args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run_calibration(harness, check, cell, devices,
                    [int(s) for s in args.seeds.split(",") if s],
                    [int(s) for s in args.control_seeds.split(",") if s],
                    args.out)
    return 0


def run_calibration(harness, check, cell, devices, seeds, control_seeds,
                    out=None):
    program = harness.Program(cell, devices)
    mu = cell.traffic["mu"]
    for seed in seeds:
        t0 = time.perf_counter()
        pool = harness.pool_for(cell, seed, harness.CHECK_STEPS)
        keep = program_side(harness, program, seed, pool)
        t1 = time.perf_counter()
        detail = []
        nums = check.compare_program(cell, program, keep, seed, pool,
                                     detail=detail)
        _emit(out, {"cell": cell.name, "seed": seed, "side": "program",
                    "numbers": nums, "program_s": t1 - t0,
                    "reference_s": time.perf_counter() - t1,
                    "detail": detail})
        del keep
        gc.collect()
    for seed in control_seeds:
        pool = harness.pool_for(cell, seed, harness.CHECK_STEPS)
        ref = host(check.reference(cell, program, seed, pool))
        p0 = check.initial(program, seed)
        unchanged = {"losses": ref["losses"], "p3": [p0] * len(ref["params"]),
                     "vote1": [jax_zeros(v) for v in ref["vote1"]],
                     "delta": ref["delta"]}
        _emit(out, {"cell": cell.name, "seed": seed,
                    "side": "state_unchanged",
                    "numbers": check.numbers(unchanged, ref, p0, mu)})
        del unchanged, p0
        for name, fault in faults(cell).items():
            t0 = time.perf_counter()
            f = host(check.reference(cell, program, seed, pool, **fault))
            side = {"losses": f["losses"], "vote1": f["vote1"],
                    "p3": f["params"], "delta": f["delta"]}
            del f
            detail = []
            nums = check.numbers(side, ref, check.initial(program, seed), mu,
                                 detail=detail)
            _emit(out, {"cell": cell.name, "seed": seed, "side": name,
                        "numbers": nums, "detail": detail,
                        "seconds": time.perf_counter() - t0})
            del side
            gc.collect()
        del ref
        gc.collect()


def host(out: dict) -> dict:
    """A reference run's trees moved to the host, so that the next run
    has the device to itself."""
    import jax
    import numpy as np
    return {k: (jax.tree.map(np.asarray, v) if k != "losses" else v)
            for k, v in out.items()}


def jax_zeros(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.zeros_like, tree)


if __name__ == "__main__":
    sys.exit(main())
