#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  Set-up (building the step, weights from the
seed, one warm-up round, which compiles or reads the compile cache)
counts as ``setup_s``; the window then runs whole global rounds until
``--seconds`` have passed.  With ``--trace 1`` the window runs under the
profiler and the per-layer metrics are printed instead of the
end-to-end ones.  The last line of standard output is one JSON object;
the numbers that decide ``correct`` are the last lines of standard
error too.  Without a TPU, with fewer chips than the cell asks for, or
on a device whose peaks are unknown, it exits non-zero and prints no
result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    if not (harness.ROOT / "src" / "repro").is_dir():
        return _fail("src/repro, the system under test, is not in this "
                     "checkout")
    try:
        cell = harness.resolve(harness.benchmark(), args.workload)
    except (KeyError, FileNotFoundError) as e:
        return _fail(str(e))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"needs a TPU, but JAX found {devices[0].platform!r} "
                     f"devices; no CPU fallback")
    if len(devices) < cell.chips:
        return _fail(f"{cell.name} needs {cell.chips} chips, found "
                     f"{len(devices)}")
    from peaks import UnknownDevice, peaks_for
    try:
        peaks_for(devices[0].device_kind)
    except UnknownDevice as e:
        return _fail(str(e))

    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        result, notes = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), devices,
            T_PROCESS, trace_dir=tdir)
    print(json.dumps({"notes": notes}), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
