"""Small copies of the benchmark's cells, and the faults a run can have,
planted in the program under test."""
from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp

import calibrate
import check
import harness

SEED = 2**31 + 77
DENSE_SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
               "head_dim": 16, "d_ff": 128, "vocab": 256}
# the cell's traffic at small sizes; each config's widths as above
SMALL_TRAFFIC = {
    "clients8-stream": {"seq_len": 16},
    "silo-4k": {"seq_len": 64, "t_e": 4},
}
SMALL_MODEL = {"stablelm-3b-6l": DENSE_SMALL}


def small_cell(name: str) -> harness.Cell:
    """The cell at small widths, computing in float32, where the program
    matches the reference to rounding: a sound run passes the cell's own
    limits."""
    entry = next(w for w in harness.benchmark()["workloads"]
                 if w["name"] == name)
    cell = harness.resolve(harness.benchmark(), name)
    cell.config["model"].update(SMALL_MODEL[entry["config"]])
    cell.traffic.update(SMALL_TRAFFIC[entry["traffic"]],
                        compute_dtype="float32")
    return cell


def run(cell) -> tuple[dict, dict]:
    result, notes = harness.run_cell(cell, SEED, 0.2, False, jax.devices(),
                                     time.perf_counter())
    return result, notes["numbers"]


@contextlib.contextmanager
def state_unchanged():
    """The step returns the parameters it was given."""
    from repro.core import hier
    make = hier.make_hier_step

    def broken(*a, **k):
        init_fn, step_fn = make(*a, **k)

        def step(state, *rest):
            new, metrics = step_fn(state, *rest)
            return new._replace(params=state.params), metrics
        return init_fn, step

    hier.make_hier_step = broken
    try:
        yield
    finally:
        hier.make_hier_step = make


@contextlib.contextmanager
def half_batch():
    """Every voter's loss leaves out half of its tokens and takes the mean
    over the rest."""
    from repro.models import build
    targets = build._targets_and_mask

    def half(tokens):
        t, mask = targets(tokens)
        keep = jnp.arange(tokens.shape[-1]) < tokens.shape[-1] // 2
        return t, mask * keep

    build._targets_and_mask = half
    try:
        yield
    finally:
        build._targets_and_mask = targets


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}


def control_verdict(cell) -> tuple[dict, dict]:
    """The reference in the program's place, its contractions in float8,
    judged by the cell's limits."""
    program = harness.Program(cell, jax.devices())
    pool = harness.pool_for(cell, SEED, harness.CHECK_STEPS)
    ref = calibrate.host(check.reference(cell, program, SEED, pool))
    ctl = check.reference(cell, program, SEED, pool, precision="float8")
    side = {"losses": ctl["losses"], "vote1": ctl["vote1"],
            "p3": ctl["params"], "delta": ctl["delta"]}
    numbers = check.numbers(side, ref, check.initial(program, SEED),
                            cell.traffic["mu"])
    return check.judge(numbers, cell.limits), numbers
