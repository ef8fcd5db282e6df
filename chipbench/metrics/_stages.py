"""Per-stage device time of the step, and the step's voter-pass counts.

The program scopes its step by stage: ``jax.named_scope("stage.<name>")``
puts ``stage.<name>`` into the ``op_name`` metadata of every HLO
instruction it emits (``src/repro/core/stages.py``).  A device trace
names each op by its instruction (``%fusion.12``), so the map from
instruction to ``op_name`` comes from the compiled step's HLO text.

An op belongs to the OUTERMOST ``stage.*`` on its ``op_name`` path (the
anchor's own forward/backward pass counts under ``anchor``); ops under no
stage, the step's loop and conditional themselves included, are
``unattributed``.  Times are self times: an op's duration less that of
the ops nested in it (``devtrace.Context.self_times``), so the stages
and ``unattributed`` add up to the busy time.

The per-layer readers see only the trace's ``Context``, so the map and
the counts are taken, once per traced run and after its window, from a
probe of the same program (:func:`probe`): the cell's step is built
again through the harness, run once from a fresh state and lowered with
those arguments; its compile hits the compile cache the run filled, so
its instructions are the ones the trace saw.  A trace whose op time the
map covers poorly (another executable) reads nothing.

The counts: each step reports ``voter_passes`` (forward and backward
passes computed) and ``votes_cast`` (those with a non-zero vote weight).
Both are fixed within a round (participation is drawn per round), so the
probe replays the first step of each of the window's rounds and weighs
it by T_E.  A program without the counters, or without stage scopes,
reads nothing.
"""
from __future__ import annotations

import gc
import re
import weakref

import harness

PREFIX = "stage."
UNATTRIBUTED = "unattributed"
MIN_COVER = 0.95          # share of busy time the map must name

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PROBES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def parse_scopes(hlo_text: str) -> dict:
    """``%instruction`` -> its ``op_name`` ("" where it has none), for
    every instruction of an HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out["%" + m.group(1)] = op.group(1) if op else ""
    return out


def stage_of(op_name: str) -> str | None:
    """The outermost ``stage.<name>`` on an ``op_name`` path."""
    for part in op_name.split("/"):
        if part.startswith(PREFIX) and len(part) > len(PREFIX):
            return part[len(PREFIX):]
    return None


def stage_seconds(ctx, scopes: dict) -> dict | None:
    """Stage -> self seconds in the window (averaged over chips), plus
    ``unattributed``; None when no instruction is scoped or the map does
    not cover the trace's ops."""
    if not scopes or not any(stage_of(v) for v in scopes.values()):
        return None
    own = ctx.self_times()
    busy = sum(own.values())
    if busy <= 0:
        return None
    if sum(s for name, s in own.items() if name in scopes) < MIN_COVER * busy:
        return None
    out = {}
    for name, s in own.items():
        key = stage_of(scopes.get(name, "")) or UNATTRIBUTED
        out[key] = out.get(key, 0.0) + s
    out.setdefault(UNATTRIBUTED, 0.0)
    return out


def attach(ctx, scopes: dict | None, counts: tuple | None = None) -> None:
    """Give ``ctx`` its map and counts without a probe (recorded traces)."""
    _PROBES[ctx] = {"scopes": scopes, "counts": counts}


def of(ctx) -> dict:
    """The probe of ``ctx``'s program, made on first use."""
    if ctx not in _PROBES:
        _PROBES[ctx] = probe(ctx.cell, harness.warmup_steps(ctx.t_e),
                             ctx.steps)
    return _PROBES[ctx]


def seconds(ctx) -> dict | None:
    return stage_seconds(ctx, of(ctx)["scopes"])


def per_step_ms(ctx, stage: str) -> float | None:
    """Self milliseconds of ``stage`` per step of the window."""
    secs = seconds(ctx)
    if secs is None or ctx.steps <= 0:
        return None
    return 1e3 * secs.get(stage, 0.0) / ctx.steps


def probe(cell, start: int, steps: int) -> dict:
    """Instruction -> op_name of the cell's compiled step, and the
    window's summed (voter_passes, votes_cast) for steps
    ``start .. start + steps`` (None if the program has no counters)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    gc.collect()                  # what the run's check left on the device
    program = harness.Program(cell, jax.devices())
    tokens = harness.pool_for(cell, 0, 1)[0]
    state, metrics = program.step(program.fresh_state(0), tokens)
    text = program.jstep.lower(
        state, {"train": {"tokens": jnp.asarray(tokens)}},
        *program.weights).compile().as_text()
    counts = None
    if "voter_passes" in metrics and "votes_cast" in metrics:
        t_e = program.t_e
        passes = cast = 0
        for r in range(start // t_e, (start + steps) // t_e):
            step = jax.device_put(np.int32(r * t_e), state.step.sharding)
            state, metrics = program.step(state._replace(step=step), tokens)
            passes += t_e * int(metrics["voter_passes"])
            cast += t_e * int(metrics["votes_cast"])
        counts = (passes, cast)
    del state, metrics, program
    gc.collect()
    return {"scopes": parse_scopes(text), "counts": counts}
