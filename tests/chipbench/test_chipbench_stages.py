"""Per-stage device time and the voter-pass counts (``metrics/_stages``).

Synthetic events check the outermost-stage rule and that the stages and
``unattributed`` tile the busy time; a function compiled on the CPU
checks the HLO-text parser; small cells on the CPU check the probe of
the program (its map and its counts) and that a traced run with the new
readers completes; two steps recorded on a v5e chip per one-chip cell,
with their maps, check the readers on real traces.  The readers read
nothing where the program has no scopes or counters.
"""
from __future__ import annotations

import gzip
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import devtrace
import harness
import plant
from metrics import (_stages, clients_voting_share, collective_ms_per_step,
                     device_idle_share, prologue_boundary_ms,
                     sign_pack_roofline, stage_anchor_ms, stage_grads_ms,
                     stage_relayout_ms, stage_unattributed_share, step_mfu,
                     tally_acc_roofline, vote_update_roofline)

MS = 1_000_000
DATA = pathlib.Path(__file__).parent / "data"
STAGE_READERS = (stage_grads_ms, stage_relayout_ms, stage_anchor_ms,
                 stage_unattributed_share)


def _ctx(events, steps=2, t_e=2):
    return devtrace.Context(events, chips=1, steps=steps, t_e=t_e,
                            tokens_per_step=100)


def _synthetic():
    """A boundary step: a conditional holding the cloud mean and the
    anchor (whose own fwd/bwd and relayout sit inside it), then a loop
    holding the local grads, a relayout, a kernel and an unscoped op."""
    ev = [("host", -1, "bench.window", 0, 100 * MS)]
    ev += [("op", 0, "%conditional", 0, 40 * MS),
           ("op", 0, "%fusion.1", 2 * MS, 6 * MS),        # cloud_mean
           ("op", 0, "%fusion.2", 8 * MS, 30 * MS),       # anchor's grads
           ("op", 0, "%copy.3", 30 * MS, 36 * MS),        # anchor relayout
           ("op", 0, "%while", 40 * MS, 90 * MS),
           ("op", 0, "%fusion.4", 41 * MS, 71 * MS),      # grads
           ("op", 0, "%concatenate.5", 72 * MS, 80 * MS),  # relayout
           ("op", 0, "%sign_pack.6", 80 * MS, 84 * MS),
           ("op", 0, "%iota.7", 95 * MS, 99 * MS)]        # no stage
    scopes = {
        "%conditional": "jit(train_step)/cond",
        "%fusion.1": "jit(train_step)/cond/branch_1_fun/stage.cloud_mean/mul",
        "%fusion.2": ("jit(train_step)/cond/branch_1_fun/stage.anchor/"
                      "stage.grads/transpose(jvp())/dot_general"),
        "%copy.3": "jit(train_step)/stage.anchor/stage.relayout/concatenate",
        "%while": "jit(train_step)/while",
        "%fusion.4": "jit(train_step)/while/body/stage.grads/dot_general",
        "%concatenate.5": "jit(train_step)/stage.relayout/concatenate",
        "%sign_pack.6": "jit(train_step)/stage.sign_pack/sign_pack/pallas",
        "%iota.7": "",
    }
    return ev, scopes


def test_outermost_stage_rule():
    assert _stages.stage_of("jit(s)/stage.anchor/while/stage.grads/dot") \
        == "anchor"
    assert _stages.stage_of("jit(s)/while/body/stage.grads/add") == "grads"
    assert _stages.stage_of("jit(s)/while") is None
    assert _stages.stage_of("") is None


def test_stage_self_times_tile_busy_time():
    ev, scopes = _synthetic()
    ctx = _ctx(ev)
    secs = _stages.stage_seconds(ctx, scopes)
    want = {"cloud_mean": 0.004, "anchor": 0.028, "grads": 0.030,
            "relayout": 0.008, "sign_pack": 0.004,
            # the conditional's and the loop's own time, and the iota
            "unattributed": 0.008 + 0.008 + 0.004}
    assert secs == pytest.approx(want)
    assert sum(secs.values()) == pytest.approx(ctx.busy_s)


def test_readers_on_synthetic_events():
    ev, scopes = _synthetic()
    ctx = _ctx(ev, steps=2, t_e=2)
    _stages.attach(ctx, scopes, counts=(16, 6))
    assert stage_grads_ms.read(ctx) == pytest.approx(15.0)
    assert stage_relayout_ms.read(ctx) == pytest.approx(4.0)
    assert stage_anchor_ms.read(ctx) == pytest.approx(28.0)
    assert stage_unattributed_share.read(ctx) == pytest.approx(
        100 * 0.020 / ctx.busy_s)
    assert clients_voting_share.read(ctx) == pytest.approx(37.5)


@pytest.mark.parametrize("case", ["no_map", "no_stage", "other_program"])
def test_readers_read_nothing_without_scopes(case):
    ev, scopes = _synthetic()
    if case == "no_map":
        scopes = {}
    elif case == "no_stage":
        scopes = {k: v.replace("stage.", "") for k, v in scopes.items()}
    else:       # the map of another executable names few of the ops
        scopes = {"%fusion.1": scopes["%fusion.1"], "%fusion.99": ""}
    ctx = _ctx(ev)
    _stages.attach(ctx, scopes, counts=None)
    for mod in STAGE_READERS + (clients_voting_share,):
        assert mod.read(ctx) is None


def test_parse_scopes_of_a_cpu_compiled_function():
    def f(x):
        with jax.named_scope("stage.grads"):
            y = jnp.sin(x) * 2
        with jax.named_scope("stage.anchor"):
            with jax.named_scope("stage.grads"):
                z = jnp.cos(y) + 1
        return z.sum()

    text = jax.jit(f).lower(jnp.ones((128,))).compile().as_text()
    scopes = _stages.parse_scopes(text)
    assert all(k.startswith("%") and " " not in k for k in scopes)
    stages = {_stages.stage_of(v) for v in scopes.values()}
    assert {"grads", "anchor"} <= stages
    assert any(_stages.stage_of(v) is None for v in scopes.values())
    sin = [v for k, v in scopes.items() if k.startswith("%sin")]
    assert sin and all(_stages.stage_of(v) == "grads" for v in sin)
    cos = [v for k, v in scopes.items() if k.startswith("%cos")]
    assert cos and all(_stages.stage_of(v) == "anchor" for v in cos)


@pytest.mark.parametrize("name", ["stablelm-3b-6l.clients8-stream",
                                  "stablelm-3b-6l.silo-4k"])
def test_probe_of_a_small_cell(name):
    """The probe finds the program's stages and replays its counters:
    the window's votes cast are the participation mask's."""
    from repro.core import clients as vclients
    cell = plant.small_cell(name)
    t_e = cell.traffic["t_e"]
    start, steps = harness.warmup_steps(t_e), 3 * t_e
    got = _stages.probe(cell, start, steps)
    stages = {_stages.stage_of(v) for v in got["scopes"].values()}
    assert {"grads", "relayout", "anchor", "cloud_mean"} <= stages
    c = cell.traffic["clients"]
    cc = vclients.ClientConfig(count=c["count"],
                               participation=c.get("participation", "full"),
                               rate=c.get("rate", 1.0), seed=c.get("seed", 0),
                               mode=c.get("mode", "merged"))
    cast = sum(t_e * int(np.sum(np.asarray(
        vclients.participation_mask(cc, 1, 1, r)) > 0))
        for r in range(start // t_e, (start + steps) // t_e))
    assert got["counts"] == (steps * c["count"], cast)


def test_readers_probe_the_program():
    """Through the readers, as a traced run calls them: on a window with
    no device op (the CPU's) the stage readers read nothing, and the
    voting share is the participation mask's."""
    cell = plant.small_cell("stablelm-3b-6l.clients8-stream")
    t_e = cell.traffic["t_e"]
    ctx = devtrace.Context([("host", -1, "bench.window", 0, MS)], chips=1,
                           steps=4 * t_e, t_e=t_e, tokens_per_step=1,
                           cell=cell)
    names = [m["name"] for m in harness.benchmark()["per_layer"]
             if m["name"].startswith(("stage.", "clients."))]
    got = {n: harness.metric_module(n).read(ctx) for n in names}
    share = got.pop("clients.voting_share")
    assert got == dict.fromkeys(got)
    passes, cast = _stages.of(ctx)["counts"]
    assert passes == 4 * t_e * cell.traffic["clients"]["count"]
    assert share == pytest.approx(100.0 * cast / passes)
    assert 0 < share < 100


# ------------------------------------------------ recorded chip traces

SILO = DATA / "silo4k_two_steps.json.gz"
SILO_N_PAD = 733_384_704       # stablelm-3b-6l's flat layout, padded


def _recorded(path, cell_name, steps=2):
    cell = harness.resolve(harness.benchmark(), cell_name)
    return devtrace.Context(
        devtrace.read_saved(str(path)), chips=1, steps=steps,
        t_e=cell.traffic["t_e"],
        tokens_per_step=harness.tokens_per_step(cell), cell=cell,
        peaks=harness.peaks_for("TPU v5 lite"), n_pad=SILO_N_PAD)


def test_existing_readers_read_the_old_trace_as_before():
    ctx = _recorded(SILO, "stablelm-3b-6l.silo-4k")
    want = {device_idle_share: 0.15045234113988393,
            step_mfu: 13.078021422741784,
            prologue_boundary_ms: 416.988581,
            sign_pack_roofline: 57.530136849737815,
            vote_update_roofline: 80.05319139251495}
    for mod, value in want.items():
        assert mod.read(ctx) == pytest.approx(value, rel=1e-12)
    assert tally_acc_roofline.read(ctx) is None
    assert collective_ms_per_step.read(ctx) is None
    # the old trace's program had no scopes
    ev = devtrace.read_saved(str(SILO))
    bare = {e[2]: "jit(train_step)/fusion" for e in ev if e[0] == "op"}
    _stages.attach(ctx, bare)
    for mod in STAGE_READERS:
        assert mod.read(ctx) is None


RECORDED = {
    "stablelm-3b-6l.silo-4k": DATA / "silo4k_stages_two_steps.json.gz",
    "stablelm-3b-6l.clients8-stream":
        DATA / "clients8_stages_two_steps.json.gz",
}


@pytest.mark.parametrize("cell_name", sorted(RECORDED))
def test_stage_readers_on_a_recorded_trace(cell_name):
    path = RECORDED[cell_name]
    ctx = _recorded(path, cell_name)
    scope_path = str(path).replace(".json.gz", ".scopes.json.gz")
    with gzip.open(scope_path, "rt") as f:
        scopes = json.load(f)
    _stages.attach(ctx, scopes)
    secs = _stages.stage_seconds(ctx, scopes)
    assert sum(secs.values()) == pytest.approx(ctx.busy_s)
    for stage in ("grads", "relayout", "anchor"):
        assert secs[stage] > 0, stage
    for mod in STAGE_READERS:
        assert mod.read(ctx) > 0, mod.__name__
    assert stage_unattributed_share.read(ctx) < 10.0
    # one boundary step of two: the anchor is in the first step only
    assert stage_anchor_ms.read(ctx) > 0
    kernel = {"stablelm-3b-6l.silo-4k": ("sign_pack", "vote_update"),
              "stablelm-3b-6l.clients8-stream": ("tally_acc",)}[cell_name]
    for name in kernel:
        assert secs[name] > 0, name
