"""The reduction from trace events to the per-layer metrics."""
from __future__ import annotations

import pathlib

import pytest

import devtrace
import harness
from metrics import collective_ms_per_step, device_idle_share, \
    prologue_boundary_ms

MS = 1_000_000


def _ctx(events, steps=4, t_e=2, chips=1):
    return devtrace.Context(events, chips=chips, steps=steps, t_e=t_e,
                            tokens_per_step=100)


def _synthetic():
    ev = [("host", -1, "bench.window", 0, 100 * MS),
          ("host", -1, "bench.dispatch", 0, 10 * MS),
          ("host", -1, "bench.loss_read", 60 * MS, 80 * MS)]
    # ops on chip 0: [10, 30) overlapping [20, 40), then [50, 60), and
    # an all-reduce [85, 95); one op outside the window
    ev += [("op", 0, "fusion.1", 10 * MS, 30 * MS),
           ("op", 0, "sign_pack_kernel", 20 * MS, 40 * MS),
           ("op", 0, "fusion.2", 50 * MS, 60 * MS),
           ("op", 0, "all-reduce.3", 85 * MS, 95 * MS),
           ("op", 0, "fusion.9", 120 * MS, 130 * MS)]
    # four step programs: boundary, local, boundary, local
    ev += [("module", 0, "jit_train_step", s * MS, e * MS)
           for s, e in ((10, 40), (50, 60), (62, 70), (85, 95))]
    return ev


def test_union_merges_overlaps():
    assert devtrace.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [[0, 3],
                                                                 [5, 10]]


def test_busy_idle_and_gaps():
    ctx = _ctx(_synthetic())
    assert ctx.window_s == pytest.approx(0.1)
    assert ctx.busy_s == pytest.approx(0.050)       # 30 + 10 + 10 ms
    assert device_idle_share.read(ctx) == pytest.approx(50.0)
    assert ctx.gaps() == [(0, 10 * MS), (40 * MS, 50 * MS),
                          (60 * MS, 85 * MS), (95 * MS, 100 * MS)]
    names = dict((round(s, 3), n) for n, s in ctx.idle_gaps(10))
    assert names[0.025] == "bench.loss_read"
    assert names[0.01] in ("bench.dispatch", "other")
    assert ctx.tokens_per_s == pytest.approx(4 * 100 / 0.1)


def test_op_sums_and_collectives():
    ctx = _ctx(_synthetic())
    assert ctx.op_seconds(r"sign_pack") == pytest.approx(0.020)
    assert collective_ms_per_step.read(ctx) == pytest.approx(10.0 / 4)
    assert ctx.top_ops(2)[0][0] in ("fusion.1", "sign_pack_kernel")


def test_boundary_split():
    ctx = _ctx(_synthetic())
    bnd, loc = ctx.boundary_split()
    assert bnd == pytest.approx([0.030, 0.008])
    assert loc == pytest.approx([0.010, 0.010])
    assert prologue_boundary_ms.read(ctx) == pytest.approx(9.0)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        _ctx([("op", 0, "fusion.1", 0, 1)])


RECORDED = pathlib.Path(__file__).parent / "data" / "silo4k_two_steps.json.gz"
SILO_N_PAD = 733_384_704       # stablelm-3b-6l's flat layout, padded


@pytest.fixture(scope="module")
def recorded():
    """Two steps (a round boundary, then a local step) of the traced
    window of ``stablelm-3b-6l.silo-4k``, recorded on a v5e chip and cut
    to those steps."""
    cell = harness.resolve(harness.benchmark(), "stablelm-3b-6l.silo-4k")
    return devtrace.Context(
        devtrace.read_saved(str(RECORDED)), chips=1, steps=2, t_e=15,
        tokens_per_step=4096, cell=cell,
        peaks=harness.peaks_for("TPU v5 lite"), n_pad=SILO_N_PAD)


def test_recorded_busy_and_self_times(recorded):
    ctx = recorded
    assert 0 < ctx.busy_s <= ctx.window_s
    assert 0 <= device_idle_share.read(ctx) < 1.0
    # the ops nest (loops hold their bodies): self times tile the busy time
    assert sum(ctx.self_times().values()) == pytest.approx(ctx.busy_s)
    top = ctx.top_ops(10)
    assert len(top) == 10 and all(name.startswith("%") for name, _ in top)
    assert top == sorted(top, key=lambda kv: -kv[1])
    gaps = ctx.idle_gaps(10)
    assert all(s >= 0 for _, s in gaps)


def test_recorded_kernels_and_prologue(recorded):
    from metrics import sign_pack_roofline, step_mfu, vote_update_roofline
    ctx = recorded
    assert ctx.op_seconds(sign_pack_roofline.PATTERN) > 0
    assert ctx.op_seconds(vote_update_roofline.PATTERN) > 0
    for mod in (sign_pack_roofline, vote_update_roofline, step_mfu):
        assert 0 < mod.read(ctx) <= 100
    bnd, loc = ctx.boundary_split()
    assert len(bnd) == 1 and len(loc) == 1
    assert prologue_boundary_ms.read(ctx) > 0
    assert collective_ms_per_step.read(ctx) is None     # one chip
