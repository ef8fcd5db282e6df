"""``correct`` separates the sound program from the control and from
the faults a cell can have, at small widths on the CPU.

Each run drives the rest of a run (``harness.run_cell``: set-up, window,
check), skipping only the look for a chip, on a small copy of a cell
(``plant.small_cell``): its traffic and limits, its configuration at
small widths, the program in float32.  A sound run passes the cell's
limits; each planted fault and the control fail them.
"""
from __future__ import annotations

import pytest

import harness
import plant

CELLS = [w["name"] for w in harness.benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    result, numbers = plant.run(plant.small_cell(name))
    assert result["correct"], numbers
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
@pytest.mark.parametrize("name", CELLS)
def test_planted_fault_fails(name, fault):
    with plant.FAULTS[fault]():
        result, numbers = plant.run(plant.small_cell(name))
    assert not result["correct"], numbers
    if fault == "state_unchanged":
        assert numbers["change_gap"] == pytest.approx(1.0)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    verdict, numbers = plant.control_verdict(plant.small_cell(name))
    assert not all(v["ok"] for v in verdict.values()), numbers
