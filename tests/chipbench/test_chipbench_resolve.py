"""Every entry of BENCHMARK.json resolves to its files by name alone."""
from __future__ import annotations

import json
import re

import pytest

import harness

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert [m["name"] for m in BENCH["end_to_end"]] == [
        "tokens_per_s", "peak_hbm_gib", "setup_s"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves(workload):
    cell = harness.resolve(BENCH, workload)
    assert cell.chips in (1, 4)
    for fn in ("param_spec", "loss", "flops_per_token"):
        assert callable(getattr(cell.ref, fn))
    assert harness.limits_path(workload).exists()
    assert cell.limits["limits"]
    tr = cell.traffic
    assert tr["mesh"]["pods"] * tr["mesh"]["data"] == cell.chips


@pytest.mark.parametrize("config", CONFIGS)
def test_config_file_is_named_by_config(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    assert entry["file"] == f"chipbench/configs/{config}.json"
    data = json.loads((harness.ROOT / entry["file"]).read_text())
    assert data["name"] == config
    assert data["reduced"] == entry["reduced"]
    harness.reference_module(config)


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_resolves(metric):
    mod = harness.metric_module(metric)
    assert callable(mod.read)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert set(entry["workloads"]) <= set(WORKLOADS)
    assert entry["moves"] == "tokens_per_s"


def test_names_follow_the_contract():
    names = (WORKLOADS + METRICS + CONFIGS
             + [m["name"] for m in BENCH["end_to_end"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    for name in names:
        assert NAME.match(name), name
    assert len(set(WORKLOADS)) == len(WORKLOADS)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_module_names_map_dashes_and_dots():
    assert harness.module_name("stablelm-3b-6l") == "stablelm_3b_6l"
    assert harness.module_name("device.idle_share") == "device_idle_share"
