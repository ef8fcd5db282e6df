"""Stage scopes and the voter-pass counters of the train step.

The scopes (``core.stages``) only add ``op_name`` metadata: each stage a
path runs must survive into the compiled step's HLO, on one device
(merged and streamed clients) and on the paper's 2 edge x 2 device
layout (four forced host devices, in a subprocess).  The counters
``voter_passes`` / ``votes_cast`` must equal the participation mask's
counts.
"""
import dataclasses
import json
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).parent / "helpers"))
import parity_harness as H  # noqa: E402
import stages_check  # noqa: E402

from repro.core import clients as vclients  # noqa: E402
from repro.core import hier, stages  # noqa: E402
from repro.core.topology import single_device_topology  # noqa: E402

HELPERS = pathlib.Path(__file__).parent / "helpers"
SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

# the pure-jnp route (off the chip) packs per leaf with the correction
# folded in; the kernel route's stages are checked for the chip in
# tests/test_tpu_compile.py
COMMON = {"grads", "correction", "relayout", "sign_pack", "cloud_mean",
          "anchor"}
EXPECTED = {
    "merged": COMMON | {"vote_update"},
    "stream": COMMON | {"tally_acc", "tally_finish"},
    "2x2": COMMON | {"vote_exchange", "vote_update"},
}


def test_stage_names_are_checked():
    assert set(EXPECTED["merged"] | EXPECTED["stream"]
               | EXPECTED["2x2"]) == set(stages.STAGES)
    with pytest.raises(ValueError):
        stages.scope("not_a_stage")


@pytest.mark.parametrize("mode", ["merged", "stream"])
def test_stages_in_compiled_step(mode):
    got = stages_check.compiled_stages(single_device_topology(), mode)
    assert got == EXPECTED[mode]


@pytest.mark.slow
def test_stages_in_compiled_step_2x2():
    env = {"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin", "HOME": "/tmp",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(HELPERS / "stages_check.py")],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])["stages"]
    assert set(got) == EXPECTED["2x2"]


def test_in_stage_nests_on_recursion():
    @stages.in_stage("relayout")
    def depth(n, x):
        return x if n == 0 else depth(n - 1, x * 2.0)

    text = jax.jit(lambda x: depth(2, x) + 1.0).lower(1.0).as_text(
        debug_info=True)
    # the add after the recursion is back outside every scope
    assert '"jit(<lambda>)/add"' in text
    assert "stage.relayout/stage.relayout/mul" in text


@pytest.mark.parametrize("mode", ["merged", "stream"])
@pytest.mark.parametrize("regime", ["full", "sampled"])
def test_voter_counters_match_participation(mode, regime):
    pods, devs, k, t_e = 1, 1, 4, 3
    problem = H.make_problem(pods, devs, rounds=3, t_e=t_e)
    cc = dataclasses.replace(H.client_cfg(pods, devs, k, regime), mode=mode)
    algo = H._algo("dc_hier_signsgd", "fused", "flat", t_e=t_e, clients=cc)
    init_fn, step = hier.make_hier_step(single_device_topology(), algo,
                                        H.make_bundle())
    state = jax.jit(init_fn)(problem["w0"], jax.random.PRNGKey(1))
    ew = jnp.ones((pods,))
    dw = jnp.ones((pods, devs))
    mask = jnp.ones((pods, devs, k))
    jstep = jax.jit(step)
    cast = []
    for s in range(problem["rounds"] * t_e):
        batch = {"train": {"x": problem["xs"][s], "y": problem["ys"][s]}}
        state, metrics = jstep(state, batch, ew, dw, mask)
        assert metrics["voter_passes"].dtype == jnp.int32
        assert int(metrics["voter_passes"]) == pods * devs * k
        part = vclients.participation_mask(cc, pods, devs, s // t_e)
        weights = cc.weight_array(pods, devs) * np.asarray(part)
        assert int(metrics["votes_cast"]) == int(np.sum(weights > 0))
        cast.append(int(metrics["votes_cast"]))
    if regime == "full":
        assert cast == [pods * devs * k] * len(cast)
    else:
        assert min(cast) < pods * devs * k


def test_legacy_step_counts_the_device_mask():
    problem = H.make_problem(1, 1, rounds=1, t_e=2)
    algo = H._algo("dc_hier_signsgd", "fused", "flat", t_e=2)
    init_fn, step = hier.make_hier_step(single_device_topology(), algo,
                                        H.make_bundle())
    state = jax.jit(init_fn)(problem["w0"], jax.random.PRNGKey(1))
    batch = {"train": {"x": problem["xs"][0], "y": problem["ys"][0]}}
    jstep = jax.jit(step)
    for live in (1.0, 0.0):
        _, metrics = jstep(state, batch, jnp.ones((1,)), jnp.ones((1, 1)),
                           jnp.full((1, 1), live))
        assert set(metrics) == {"loss", "loss_per_pod", "mu",
                                "voter_passes", "votes_cast"}
        assert int(metrics["voter_passes"]) == 1
        assert int(metrics["votes_cast"]) == int(live)
