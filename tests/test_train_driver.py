"""Integration: the end-to-end driver trains, checkpoints, resumes, and
survives injected faults (device loss -> quorum vote; elastic reweight)."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import hier
from repro.core.topology import single_device_topology
from repro.launch.train import RunCfg, run_training
from repro.runtime import failures


def _algo(**kw):
    base = dict(method="dc_hier_signsgd", mu=2e-3, rho=0.3, t_e=4,
                compute_dtype=jnp.float32)
    base.update(kw)
    return hier.AlgoConfig(**base)


@pytest.fixture(scope="module")
def topo():
    return single_device_topology()


@pytest.mark.slow
def test_training_reduces_loss(topo):
    cfg = configs.get_smoke("stablelm_3b")
    _, hist = run_training(cfg, topo, _algo(), RunCfg(
        steps=24, batch_per_device=8, seq_len=64, log_every=0))
    first = sum(h["loss"] for h in hist[:4]) / 4
    last = sum(h["loss"] for h in hist[-4:]) / 4
    assert last < first, (first, last)


@pytest.mark.slow
def test_checkpoint_resume_continues(topo, tmp_path):
    cfg = configs.get_smoke("xlstm_350m")
    run = RunCfg(steps=10, batch_per_device=4, seq_len=32,
                 ckpt_dir=str(tmp_path), ckpt_every=5, log_every=0)
    _, h1 = run_training(cfg, topo, _algo(), run)
    run2 = RunCfg(steps=14, batch_per_device=4, seq_len=32,
                  ckpt_dir=str(tmp_path), ckpt_every=5, log_every=0)
    _, h2 = run_training(cfg, topo, _algo(), run2)
    # resumed run starts where the first left off
    assert h2[0]["step"] == 10
    assert all(x["loss"] == y["loss"] for x, y in zip(h1, h1))


@pytest.mark.slow
def test_flat_state_resumes_from_tree_checkpoint(topo, tmp_path):
    """Cross-layout resume: a tree-state run's checkpoint loads into a
    state_layout='flat' run (store converts leaves into the buffer) and
    training continues from the same step."""
    cfg = configs.get_smoke("xlstm_350m")
    run = RunCfg(steps=10, batch_per_device=4, seq_len=32,
                 ckpt_dir=str(tmp_path), ckpt_every=5, log_every=0)
    run_training(cfg, topo, _algo(), run)
    run2 = RunCfg(steps=14, batch_per_device=4, seq_len=32,
                  ckpt_dir=str(tmp_path), ckpt_every=5, log_every=0)
    _, h2 = run_training(cfg, topo,
                         _algo(state_layout="flat", transport="fused"),
                         run2)
    assert h2[0]["step"] == 10
    assert all(jnp.isfinite(h["loss"]) for h in h2)


@pytest.mark.slow
def test_fault_injection_device_loss(topo):
    """Losing a device mid-run degrades to quorum voting, not a crash."""
    cfg = configs.get_smoke("gemma3_1b")
    inj = failures.FaultInjector({6: ("device", 0, 0),
                                  9: ("recover", 0, 0)})
    _, hist = run_training(cfg, topo, _algo(), RunCfg(
        steps=12, batch_per_device=4, seq_len=32, log_every=0),
        fault_injector=inj)
    assert len(hist) == 12
    assert all(jnp.isfinite(h["loss"]) for h in hist)
    # membership dipped during the outage and recovered
    assert min(h["live"] for h in hist) < 1.0
    assert hist[-1]["live"] == 1.0


@pytest.mark.slow
def test_overlap_driver_resumes_mid_flight(topo, tmp_path):
    """The end-to-end driver runs the overlapped cloud schedule and
    resumes from a checkpoint taken MID-round (t_e=4, ckpt_every=5:
    step 10 is two local steps into a round, with an aggregate staged
    in agg_next) -- the staged slot rides the async checkpoint path."""
    cfg = configs.get_smoke("xlstm_350m")
    algo = _algo(cloud_overlap="overlap")
    run = RunCfg(steps=10, batch_per_device=4, seq_len=32,
                 ckpt_dir=str(tmp_path), ckpt_every=5, log_every=0)
    _, h1 = run_training(cfg, topo, algo, run)
    run2 = RunCfg(steps=14, batch_per_device=4, seq_len=32,
                  ckpt_dir=str(tmp_path), ckpt_every=5, log_every=0)
    _, h2 = run_training(cfg, topo, algo, run2)
    assert h2[0]["step"] == 10
    assert all(jnp.isfinite(h["loss"]) for h in h1 + h2)


def test_cli_rejects_overlap_on_fsdp_arch():
    """--cloud_overlap=overlap on an FSDP arch is rejected at the CLI
    (exit 2, readable argparse error) BEFORE any model build or
    tracing."""
    import pathlib
    import subprocess
    import sys
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "gemma3_12b", "--cloud_overlap", "overlap", "--steps", "1"],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": str(src), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"})
    assert r.returncode == 2, (r.returncode, r.stderr[-2000:])
    assert "replicated regime" in r.stderr
    assert "--cloud_overlap" in r.stderr
    assert "Traceback" not in r.stderr


def test_cli_rejects_bad_client_carve():
    """A per-device batch that does not divide into --clients_per_device
    is rejected at the CLI (exit 2, readable argparse error) BEFORE any
    model build or tracing -- not a mid-trace shape error."""
    import pathlib
    import subprocess
    import sys
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--smoke",
         "--batch", "5", "--clients_per_device", "4", "--steps", "1"],
        capture_output=True, text=True, timeout=300,
        env={"PYTHONPATH": str(src), "PATH": "/usr/bin:/bin",
             "HOME": "/tmp"})
    assert r.returncode == 2, (r.returncode, r.stderr[-2000:])
    assert "does not divide into" in r.stderr
    assert "--clients_per_device" in r.stderr
    assert "Traceback" not in r.stderr


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    """``chip_smoke.py`` has no CPU fallback: with only CPU devices, or
    run outside a checkout of the repository, it exits non-zero and
    prints no result line."""
    script = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0, (r.stdout, r.stderr)
    assert '"ok"' not in r.stdout, r.stdout
