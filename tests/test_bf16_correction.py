"""The bf16 DC correction's kernel route on four-device meshes.

``helpers/bf16_correction_check.py`` forces four host devices before it
imports jax, so it runs in a subprocess: per-rank interpret-mode kernels
with bf16 directions and a non-zero bf16 flat delta, merged and streamed,
with and without a model axis, bitwise against the jnp route.
"""
import pathlib
import subprocess
import sys

HELPERS = pathlib.Path(__file__).resolve().parent / "helpers"
SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def test_bf16_flat_correction_multidevice():
    env = {"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin", "HOME": "/tmp",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, str(HELPERS / "bf16_correction_check.py")],
        capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, (
        f"STDOUT:\n{r.stdout[-4000:]}\nSTDERR:\n{r.stderr[-4000:]}")
    assert "bf16 correction check OK" in r.stdout
