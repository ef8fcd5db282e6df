"""bf16 DC correction on four-device meshes: kernel route vs jnp route.

With bf16 directions and a bf16 flat delta the per-rank kernel route
(interpret-mode Pallas on CPU) adds the correction once on each rank's
lane-row buffer (``votes._fused_kernel_bufs``), the streamed loop
viewing the delta as lane rows once a step; the jnp route and the
``ag_packed`` reference add it leaf by leaf.  Two rounds, so the staged
correction is non-zero in the second: the trajectories must stay
bitwise identical, on

  * 2 edges x 2 devices, no model axis (the paper's layout): merged
    K = 1 and streamed K = 2 with Bernoulli participation;
  * 2 edges x 2 model shards (per-shard buckets): streamed K = 2.

Run directly (forces 4 host devices before importing jax):
    PYTHONPATH=src python tests/helpers/bf16_correction_check.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import dataclasses  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

import parity_harness as H  # noqa: E402
from repro.core import votes  # noqa: E402
from repro.core.topology import Topology  # noqa: E402

BF16 = dict(compute_dtype=jnp.bfloat16, delta_dtype=jnp.bfloat16)
sampled = H.client_cfg(2, 2, 2, "sampled")
stream = dataclasses.replace(sampled, mode="stream")


# the kernel route must reach the flat-buffer add: bf16 leaves, a flat
# delta, no delta handed to the kernels
routes = []
_bufs = votes._fused_kernel_bufs


def _spy(layout, u_dev, delta_tree, delta_buf, rho):
    out = _bufs(layout, u_dev, delta_tree, delta_buf, rho)
    dtypes = {leaf.dtype for leaf in jax.tree.leaves(u_dev)}
    routes.append(dtypes == {jnp.dtype(jnp.bfloat16)} and delta_tree is None
                  and delta_buf is not None and out[1] is None)
    return out


votes._fused_kernel_bufs = _spy


def topo_of(devs, shards):
    mesh = Mesh(np.array(jax.devices()).reshape(2, devs, shards),
                ("pod", "data", "model"))
    return Topology(mesh=mesh, pod_axis="pod")


def run(topo, problem, transport, layout, interpret=False, **kw):
    if interpret:
        os.environ["REPRO_FUSED_PALLAS"] = "interpret"
    try:
        out, _ = H.run_hier(topo, problem, "dc_hier_signsgd", transport,
                            layout, **BF16, **kw)
    finally:
        os.environ.pop("REPRO_FUSED_PALLAS", None)
    return out


for devs, shards, tag, kw in (
        (2, 1, "2x2x1 K=1", {}),
        (2, 1, "2x2x1 K=2 stream", {"clients": stream}),
        (1, 2, "2x1x2 K=2 stream", {"clients": stream})):
    topo = topo_of(devs, shards)
    problem = H.make_problem(2, devs, rounds=2, t_e=2)
    ref = run(topo, problem, "ag_packed", "tree", **kw)
    got_jnp = run(topo, problem, "fused", "flat", **kw)
    routes.clear()
    got_krn = run(topo, problem, "fused", "flat", interpret=True, **kw)
    assert routes and all(routes), (tag, routes)
    H.assert_trees_equal(ref, got_jnp, f"{tag}/jnp")
    H.assert_trees_equal(ref, got_krn, f"{tag}/kernel")
    # the correction is live: without it the trajectory parts
    plain = run(topo, problem, "fused", "flat", rho=0.0, **kw)
    assert any(not np.array_equal(ref[k], plain[k]) for k in ref), tag
    print(f"{tag}: bf16 kernel route bitwise the jnp route OK")
print("bf16 correction check OK")
