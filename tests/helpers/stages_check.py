"""Which ``stage.*`` scopes survive into the compiled train step.

``compiled_stages`` compiles the smoke-config DC train step (flat
state, fused transport) on a given topology and returns the stages
named in the optimized HLO's ``op_name`` metadata.  Run directly, it
compiles the paper's 2 edge x 2 device layout on four forced host
devices and prints the stages as one JSON line:

    PYTHONPATH=src python tests/helpers/stages_check.py
"""
import os
import re

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.core import clients as vclients, hier  # noqa: E402
from repro.launch import specs  # noqa: E402
from repro.models import build  # noqa: E402

STAGE = re.compile(r'op_name="[^"]*?stage\.(\w+)')


def compiled_stages(topo, mode: str = "merged") -> set:
    """Stages in the compiled step's HLO; ``mode`` is ``merged`` (one
    client a chip) or ``stream`` (K = 4, Bernoulli participation)."""
    cfg = configs.get_smoke("stablelm_3b")
    clients = (vclients.ClientConfig() if mode == "merged" else
               vclients.ClientConfig(count=4, participation="bernoulli",
                                     rate=0.5, mode="stream"))
    algo = hier.AlgoConfig(method="dc_hier_signsgd", t_e=2,
                           transport="fused", state_layout="flat",
                           clients=clients, compute_dtype=jnp.bfloat16)
    built = build.build_model(cfg, topo)
    _, step = hier.make_hier_step(topo, algo, built.bundle)
    state = specs.train_state_abstract(built, topo, algo)
    voters = topo.pods * topo.devices_per_pod
    shape = dataclasses.replace(configs.SHAPES["train_4k"],
                                global_batch=4 * voters, seq_len=16)
    batch = specs.train_batch_abstract(cfg, shape, topo)
    ew, dw, mask = specs.weights_abstract(topo, algo.clients)
    text = jax.jit(step, donate_argnums=(0,)).lower(
        state, batch, ew, dw, mask).compile().as_text()
    return set(STAGE.findall(text))


if __name__ == "__main__":
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.topology import Topology
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2, 1),
                ("pod", "data", "model"))
    topo = Topology(mesh=mesh, pod_axis="pod")
    print(json.dumps({"stages": sorted(compiled_stages(topo))}))
