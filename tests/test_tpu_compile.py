"""The TPU compiler accepts the Pallas kernels and the fused train step.

Interpret mode (tests/test_kernels.py) checks what the kernels compute;
it cannot see what Mosaic refuses on the chip -- blocks that break the
(8, 128) tiling rule, reshapes it cannot lay out, 1-D VMEM blocks, VMEM
overflow.  These tests compile for a described (not attached) TPU v5e,
so they need the TPU compiler that ships with jaxlib, but no chip.
They run no program.

Shapes: gemma3-1b's flat master buffer (802 385 920 coordinates = 195 895
rows of 4096, an odd row count) and a 3-row buffer (fewer rows than one
8-row tile).  The topology is described inside a module fixture: only
the worker that runs this file loads the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro import configs
from repro.core import clients as vclients
from repro.core import hier
from repro.core.topology import Topology
from repro.kernels import ops as kops
from repro.kernels import sign_pack as sp
from repro.kernels import tally_acc as ta
from repro.kernels import vote_update as vu
from repro.launch import specs
from repro.models import build

GEMMA_ROWS = 802385920 // sp.TILE          # 195895 word rows
ROWS = [GEMMA_ROWS, 3]
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip: keep it off here
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, kernel=None):
    """Compile for the described chip; with ``kernel``, the Pallas call's
    HLO instruction must carry that name (``%<kernel>.N``), which the
    benchmark's kernel readers match in a device trace."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert CUSTOM_CALL in text
    if kernel is not None:
        calls = re.findall(r"%([\w.-]+) = \S+ custom-call\(.*"
                           + re.escape(CUSTOM_CALL), text)
        assert calls and all(re.fullmatch(rf"{kernel}(\.\d+)?", c)
                             for c in calls), calls
    return compiled


@pytest.mark.parametrize("word_rows", ROWS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_delta", [False, True])
def test_sign_pack_compiles(one_chip, word_rows, dtype, with_delta):
    sds = jax.ShapeDtypeStruct((1, word_rows * sp.PACK, sp.LANES), dtype,
                               sharding=one_chip)
    if with_delta:
        _compile(lambda g, d: sp.sign_pack(g, d, 0.2), sds, sds,
                 kernel="sign_pack")
    else:
        _compile(lambda g: sp.sign_pack(g), sds, kernel="sign_pack")


@pytest.mark.parametrize("word_rows", ROWS)
@pytest.mark.parametrize("masked", [False, True])
def test_vote_update_compiles(one_chip, word_rows, masked):
    k = 4
    words = jax.ShapeDtypeStruct((1, k, word_rows, sp.LANES), jnp.uint32,
                                 sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, word_rows * sp.PACK, sp.LANES),
                             jnp.float32, sharding=one_chip)
    if masked:
        m = jax.ShapeDtypeStruct((1, k), jnp.int32, sharding=one_chip)
        _compile(lambda p, v, m: vu.vote_update(p, v, m, mu=1e-3),
                 words, v, m, kernel="vote_update")
    else:
        _compile(lambda p, v: vu.vote_update(p, v, mu=1e-3), words, v,
                 kernel="vote_update")


@pytest.mark.parametrize("word_rows", ROWS)
@pytest.mark.parametrize("tally_dtype", [jnp.int8, jnp.int32])
def test_tally_acc_compiles(one_chip, word_rows, tally_dtype):
    shape = (1, word_rows * sp.PACK, sp.LANES)
    g = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    t = jax.ShapeDtypeStruct(shape, tally_dtype, sharding=one_chip)
    w = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    _compile(lambda g, d, w, t: ta.tally_acc(g, d, w, t, rho=0.2),
             g, g, w, t, kernel="tally_acc")
    _compile(lambda g, w, t: ta.tally_acc(g, None, w, t), g, w, t,
             kernel="tally_acc")


@pytest.mark.parametrize("mode", ["merged", "stream"])
def test_fused_step_compiles_with_kernels(topo, monkeypatch, mode):
    """The whole smoke-config DC train step (flat state, fused
    transport, round-boundary branch included) compiles for one chip
    with the kernel route on, and the kernels are in its HLO."""
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_FUSED_PALLAS", raising=False)
    mesh = Mesh([[topo.devices[0]]], ("data", "model"))
    tp = Topology(mesh=mesh, pod_axis=None)
    cfg = configs.get_smoke("gemma3_1b")
    clients = (vclients.ClientConfig() if mode == "merged" else
               vclients.ClientConfig(count=4, participation="bernoulli",
                                     rate=0.5, mode="stream"))
    algo = hier.AlgoConfig(method="dc_hier_signsgd", t_e=2,
                           transport="fused", state_layout="flat",
                           clients=clients, compute_dtype=jnp.bfloat16)
    built = build.build_model(cfg, tp)
    _, step = hier.make_hier_step(tp, algo, built.bundle)
    state = specs.train_state_abstract(built, tp, algo)
    shape = dataclasses.replace(configs.SHAPES["train_4k"],
                                global_batch=4, seq_len=16)
    batch = specs.train_batch_abstract(cfg, shape, tp)
    ew, dw, mask = specs.weights_abstract(tp, algo.clients)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, batch, ew, dw, mask).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) >= 1
    # the kernels keep their names, and every stage of the kernel
    # route is in the op_name metadata
    kernels = (("sign_pack", "vote_update") if mode == "merged"
               else ("tally_acc",))
    for name in kernels:
        assert re.search(rf"%{name}(\.\d+)? = ", text), name
    run = {"merged": {"sign_pack", "vote_update"},
           "stream": {"tally_acc", "tally_finish"}}[mode]
    got = set(re.findall(r'op_name="[^"]*?stage\.(\w+)', text))
    assert got == {"grads", "correction", "relayout", "cloud_mean",
                   "anchor"} | run
