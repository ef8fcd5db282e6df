"""Multi-chip fused transport check: 2x2x2 (pod, data, model) host mesh.

The tentpole acceptance cell for the model-axis-sharded flat layout
(``core.flatbuf`` sharded layouts + the ``core.votes`` shard_map fused
program):

1. trajectory parity -- ``transport="fused"`` + ``state_layout="flat"``
   on the model=2 mesh is BITWISE identical to the ``ag_packed`` /
   tree-layout reference (the jnp oracle), on both the pure-jnp route
   and the per-rank Pallas kernel route (interpret mode on CPU);
2. the flat state actually engages the sharded layout
   (``layout.shards == 2``);
3. the optimized HLO of the compiled train step contains NO model-axis
   all-gather (no whole-leaf gather -- asserted STRICTLY via
   ``benchmarks.hlo_analysis.assert_axis_free``, so unattributed
   collectives fail the check instead of hiding in it), and its total
   all-gather traffic is bounded by the 1-bit packed uplink payload;
4. the UNEVEN TP leaf cell: an odd hidden dim (65 % 2 != 0) makes both
   weight matrices shard as padded blocks (``LeafSlot.shard_pad``) --
   the layout must stay ``shards == 2`` with ``shard_dim`` set (NO
   per-bucket copy), trajectories must stay bitwise vs the tree-state
   reference, and the optimized HLO must still carry zero model-axis
   all-gather bytes;
5. the paper's 2 edge x 2 device layout with NO model axis (2x2x1):
   the unsharded flat layout still runs the per-rank shard_map program
   when the kernels run (interpret mode on CPU), and fused/flat is
   BITWISE ``ag_packed``/flat merged at K = 1 and at K = 2 with
   Bernoulli participation, and streamed at K = 2 with Bernoulli
   participation.

Run directly (forces 8 host devices before importing jax):
    PYTHONPATH=src python tests/helpers/sharded_fused_check.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import pathlib
import sys
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

import parity_harness as H  # noqa: E402
from benchmarks import hlo_analysis  # noqa: E402
from repro.core import hier  # noqa: E402
from repro.core.topology import Topology  # noqa: E402

Pn, Dn, Mn = 2, 2, 2
mesh = Mesh(np.array(jax.devices()).reshape(Pn, Dn, Mn),
            ("pod", "data", "model"))
topo = Topology(mesh=mesh, pod_axis="pod")
problem = H.make_problem(Pn, Dn)

# ---- 1a. bitwise trajectory parity, jnp route -------------------------
ref, _ = H.run_hier(topo, problem, "dc_hier_signsgd", "ag_packed", "tree")
got, _ = H.run_hier(topo, problem, "dc_hier_signsgd", "fused", "flat")
H.assert_trees_equal(ref, got, "multichip/fused/flat")
print("multichip fused/flat bitwise parity OK (jnp route)")

# ---- 1b. per-rank Pallas kernels inside shard_map (interpret on CPU) --
os.environ["REPRO_FUSED_PALLAS"] = "interpret"
small = H.make_problem(Pn, Dn, rounds=1, t_e=2)
ref_k, _ = H.run_hier(topo, small, "dc_hier_signsgd", "ag_packed", "tree")
got_k, _ = H.run_hier(topo, small, "dc_hier_signsgd", "fused", "flat")
H.assert_trees_equal(ref_k, got_k, "multichip/fused/flat/kernel")
del os.environ["REPRO_FUSED_PALLAS"]
print("multichip fused/flat bitwise parity OK (kernel route, interpret)")

# ---- 2 + 3. sharded layout engaged, HLO free of model-axis gathers ----
def _compiled_step_stats(prob, bundle):
    """(state, HLO stats) of the compiled fused/flat train step."""
    algo = H._algo("dc_hier_signsgd", "fused", "flat", t_e=prob["t_e"])
    init_fn, step = hier.make_hier_step(topo, algo, bundle)
    state = jax.jit(init_fn)(prob["w0"], jax.random.PRNGKey(1))
    ew = jnp.full((Pn,), 1.0 / Pn)
    dw = jnp.full((Pn, Dn), 1.0 / Dn)
    mask = jnp.ones((Pn, Dn))
    batch = {"train": {"x": prob["xs"][0], "y": prob["ys"][0]}}
    txt = jax.jit(step).lower(state, batch, ew, dw,
                              mask).compile().as_text()
    return state, hlo_analysis.analyze_hlo_text(
        txt, axis_sizes={"pod": Pn, "data": Dn, "model": Mn})


state, stats = _compiled_step_stats(problem, H.make_bundle())
layout = state.params.layout
assert layout.shards == Mn, layout
assert any(s.shard_dim is not None for s in layout.slots)

hlo_analysis.assert_axis_free(stats, op="all-gather", axis="model")
ag_total = hlo_analysis.collective_bytes(stats, op="all-gather")
payload_bound = 4 * layout.n_words        # the whole 1-bit uplink, uint32
assert 0 < ag_total <= payload_bound, (ag_total, payload_bound)
print(f"HLO: zero model-axis all-gather bytes; uplink all-gather "
      f"{ag_total:.0f} B <= packed payload bound {payload_bound} B")

# ---- 4. uneven TP leaves stay SHARDED as padded blocks ----------------
uneven = H.make_problem(Pn, Dn, hid=H.UNEVEN_HID)
ref_u, _ = H.run_hier(topo, uneven, "dc_hier_signsgd", "ag_packed",
                      "tree")
got_u, _ = H.run_hier(topo, uneven, "dc_hier_signsgd", "fused", "flat")
H.assert_trees_equal(ref_u, got_u, "multichip/fused/flat/uneven")
print("uneven TP leaf bitwise parity OK (jnp route)")

# the per-rank kernel route must sweep the uneven last block's zero
# shard tail under the don't-care contract (kernels/ops.py) -- rerun
# the cell through interpret-mode Pallas like the even cell above
os.environ["REPRO_FUSED_PALLAS"] = "interpret"
small_u = H.make_problem(Pn, Dn, rounds=1, t_e=2, hid=H.UNEVEN_HID)
ref_uk, _ = H.run_hier(topo, small_u, "dc_hier_signsgd", "ag_packed",
                       "tree")
got_uk, _ = H.run_hier(topo, small_u, "dc_hier_signsgd", "fused", "flat")
H.assert_trees_equal(ref_uk, got_uk, "multichip/fused/flat/uneven/kernel")
del os.environ["REPRO_FUSED_PALLAS"]
print("uneven TP leaf bitwise parity OK (kernel route, interpret)")

state_u, stats_u = _compiled_step_stats(uneven, H.make_bundle())
lay_u = state_u.params.layout
assert lay_u.shards == Mn, lay_u
padded = [s for s in lay_u.slots if s.shard_pad > 0]
assert len(padded) == 2, lay_u.slots      # w (65%2) and w2 (65%2)
assert all(s.shard_dim is not None for s in padded)
hlo_analysis.assert_axis_free(stats_u, op="all-gather", axis="model")
ag_u = hlo_analysis.collective_bytes(stats_u, op="all-gather")
assert 0 < ag_u <= 4 * lay_u.n_words, (ag_u, 4 * lay_u.n_words)
print(f"uneven HLO: zero model-axis all-gather bytes; uplink "
      f"{ag_u:.0f} B <= packed payload bound {4 * lay_u.n_words} B")

# ---- 5. 2 edges x 2 devices, no model axis: per-rank kernel route ----
import collections  # noqa: E402
import dataclasses  # noqa: E402
from repro.core import votes  # noqa: E402

mesh4 = Mesh(np.array(jax.devices()[:Pn * Dn]).reshape(Pn, Dn, 1),
             ("pod", "data", "model"))
topo4 = Topology(mesh=mesh4, pod_axis="pod")
small4 = H.make_problem(Pn, Dn, rounds=1, t_e=2)
# count traces of the per-rank programs: both must engage with no model
# axis (merged -> fused chain, stream -> tally accumulation)
traced = collections.Counter()


def _counting(name):
    fn = getattr(votes, name)

    def wrapped(topo, layout, *args, **kw):
        assert layout.shards == 1, layout
        traced[name] += 1
        return fn(topo, layout, *args, **kw)
    setattr(votes, name, wrapped)


_counting("_fused_shard_map")
_counting("_tally_acc_shard_map")
os.environ["REPRO_FUSED_PALLAS"] = "interpret"
sampled = H.client_cfg(Pn, Dn, 2, "sampled")
for tag, kw, program in (
        ("K=1", {}, "_fused_shard_map"),
        ("K=2 bernoulli", {"clients": sampled}, "_fused_shard_map"),
        ("K=2 bernoulli stream",
         {"clients": dataclasses.replace(sampled, mode="stream")},
         "_tally_acc_shard_map")):
    ref4, _ = H.run_hier(topo4, small4, "dc_hier_signsgd", "ag_packed",
                         "flat", **kw)
    before = traced[program]
    got4, _ = H.run_hier(topo4, small4, "dc_hier_signsgd", "fused", "flat",
                         **kw)
    assert traced[program] > before, (tag, program, traced)
    H.assert_trees_equal(ref4, got4, f"2x2x1/fused/flat/{tag}")
    print(f"2x2x1 per-rank kernel route ({program}) bitwise parity OK "
          f"({tag})")
del os.environ["REPRO_FUSED_PALLAS"]
print("sharded fused check OK")
