"""One run of one benchmark cell: set-up, measured window, correctness.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in files of its own, found by the names in
``BENCHMARK.json``:

  configs/<config>.json      sizes of the model as it is run
  reference/<config>.py      its plain float32 reference and FLOPs
  traffic/<traffic>.json     the federated job: method, K, T_E, batch
  metrics/<metric>.py        a per-layer metric's reader
  limits/<workload>.json     the limit of each number ``correct`` compares

(``-`` and ``.`` in a name become ``_`` in a Python module's name.)

The system under test is the training step of ``src/repro``: the
jitted ``train_step`` of ``core.hier.make_hier_step`` (donated state),
fed as ``launch.train.run_training`` feeds it.  The window runs whole
global rounds of T_E steps until the requested seconds have passed.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import math
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
for _p in (str(HERE), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import sampler  # noqa: E402
from peaks import peaks_for  # noqa: E402

GIB = float(1 << 30)


def module_name(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    ref: object
    limits: dict | None
    end_to_end: list
    per_layer: list


def config_path(name: str) -> pathlib.Path:
    return HERE / "configs" / f"{name}.json"


def traffic_path(name: str) -> pathlib.Path:
    return HERE / "traffic" / f"{name}.json"


def limits_path(workload: str) -> pathlib.Path:
    return HERE / "limits" / f"{workload}.json"


def reference_module(config: str):
    return importlib.import_module(f"reference.{module_name(config)}")


def metric_module(metric: str):
    return importlib.import_module(f"metrics.{module_name(metric)}")


def resolve(bench: dict, workload: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    lp = limits_path(workload)
    reports = lambda m: "workloads" not in m or workload in m["workloads"]
    return Cell(
        name=workload, chips=w["chips"],
        config=_json(config_path(w["config"])),
        traffic=_json(traffic_path(w["traffic"])),
        ref=reference_module(w["config"]),
        limits=_json(lp) if lp.exists() else None,
        end_to_end=[m for m in bench["end_to_end"] if reports(m)],
        per_layer=[m for m in bench["per_layer"] if reports(m)])


# ---------------------------------------------------------------- weights

def seed_key(seed: int, stream: int):
    import jax
    key = jax.random.PRNGKey(0)
    for word in (seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, stream):
        key = jax.random.fold_in(key, word)
    return key


def _is_spec_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make_params(spec: dict, key):
    """One replica's f32 weights from ``key``: each leaf drawn from its own
    fold of the key, by the init the reference's ``param_spec`` names."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_spec_leaf)
    out = []
    for i, (shape, init) in enumerate(leaves):
        if init[0] == "normal":
            out.append(init[1] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
        elif init[0] == "const":
            out.append(jnp.full(shape, init[1], jnp.float32))
        else:
            out.append(jnp.zeros(shape, jnp.float32))
    return treedef.unflatten(out)


def spec_shapes(spec: dict):
    import jax
    return jax.tree.map(lambda s: tuple(s[0]), spec, is_leaf=_is_spec_leaf)


# ---------------------------------------------------------------- program

class Program:
    """The system under test, built through its own API, on ``devices``."""

    def __init__(self, cell: Cell, devices):
        import jax
        import jax.numpy as jnp
        from repro.core import clients as vclients, hier
        from repro.core.topology import Topology
        from repro.models import build
        from repro.models.config import LMConfig, XLSTMCfg
        from repro.runtime import elastic
        from jax.sharding import Mesh

        tr, model = cell.traffic, dict(cell.config["model"])
        if "xlstm" in model:
            model["xlstm"] = XLSTMCfg(**model["xlstm"])
        self.cfg = LMConfig(name=cell.config["name"], **model)
        mesh = tr["mesh"]
        self.pods, self.devices = mesh["pods"], mesh["data"]
        devs = np.asarray(devices[:self.pods * self.devices])
        # the reference keeps each edge on the first chip of that edge
        self.pod_devices = ([devs[q * self.devices] for q in range(self.pods)]
                            if self.pods > 1 else None)
        if self.pods > 1:
            self.topo = Topology(Mesh(devs.reshape(self.pods, self.devices, 1),
                                      ("pod", "data", "model")), "pod")
        else:
            self.topo = Topology(Mesh(devs.reshape(self.devices, 1),
                                      ("data", "model")), pod_axis=None)
        c = tr["clients"]
        cc = vclients.ClientConfig(
            count=c["count"], participation=c.get("participation", "full"),
            rate=c.get("rate", 1.0), seed=c.get("seed", 0),
            mode=c.get("mode", "merged"))
        self.t_e = tr["t_e"]
        self.algo = hier.AlgoConfig(
            method=tr["method"], mu=tr["mu"], rho=tr["rho"], t_e=tr["t_e"],
            transport=tr["transport"], state_layout=tr["state_layout"],
            clients=cc, compute_dtype=jnp.dtype(tr["compute_dtype"]),
            delta_dtype=jnp.dtype(tr["delta_dtype"]))
        built = build.build_model(self.cfg, self.topo)
        self.spec = cell.ref.param_spec(cell.config["model"])
        want = spec_shapes(self.spec)
        have = jax.tree.map(lambda a: tuple(a.shape), built.abstract_params())
        if jax.tree.structure(want) != jax.tree.structure(have) or \
                jax.tree.leaves(want) != jax.tree.leaves(have):
            raise ValueError("the reference's parameter tree does not match "
                             "the model's")
        init_fn, step_fn = hier.make_hier_step(self.topo, self.algo,
                                               built.bundle)
        self.jstep = jax.jit(step_fn, donate_argnums=(0,))
        spec = self.spec
        self.init = jax.jit(lambda k0, k1: init_fn(make_params(spec, k0), k1))
        self.params0 = jax.jit(lambda k0: make_params(spec, k0))
        arrays = elastic.Membership(self.pods, self.devices,
                                    clients=cc).weights()
        self.edge_weights = np.asarray(arrays.edge_weights, np.float64)
        self.dev_weights = np.asarray(arrays.dev_weights, np.float64)
        self.weights = (jnp.asarray(arrays.edge_weights),
                        jnp.asarray(arrays.dev_weights),
                        jnp.asarray(arrays.mask))

    def fresh_state(self, seed: int):
        return self.init(seed_key(seed, 0), seed_key(seed, 1))

    def step(self, state, tokens: np.ndarray):
        import jax.numpy as jnp
        return self.jstep(state, {"train": {"tokens": jnp.asarray(tokens)}},
                          *self.weights)

    @staticmethod
    def host_trees(flat_state) -> list:
        """A flat [P, n_pad] state entry -> per-pod numpy leaf trees."""
        from repro.core import flatbuf
        buf = np.asarray(flat_state.buf)
        tree = flatbuf.unflatten_tree(flat_state.layout, buf, batch_dims=1,
                                      cast=False)
        import jax
        return [jax.tree.map(lambda a: a[q], tree)
                for q in range(buf.shape[0])]


def pool_for(cell: Cell, seed: int, n_batches: int) -> np.ndarray:
    tr, m = cell.traffic, cell.config["model"]
    data = tr.get("data", {})
    return sampler.make_pool(
        m["vocab"], tr["mesh"]["pods"], tr["mesh"]["data"],
        tr["clients"]["count"], tr["batch_per_device"], tr["seq_len"],
        n_batches, seed, skew=data.get("skew", 1.2),
        hetero=data.get("hetero", 1.0),
        alpha_client=data.get("alpha_client"))


def tokens_per_step(cell: Cell) -> int:
    tr = cell.traffic
    return (tr["mesh"]["pods"] * tr["mesh"]["data"]
            * tr["batch_per_device"] * tr["seq_len"])


# ---------------------------------------------------------------- set-up

CHECK_STEPS = 3


def warmup_steps(t_e: int) -> int:
    """Whole rounds, at least one, covering the steps the check reads."""
    return t_e * -(-CHECK_STEPS // t_e)


def first_steps(program: Program, state, pool: np.ndarray):
    """Drive the fresh state through the warm-up (whole rounds) with the
    window's own call, keeping what the correctness check reads: the
    masters after steps 1 and 3, the correction staged at step 0, and
    the first steps' losses per edge."""
    import jax
    keep = {"losses": []}
    for i in range(warmup_steps(program.t_e)):
        state, metrics = program.step(state, pool[i % len(pool)])
        if i < CHECK_STEPS:
            keep["losses"].append(np.asarray(metrics["loss_per_pod"]))
        if i == 0:
            keep["n_pad"] = state.params.layout.n_pad
            keep["p1"] = Program.host_trees(state.params)
            keep["delta"] = (Program.host_trees(state.delta_next)
                             if state.delta_next is not None else None)
        if i == CHECK_STEPS - 1:
            keep["p3"] = Program.host_trees(state.params)
    jax.block_until_ready(state)
    keep["losses"] = np.stack(keep["losses"]).astype(np.float64)
    return state, keep


def window(program: Program, state, pool: np.ndarray, seconds: float,
           start_step: int, trace_dir: str | None = None):
    """Whole rounds until ``seconds`` have passed, starting at pool
    index ``start_step``.  Returns (state, steps, failed steps, elapsed
    seconds); a step whose loss is not finite has failed."""
    import jax
    t_e = program.t_e
    steps = failed = 0
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    span = jax.profiler.TraceAnnotation
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            losses = []
            for i in range(t_e):
                with span("bench.batch"):
                    toks = pool[(start_step + steps + i) % len(pool)]
                with span("bench.dispatch"):
                    state, metrics = program.step(state, toks)
                losses.append(metrics["loss"])
            with span("bench.loss_read"):
                vals = jax.device_get(losses)
            failed += sum(1 for v in vals if not math.isfinite(float(v)))
            steps += t_e
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    return state, steps, failed, elapsed


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ---------------------------------------------------------------- the run

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t_process: float, trace_dir: str | None = None
             ):
    """Set-up, window and check of one cell; returns the result line
    and notes for standard error."""
    import jax
    import check

    program = Program(cell, devices)
    warm = warmup_steps(program.t_e)
    pool = pool_for(cell, seed, warm + program.t_e)
    state = program.fresh_state(seed)
    state, keep = first_steps(program, state, pool)
    compiles_before = program.jstep._cache_size()
    setup_s = time.perf_counter() - t_process

    state, steps, failed, elapsed = window(
        program, state, pool, seconds, warm, trace_dir if trace else None)
    compiled_in_window = program.jstep._cache_size() - compiles_before
    peak = peak_bytes(devices[:cell.chips])
    del state
    gc.collect()

    numbers = check.compare_program(cell, program, keep, seed, pool)
    checks = check.judge(numbers, cell.limits)
    correct = (failed == 0 and compiled_in_window == 0
               and all(c["ok"] for c in checks.values()) and bool(checks))

    kind = devices[0].device_kind
    result = {
        "correct": correct, "attempted": steps, "failed": failed,
        "metrics": {},
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": cell.chips, "memory_peak_bytes": peak},
    }
    if not trace:
        values = {"tokens_per_s": steps * tokens_per_step(cell) / elapsed,
                  "peak_hbm_gib": peak / GIB, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    else:
        import devtrace
        ctx = devtrace.Context(
            devtrace.load(trace_dir), chips=cell.chips, steps=steps,
            t_e=program.t_e, tokens_per_step=tokens_per_step(cell),
            cell=cell, peaks=peaks_for(kind), n_pad=keep["n_pad"])
        for m in cell.per_layer:
            value = metric_module(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = ctx.busy_s
        result["device"]["window_s"] = ctx.window_s
        result["breakdown"] = {"device_ops": ctx.top_ops(10),
                               "idle_gaps": ctx.idle_gaps(10)}
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in checks.items()}
    notes = {"numbers": numbers, "compiled_in_window": compiled_in_window,
             "window_s": elapsed, "steps": steps}
    return result, notes
