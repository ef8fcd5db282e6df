"""Virtual-client sweep benchmark: merged voter axis vs streamed loop.

Sweeps K clients per device x {merged, stream} on the cost-model MLP
(51018 params, the paper's EMNIST shape) and records per-step wall time
plus two memory accountings:

  * analytic peak LIVE sign-plane bytes of the local step -- merged
    materializes K int8 sign planes + K packed word planes at once
    (K * (n + n/8) bytes); the streamed sweep holds ONE client's packed
    words plus the persistent integer tally
    (n/8 + tally_itemsize * n bytes), independent of K;
  * the compiled step's ``memory_analysis()`` temp/argument bytes
    (empirical, backend permitting).

Merged rows whose estimated live gradient planes (K * n * 4 bytes of
f32 voter grads) exceed ``--max_live_mb`` are recorded as REFUSED
without compiling -- that is the regime the streamed mode exists for:
K=1024 streams on a single CPU device while merged would blow the
budget.  The acceptance contract (checked into BENCH_clients.json):
stream at K=1024 stays within 2x of the K=1 merged baseline in peak
live sign-plane bytes (unit weights at K=1024 need an int16 tally:
2.125n vs the baseline's 1.125n, ratio ~1.89).

  PYTHONPATH=src python benchmarks/bench_clients.py [--fast]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import clients as vclients
from repro.core import hier, votes
from repro.core.topology import single_device_topology
from repro.launch import compile_cache

# the cost-model EMNIST MLP (benchmarks/cost_model.D_PARAMS)
DIN, HID, DOUT = 784, 64, 10
N_PARAMS = DIN * HID + HID + HID * DOUT + DOUT          # 51018

SPECS = {"w1": P(None, None), "b1": P(None),
         "w2": P(None, None), "b2": P(None)}

K_SWEEP = (4, 64, 256, 1024)
K_SWEEP_FAST = (4, 64)


def loss_fn(params, batch, rng):
    h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred - batch["y"]) ** 2)


def init_params(key):
    k1, k2 = jax.random.split(key)
    return {"w1": jax.random.normal(k1, (DIN, HID)) * 0.05,
            "b1": jnp.zeros((HID,)),
            "w2": jax.random.normal(k2, (HID, DOUT)) * 0.05,
            "b2": jnp.zeros((DOUT,))}


def client_config(k: int, mode: str) -> vclients.ClientConfig:
    if k == 1:                      # the inactive legacy baseline
        return vclients.ClientConfig()
    return vclients.ClientConfig(count=k, participation="bernoulli",
                                 rate=0.5, seed=3, mode=mode)


def sign_plane_bytes(mode: str, k: int, weight_bound: int | None) -> int:
    """Analytic peak live sign-plane bytes of one local step."""
    n = N_PARAMS
    words_b = (n // 32 + (1 if n % 32 else 0)) * 4
    if mode == "merged":
        return k * n + k * words_b              # K int8 planes + K packed
    acc = jnp.dtype(votes.tally_dtype(weight_bound)).itemsize
    return words_b + acc * n                    # ONE packed plane + tally


def merged_live_grad_mb(k: int) -> float:
    """Estimated live f32 voter-gradient planes of the merged step."""
    return k * N_PARAMS * 4 / 2**20


def bench_one(topo, k: int, mode: str, iters: int, max_live_mb: float):
    cc = client_config(k, mode)
    bound = (cc.weight_bound(topo.pods, topo.devices_per_pod)
             if cc.active else None)
    row = {
        "mode": mode, "clients": k, "batch_per_device": k,
        "sign_plane_bytes": sign_plane_bytes(mode, k, bound),
        "refused": False, "reason": None,
    }
    if mode == "merged" and merged_live_grad_mb(k) > max_live_mb:
        row["refused"] = True
        row["reason"] = (f"estimated live voter grads "
                         f"{merged_live_grad_mb(k):.0f} MB > "
                         f"--max_live_mb {max_live_mb:.0f}")
        return row

    algo = hier.AlgoConfig(method="dc_hier_signsgd", transport="fused",
                           state_layout="flat", clients=cc,
                           compute_dtype=jnp.float32,
                           master_dtype=jnp.float32,
                           delta_dtype=jnp.float32)
    bundle = hier.ModelBundle(loss=loss_fn, compute_specs=SPECS,
                              master_specs=SPECS)
    # sync="never": the steady-state local step (the anchor pass is a
    # per-round cost, amortized 1/T_E; this bench prices the inner loop)
    init_fn, step = hier.make_hier_step(topo, algo, bundle, sync="never")
    state = jax.jit(init_fn)(init_params(jax.random.PRNGKey(0)),
                             jax.random.PRNGKey(1))
    p, d = topo.pods, topo.devices_per_pod
    b = k                                       # one row per client
    key = jax.random.PRNGKey(7)
    batch = {"train": {
        "x": jax.random.normal(key, (p, d, b, DIN)),
        "y": jax.random.normal(jax.random.fold_in(key, 1),
                               (p, d, b, DOUT))}}
    ew = jnp.ones((p,)) / p
    dw = jnp.ones((p, d)) / d
    mask = jnp.ones((p, d))

    jstep = jax.jit(step)
    lowered = jstep.lower(state, batch, ew, dw, mask)
    compiled = lowered.compile()
    try:
        ma = compiled.memory_analysis()
        row["temp_bytes"] = getattr(ma, "temp_size_in_bytes", None)
        row["argument_bytes"] = getattr(ma, "argument_size_in_bytes", None)
    except Exception as e:                       # backend-dependent
        row["memory_analysis_error"] = str(e)

    state, _ = jax.block_until_ready(jstep(state, batch, ew, dw, mask))
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = jstep(state, batch, ew, dw, mask)
    jax.block_until_ready(state)
    row["us_per_step"] = (time.perf_counter() - t0) / iters * 1e6
    row["loss"] = float(metrics["loss"])
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI profile: K in {4, 64}, fewer timed iters")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--max_live_mb", type=float, default=128.0,
                    help="live-memory budget; merged rows whose voter "
                         "grads exceed it are recorded as refused")
    ap.add_argument("--out", default=str(
        pathlib.Path(__file__).resolve().parents[1]
        / "BENCH_clients.json"))
    args = ap.parse_args()
    compile_cache.enable()

    topo = single_device_topology()
    sweep = K_SWEEP_FAST if args.fast else K_SWEEP
    iters = args.iters or (2 if args.fast else 5)

    rows = [bench_one(topo, 1, "merged", iters, args.max_live_mb)]
    print("mode,clients,us_per_step,sign_plane_bytes,refused")
    for k in sweep:
        for mode in ("merged", "stream"):
            rows.append(bench_one(topo, k, mode, iters, args.max_live_mb))
    for r in rows:
        print(f"{r['mode']},{r['clients']},"
              f"{r.get('us_per_step', 0.0):.1f},"
              f"{r['sign_plane_bytes']},{r['refused']}")

    by = {(r["mode"], r["clients"]): r for r in rows}
    base = by[("merged", 1)]["sign_plane_bytes"]
    checks = {"merged_k1_sign_plane_bytes": base}
    top = max(sweep)
    if ("stream", top) in by:
        ratio = by[("stream", top)]["sign_plane_bytes"] / base
        checks[f"stream_k{top}_sign_plane_ratio"] = round(ratio, 3)
        checks["stream_within_2x_of_k1_merged"] = ratio <= 2.0
        checks[f"stream_k{top}_ran"] = not by[("stream", top)]["refused"]
    if ("merged", top) in by:
        checks[f"merged_k{top}_refused"] = by[("merged", top)]["refused"]
    report = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "n_params": N_PARAMS,
            "iters": iters,
            "max_live_mb": args.max_live_mb,
            "note": "dc_hier_signsgd/fused/flat local step (sync=never), "
                    "one row per client per device batch; sign-plane "
                    "bytes are the analytic peak live planes (merged: "
                    "K*(n + n/8); stream: n/8 + tally_itemsize*n).",
        },
        "rows": rows,
        "checks": checks,
    }
    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {out_path} (checks={checks})")


if __name__ == "__main__":
    main()
