"""Transport benchmark: the sign->pack->vote->update sweep per transport.

Times one full local-step direction+update (DC correction fused pre-sign,
majority vote over the ``data`` axis, ``v <- v - mu*vote``) for each sign
transport (``ag_packed`` per-leaf, ``ar_int8``, flat-buffer ``fused``)
across model sizes and logical (pods x devices) counts, and extracts the
static HBM / collective byte accounting from the optimized HLO via
``benchmarks.hlo_analysis`` -- the same analyzer the dry-run rooflines use.

Runs anywhere (CPU uses the pure-jnp fallback path, which is what GSPMD
lowers on real meshes); on TPU the fused transport's local sweeps run the
Pallas kernels.  Emits machine-readable ``BENCH_transports.json`` (checked
in to seed the perf trajectory) plus a CSV mirror on stdout.

  PYTHONPATH=src python benchmarks/bench_transports.py \
      --sizes 1000000,8000000 --devices 1x8,2x4 --iters 3
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

from benchmarks import hlo_analysis
from repro.core import flatbuf, signs, votes
from repro.core.topology import single_device_topology
from repro.launch import compile_cache

MU, RHO = 1e-3, 0.2

TRANSPORTS = ("ag_packed", "ar_int8", "fused", "fused_flat")


def model_shapes(n_target: int) -> list[tuple[int, ...]]:
    """Mixed leaf shapes ~ a transformer stack: wide aligned matrices plus
    odd-minor vectors (norm scales / biases) that defeat 32-bit packing."""
    shapes: list[tuple[int, ...]] = [(33,), (129,), (513,), (1023,)]
    remaining = n_target - sum(s[0] for s in shapes)
    d = 1024
    while remaining > 0:
        r = min(max(remaining // d, 1), 4096)
        shapes.append((r, d))
        remaining -= r * d
    return shapes


def make_inputs(n_target: int, pods: int, devs: int, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    g_dev, delta, params = {}, {}, {}
    for i, s in enumerate(model_shapes(n_target)):
        k = jax.random.fold_in(key, i)
        g_dev[f"leaf{i}"] = jax.random.normal(k, (pods, devs) + s)
        delta[f"leaf{i}"] = jax.random.normal(
            jax.random.fold_in(k, 1), (pods,) + s)
        params[f"leaf{i}"] = jax.random.normal(
            jax.random.fold_in(k, 2), (pods,) + s)
    return g_dev, delta, params


def make_step(topo, transport: str, layout=None):
    """One DC local step: direction via ``transport`` + sign-descent update.

    Mirrors ``core.hier.local_direction`` exactly (per-leaf delta
    broadcast + add for the per-leaf transports; correction folded into
    the flat sweep for ``fused``).  ``fused_flat`` is the
    ``state_layout="flat"`` hot path: params/delta are already flat
    buffers and the update is ONE whole-model ``vote_update``
    read-modify-write (``votes.fused_sign_vote_update``)."""

    if transport == "fused_flat":
        def step_flat(g_dev, delta_buf, params_buf):
            return votes.fused_sign_vote_update(
                topo, layout, g_dev, delta_buf, RHO, None, params_buf,
                jnp.float32(MU), mu_static=MU)

        return step_flat

    def step(g_dev, delta, params):
        if transport == "fused":
            direction = votes.fused_sign_vote(topo, g_dev, delta, RHO, None)
        else:
            u = jax.tree.map(
                lambda g, dl: g + RHO * dl[:, None].astype(g.dtype),
                g_dev, delta)
            s = jax.tree.map(signs.sgn, u)
            direction = jax.tree.map(
                lambda s_: votes.majority_vote_dev(
                    topo, s_, None, transport,
                    P(*([None] * (s_.ndim - 2)))),
                s)
        return jax.tree.map(
            lambda v, d: v - MU * d.astype(v.dtype), params, direction)

    return step


def bench_one(topo, transport, n_target, pods, devs, iters):
    g_dev, delta, params = make_inputs(n_target, pods, devs)
    n_real = sum(int(x[0, 0].size) for x in jax.tree.leaves(g_dev))
    layout = None
    if transport == "fused_flat":
        layout = flatbuf.make_layout(g_dev, batch_dims=2)
        delta = flatbuf.flatten_tree(layout, delta, batch_dims=1,
                                     dtype=jnp.float32)
        params = flatbuf.flatten_tree(layout, params, batch_dims=1,
                                      dtype=jnp.float32)
    step = jax.jit(make_step(topo, transport, layout))
    lowered = step.lower(g_dev, delta, params)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    stats = hlo_analysis.analyze_hlo_text(hlo)

    out = jax.block_until_ready(step(g_dev, delta, params))   # warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jax.block_until_ready(step(g_dev, delta, params))
    dt = (time.perf_counter() - t0) / iters
    del out
    return {
        "transport": transport,
        "n_params": n_real,
        "pods": pods,
        "devices_per_pod": devs,
        "us_per_step": dt * 1e6,
        "hbm_bytes": stats["hbm_bytes"],
        "hbm_bytes_out": stats["hbm_bytes_out"],
        "collective_bytes": stats.get("collective_bytes_total", 0.0),
        "wire_bits_per_coord_uplink": signs.uplink_bits(
            "dc_hier_signsgd", n_real, 1) / n_real,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="1000000,8000000",
                    help="comma-separated param counts (paper range 1M-100M)")
    ap.add_argument("--devices", default="1x8,2x4",
                    help="comma-separated PxD logical device counts")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--out", default=str(
        pathlib.Path(__file__).resolve().parents[1]
        / "BENCH_transports.json"))
    args = ap.parse_args()
    compile_cache.enable()

    topo = single_device_topology()
    sizes = [int(float(s)) for s in args.sizes.split(",")]
    devices = [tuple(int(x) for x in d.split("x"))
               for d in args.devices.split(",")]

    rows, checks = [], []
    print("transport,n_params,pods,devices,us_per_step,hbm_bytes,"
          "hbm_bytes_out")
    for n in sizes:
        for pods, devs in devices:
            cell = {}
            for transport in TRANSPORTS:
                r = bench_one(topo, transport, n, pods, devs, args.iters)
                rows.append(r)
                cell[transport] = r
                print(f"{r['transport']},{r['n_params']},{r['pods']},"
                      f"{r['devices_per_pod']},{r['us_per_step']:.1f},"
                      f"{r['hbm_bytes']:.0f},{r['hbm_bytes_out']:.0f}")
            # acceptance: fused <= per-leaf ag_packed in HBM bytes per
            # step, and the flat-state path no worse than fused
            checks.append({
                "n_params": cell["fused"]["n_params"],
                "pods": pods, "devices_per_pod": devs,
                "fused_hbm_bytes": cell["fused"]["hbm_bytes"],
                "fused_flat_hbm_bytes": cell["fused_flat"]["hbm_bytes"],
                "ag_packed_hbm_bytes": cell["ag_packed"]["hbm_bytes"],
                "fused_le_ag_packed": (cell["fused"]["hbm_bytes"]
                                       <= cell["ag_packed"]["hbm_bytes"]),
                "fused_flat_le_ag_packed": (
                    cell["fused_flat"]["hbm_bytes"]
                    <= cell["ag_packed"]["hbm_bytes"]),
            })
    report = {
        "meta": {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "mu": MU, "rho": RHO, "iters": args.iters,
            "note": "DC local step: sign(g+rho*delta) -> vote -> update; "
                    "single physical device, logical [P, D] dims; "
                    "hbm/collective bytes from hlo_analysis on the "
                    "optimized HLO.",
        },
        "rows": rows,
        "hbm_check": checks,
        "all_fused_le_ag_packed": all(c["fused_le_ag_packed"]
                                      for c in checks),
        "all_fused_flat_le_ag_packed": all(c["fused_flat_le_ag_packed"]
                                           for c in checks),
    }
    out_path = pathlib.Path(args.out)
    out_path.write_text(json.dumps(report, indent=2) + "\n")
    print(f"wrote {out_path} "
          f"(all_fused_le_ag_packed={report['all_fused_le_ag_packed']})")


if __name__ == "__main__":
    main()
