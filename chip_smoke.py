#!/usr/bin/env python3
"""Chip smoke test: DC-HierSignSGD training on TPU through ``run_training``.

Default (one chip): the jnp wire format is checked against numpy
(``pack_signs`` at gemma3-1b leaf shapes); then a model at its full
published width (random weights from ``--seed``) trains 2 rounds at
T_E = 2 -- so the round-boundary step runs twice -- in two phases:

  merged  K = 1 voter per device (kernels: sign_pack + vote_update);
  stream  K = 4 streamed virtual clients, Bernoulli participation 0.5
          (kernel: tally_acc, weighted vote),

each with ``--transport fused`` and again with ``--transport ag_packed``
at ``state_layout="flat"``.  The two transports must print the same
per-step losses digit for digit (the transports are bit-identical by
construction).  Batch, sequence length, steps and K are cut; widths
and depths are not.  The merged phase runs gemma3-1b (26 layers,
d 1152, vocab 262144); the stream phase runs xlstm-350m (24 blocks,
d 1024), because gemma3-1b's streamed step does not fit one v5e's HBM
(AOT compile for a described v5e: 17.8 GiB against 15.75 GiB).
A step that does not fit the chip fails the run.

``--chips 4`` runs only the paper's hierarchy on one four-chip host:
2 edges x 2 devices (pod x data), fused/flat against ag_packed/flat,
on gemma3-1b at full width and depth (each chip holds one replica).
It also requires a non-zero DC correction after the first round
boundary (the cloud tier ran) and Pallas kernels in the compiled step.

Every phase prints its kernel route, the ``tpu_custom_call`` count of
the compiled step, compile and step seconds, and the device's
``peak_bytes_in_use``.  The last line of a passing run is one JSON
object naming the device; any failed phase exits non-zero without it.
It needs a TPU: with no accelerator (e.g. ``JAX_PLATFORMS=cpu``) it
exits non-zero.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # one host with four chips
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
# one model per phase, at its published width and depth
MERGED_ARCH = "gemma3_1b"
STREAM_ARCH = "xlstm_350m"
HIER_ARCH = "gemma3_1b"
WIRE_SHAPES = ((1152,), (22, 1152), (4, 288), (1152, 6912))


class SmokeFailure(Exception):
    pass


def _log(msg: str) -> None:
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def _peak_bytes(jax) -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def _run_cell(jax, name, cfg, topo, algo, run):
    """One training run; returns (losses, final state)."""
    from repro.kernels import ops as kops
    from repro.launch import train

    route = (f"fused kernel route {kops.fused_kernel_mode()}"
             if algo.transport == "fused" else "jnp per-leaf transport")
    _log(f"{name}: transport={algo.transport} layout={algo.state_layout} "
         f"K={algo.clients.count} mode={algo.clients.mode} "
         f"participation={algo.clients.participation} route={route}")
    info = {}

    def on_compiled(compiled, seconds):
        text = compiled.as_text()
        info["custom_calls"] = text.count(
            'custom_call_target="tpu_custom_call"')
        info["compile_s"] = seconds
        ma = compiled.memory_analysis()
        _log(f"{name}: compiled in {seconds:.1f}s; tpu_custom_call="
             f"{info['custom_calls']}; argument "
             f"{ma.argument_size_in_bytes} B, output "
             f"{ma.output_size_in_bytes} B, alias {ma.alias_size_in_bytes}"
             f" B, temp {ma.temp_size_in_bytes} B")

    state, hist = train.run_training(cfg, topo, algo, run,
                                     on_compiled=on_compiled)
    losses = [h["loss"] for h in hist]
    secs = [h["seconds"] for h in hist]
    _log(f"{name}: losses {[repr(x) for x in losses]}")
    _log(f"{name}: step seconds {[round(s, 4) for s in secs]} (first "
         f"includes tracing; steady median "
         f"{statistics.median(secs[1:]):.4f}s); peak_bytes_in_use "
         f"{_peak_bytes(jax)} (process peak so far)")
    if algo.transport == "fused" and info.get("custom_calls", 0) <= 0:
        raise SmokeFailure(f"{name}: no tpu_custom_call in the fused step")
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise SmokeFailure(f"{name}: non-finite loss {losses}")
    return losses, state


def _compare(name, runs):
    """Fused and ag_packed losses must agree digit for digit."""
    (ta, la), (tb, lb) = runs
    if la != lb:
        raise SmokeFailure(f"{name}: {ta} and {tb} losses differ:\n"
                           f"  {ta}: {[repr(x) for x in la]}\n"
                           f"  {tb}: {[repr(x) for x in lb]}")
    _log(f"{name}: {ta} == {tb} on all {len(la)} losses")


def _phase(jax, name, cfg, topo, algo, run, check_state=None):
    """Train ``cfg`` with both transports and compare their losses;
    returns the model's description.  Any error fails the phase."""
    _log(f"{name}: {cfg.name}: {cfg.n_layers} layers, d {cfg.d_model}, "
         f"vocab {cfg.vocab}")
    runs = []
    for transport in ("fused", "ag_packed"):
        a = dataclasses.replace(algo, transport=transport)
        losses, state = _run_cell(jax, f"{name}/{transport}", cfg, topo, a,
                                  run)
        if check_state is not None:
            check_state(f"{name}/{transport}", state)
        runs.append((transport, losses))
        del state                      # free the device state between runs
        gc.collect()
        jax.clear_caches()
    _compare(name, runs)
    return f"{cfg.name} ({cfg.n_layers} layers)"


def _wire_check(jax, seed):
    """The jnp transports' wire format on the chip: ``pack_signs`` of
    the signs of random floats equals a numpy pack, word for word, at
    gemma3-1b leaf shapes (norm scales, q/k norms, an MLP matrix).  The
    TPU compiler once set bits 16-22 wrong for the small ones only, so
    both transports' losses cannot vouch for it alone."""
    import numpy as np
    from repro.core import signs

    rng = np.random.default_rng(seed)
    pack = jax.jit(lambda u: signs.pack_signs(signs.sgn(u)))
    for shape in WIRE_SHAPES:
        u = rng.standard_normal(shape, dtype=np.float32)
        bits = (u >= 0).astype(np.uint64).reshape(shape[:-1] + (-1, 32))
        want = (bits << np.arange(32, dtype=np.uint64)).sum(-1)
        bad = int(np.sum(np.asarray(pack(u)) != want))
        if bad:
            raise SmokeFailure(f"wire: pack_signs wrong in {bad} of "
                               f"{want.size} words of a {shape} leaf")
    _log(f"wire: pack_signs equals the numpy pack at {WIRE_SHAPES}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: {SRC}/repro not found -- run this script from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {platform!r} "
              f"devices; no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1

    import jax.numpy as jnp
    from repro import configs
    from repro.core import clients as vclients, hier
    from repro.core.topology import single_device_topology
    from repro.launch import compile_cache, train
    from repro.launch.mesh import make_host_topology

    cache = compile_cache.enable()
    _log(f"{len(devices)} x {platform} ({devices[0].device_kind}); "
         f"compile cache {cache}")
    algo = hier.AlgoConfig(method="dc_hier_signsgd", mu=1e-3, rho=0.2,
                           t_e=2, state_layout="flat",
                           compute_dtype=jnp.bfloat16)
    t_start = time.perf_counter()
    ran = {}
    try:
        if args.chips == 4:
            topo = make_host_topology(pods=2, data=2, model=1)
            run = train.RunCfg(steps=4, batch_per_device=1, seq_len=256,
                               log_every=1, seed=args.seed)

            def check_delta(name, state):
                # the fresh anchor delta of boundary 0 is staged and
                # becomes the active correction at boundary 2
                d = float(jnp.max(jnp.abs(
                    state.delta.buf.astype(jnp.float32))))
                _log(f"{name}: max |DC delta| after 2 boundaries = {d!r}")
                if not d > 0.0:
                    raise SmokeFailure(f"{name}: DC delta is zero: the "
                                       f"cloud tier did not run")

            ran["hier2x2"] = _phase(jax, "hier2x2",
                                    configs.get_config(HIER_ARCH), topo,
                                    algo, run, check_delta)
        else:
            _wire_check(jax, args.seed)
            topo = single_device_topology()
            merged = train.RunCfg(steps=4, batch_per_device=1, seq_len=256,
                                  log_every=1, seed=args.seed)
            ran["merged"] = _phase(jax, "merged",
                                   configs.get_config(MERGED_ARCH), topo,
                                   algo, merged)
            stream_algo = dataclasses.replace(
                algo, clients=vclients.ClientConfig(
                    count=4, participation="bernoulli", rate=0.5,
                    mode="stream"))
            stream = dataclasses.replace(merged, batch_per_device=4,
                                         seq_len=128)
            ran["stream"] = _phase(jax, "stream",
                                   configs.get_config(STREAM_ARCH), topo,
                                   stream_algo, stream)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _log(f"all phases passed in {time.perf_counter() - t_start:.1f}s: "
         f"{ran}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
