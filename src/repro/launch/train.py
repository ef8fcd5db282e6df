"""End-to-end hierarchical training driver.

Wires together: config -> model -> DC-HierSignSGD step -> synthetic data
stream -> elastic membership -> async checkpointing -> failure recovery.
Runs the production configs on a real mesh, and the reduced smoke configs
on CPU (the integration tests and examples call ``run_training`` with a
small Topology).  ``main`` prints the platform it runs on: with no
accelerator JAX runs it on the CPU.

CLI (reduced-scale CPU run):
  PYTHONPATH=src python -m repro.launch.train --arch gemma3_1b --smoke \
      --steps 30 --t_e 5 --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import store
from repro.checkpoint.async_ckpt import AsyncSaver
from repro.core import clients as vclients
from repro.core import hier, schedule, votes
from repro.core.topology import Topology, single_device_topology
from repro.data import synthetic
from repro.launch import compile_cache
from repro.models import build
from repro.runtime import chaos as chaos_mod
from repro.runtime import elastic, failures


@dataclasses.dataclass
class RunCfg:
    steps: int = 50
    batch_per_device: int = 4
    seq_len: int = 128
    ckpt_dir: str | None = None
    ckpt_every: int = 20
    log_every: int = 5
    hetero: float = 1.0
    alpha_client: float | None = None
    edge_assign: str = "fixed"
    seed: int = 0


def run_training(cfg, topo: Topology, algo: hier.AlgoConfig, run: RunCfg,
                 fault_injector: failures.FaultInjector | None = None,
                 on_metrics: Callable[[int, dict], None] | None = None,
                 on_compiled: Callable[[Any, float], None] | None = None):
    """Returns (final_state, history).  Deterministic given seeds.

    ``history`` holds one dict per step: step, loss, live share and the
    step's wall seconds (the first includes compilation).
    ``on_compiled(compiled, seconds)``, when given, receives the step
    program compiled ahead of the first step (``jax.stages.Compiled``:
    its HLO text and memory analysis) and the seconds that took; the
    loop's dispatch reuses that executable (no second compile).
    """
    built = build.build_model(cfg, topo)
    init_fn, step_fn = hier.make_hier_step(topo, algo, built.bundle)
    jstep = jax.jit(step_fn, donate_argnums=(0,))

    params = built.init_params(jax.random.PRNGKey(run.seed))
    # init under jit: masters constrained to uneven model-sharded specs
    # (odd vocab/head extents on a TP mesh) only exist as jit-produced
    # arrays -- eager placement of uneven shardings is unsupported
    state = jax.jit(init_fn)(params, jax.random.PRNGKey(run.seed + 1))
    del params      # the state owns the masters; free the init tree

    stream = synthetic.make_stream(synthetic.LMStreamCfg(
        vocab=cfg.vocab, seq_len=run.seq_len,
        batch_per_device=run.batch_per_device, pods=topo.pods,
        devices_per_pod=topo.devices_per_pod, seed=run.seed,
        hetero=run.hetero, clients_per_device=algo.clients.count,
        alpha_client=run.alpha_client, edge_assign=run.edge_assign,
        frames=cfg.encoder_frames if cfg.family in ("encdec", "audio")
        else 0,
        frontend_dim=cfg.frontend_dim, n_patches=cfg.n_patches,
        d_model=cfg.d_model))

    # membership speaks the step's own vocabulary: with an active
    # ClientConfig the mask it emits is client-granular [P, D, K], and
    # every churn event is a VALUE change of fixed-shape arrays (no
    # retrace -- pinned by the parity matrix's zero-recompilation test)
    member = elastic.Membership(topo.pods, topo.devices_per_pod,
                                clients=algo.clients)
    detector = failures.FailureDetector()
    saver = AsyncSaver(run.ckpt_dir) if run.ckpt_dir else None

    # resume if a checkpoint exists
    start = 0
    if run.ckpt_dir:
        restored = store.restore_latest(run.ckpt_dir, state)
        if restored is not None:
            start, state = restored
            print(f"[train] resumed from step {start}")

    history = []
    step = start
    while step < run.steps:
        if fault_injector is not None:
            # events at step s apply BEFORE step s runs -- the same
            # semantics chaos.compile_schedule gives the parity tests
            chaos_mod.apply_events(member, fault_injector.at(step),
                                   now=float(step))
        arrays = member.weights()
        batch = {"train": stream(step)}
        weights = (jnp.asarray(arrays.edge_weights),
                   jnp.asarray(arrays.dev_weights), jnp.asarray(arrays.mask))
        if on_compiled is not None:
            t0 = time.perf_counter()
            compiled = jstep.lower(state, batch, *weights).compile()
            on_compiled(compiled, time.perf_counter() - t0)
            on_compiled = None
        t0 = time.perf_counter()
        state, metrics = jstep(state, batch, *weights)
        loss = float(metrics["loss"])
        seconds = time.perf_counter() - t0
        detector.record_step(seconds)
        if fault_injector is not None and fault_injector.nan_due(step):
            loss = float("nan")        # injected numeric blow-up

        if not detector.check_loss(loss):
            if saver:
                saver.wait()
            restored = (store.restore_latest(run.ckpt_dir, state)
                        if run.ckpt_dir else None)
            if restored is None or not detector.may_restore():
                raise RuntimeError(
                    f"non-finite loss at step {step}, no checkpoint")
            detector.record_restore()   # may_restore() is a pure query
            step, state = restored
            if fault_injector is not None:
                # membership replays from the schedule so the replayed
                # steps see the same arrays as the first pass
                member = chaos_mod.replay_membership(fault_injector,
                                                     member, step)
            print(f"[train] non-finite loss; restored step {step}")
            continue

        history.append({"step": step, "loss": loss,
                        "live": float(np.mean(member.live)),
                        "seconds": seconds})
        if on_metrics:
            on_metrics(step, metrics)
        if run.log_every and step % run.log_every == 0:
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"mu {float(metrics['mu']):.2e} "
                  f"live {member.live.mean():.2f}")
        step += 1
        if saver and step % run.ckpt_every == 0:
            saver.submit(step, state)
    if saver:
        saver.submit(step, state)
        saver.close()
    return state, history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3_1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--t_e", type=int, default=5)
    ap.add_argument("--method", default="dc_hier_signsgd",
                    choices=hier.ALL_METHODS)
    ap.add_argument("--transport", default="ag_packed",
                    choices=votes.SIGN_TRANSPORTS)
    ap.add_argument("--state_layout", default="tree",
                    choices=["tree", "flat"],
                    help="flat: master params live as the core.flatbuf "
                         "buffer (whole-model fused update)")
    ap.add_argument("--mu", type=float, default=1e-3)
    ap.add_argument("--rho", type=float, default=0.2)
    ap.add_argument("--cloud_period", type=int, default=2,
                    help="mtgc only: rounds between cloud-timescale eta "
                         "refreshes (the edge-timescale gamma refreshes "
                         "every round)")
    ap.add_argument("--cloud_overlap", default="sync",
                    choices=list(schedule.CLOUD_OVERLAP_MODES),
                    help="cloud sync schedule: sync = issue and commit "
                         "the cross-pod aggregate at the same round "
                         "boundary (the paper's barrier); overlap = "
                         "commit one boundary later, hiding the cloud "
                         "round-trip behind a round of local stepping "
                         "(staged agg_next slot; replicated regime only)")
    ap.add_argument("--clients_per_device", type=int, default=1,
                    help="K virtual clients per data slice (the device "
                         "batch is carved into K per-client shards)")
    ap.add_argument("--client_mode", default="merged",
                    choices=list(vclients.CLIENT_MODES),
                    help="merged: widen the voter axis to D*K; stream: "
                         "loop clients inside the step in O(model/32 + "
                         "tally) memory (bitwise identical)")
    ap.add_argument("--alpha_client", type=float, default=None,
                    help="intra-edge Dirichlet concentration: each "
                         "virtual client samples from its own tilted "
                         "unigram (None/inf = the exact legacy "
                         "within-edge IID stream)")
    ap.add_argument("--edge_assign", default="fixed",
                    choices=list(synthetic.cluster.EDGE_ASSIGN_MODES),
                    help="client->edge placement: fixed = topology "
                         "order; random = seeded balanced scatter; "
                         "clustered = deterministic signature "
                         "clustering (requires --clients_per_device>1 "
                         "and --alpha_client)")
    ap.add_argument("--participation", default="full",
                    choices=list(vclients.PARTICIPATION_MODES),
                    help="per-round client sampling (pinned to "
                         "(seed, round); bernoulli/fixed use --participation_rate)")
    ap.add_argument("--participation_rate", type=float, default=1.0)
    ap.add_argument("--participation_seed", type=int, default=0)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="run under a seeded fault schedule "
                         "(runtime.chaos.FaultInjector.seeded: client/"
                         "pod kills, heartbeat loss, straggler "
                         "demotion, recoveries -- same seed, same "
                         "schedule); nan-loss recovery needs --ckpt")
    ap.add_argument("--multi_pod", action="store_true",
                    help="use the production 2x16x16 mesh")
    args = ap.parse_args()
    compile_cache.enable()

    # surface the carve constraint and the scenario axes as clean CLI
    # errors instead of jit-time tracebacks (clustered assignment is
    # rejected here when the clients carve is inactive)
    try:
        vclients.validate_batch_carve(args.batch, args.clients_per_device,
                                      flag="clients_per_device")
        synthetic.validate_scenario(synthetic.LMStreamCfg(
            vocab=2, seq_len=args.seq, batch_per_device=args.batch,
            pods=1, devices_per_pod=1,
            clients_per_device=args.clients_per_device,
            alpha_client=args.alpha_client, edge_assign=args.edge_assign))
    except ValueError as e:
        ap.error(str(e))

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    # validate the schedule x regime combination up front: a clean CLI
    # error beats the make_hier_step ValueError's jit-time traceback
    if args.cloud_overlap == "overlap" and cfg.param_mode == "fsdp":
        ap.error(f"--cloud_overlap=overlap requires the replicated "
                 f"regime, but --arch {args.arch} uses param_mode='fsdp' "
                 f"(the staged in-flight aggregate is a whole-model "
                 f"master snapshot the FSDP lift never materializes)")
    if args.multi_pod:
        from repro.launch import mesh as mesh_mod
        topo = mesh_mod.make_topology(multi_pod=True)
    else:
        topo = single_device_topology()
    dev = topo.mesh.devices.flat[0]
    print(f"[train] {topo.mesh.size} x {dev.platform} ({dev.device_kind})")
    algo = hier.AlgoConfig(method=args.method, mu=args.mu, rho=args.rho,
                           cloud_period=args.cloud_period,
                           cloud_overlap=args.cloud_overlap,
                           t_e=args.t_e, transport=args.transport,
                           state_layout=args.state_layout,
                           clients=vclients.ClientConfig(
                               count=args.clients_per_device,
                               participation=args.participation,
                               rate=args.participation_rate,
                               seed=args.participation_seed,
                               mode=args.client_mode),
                           compute_dtype=jnp.float32 if args.smoke
                           else jnp.bfloat16)
    run = RunCfg(steps=args.steps, batch_per_device=args.batch,
                 seq_len=args.seq, ckpt_dir=args.ckpt,
                 alpha_client=args.alpha_client,
                 edge_assign=args.edge_assign)
    injector = None
    if args.chaos is not None:
        injector = chaos_mod.FaultInjector.seeded(
            args.chaos, args.steps, topo.pods, topo.devices_per_pod,
            algo.clients.count)
        print(f"[train] chaos seed {args.chaos}: "
              f"{len(injector.events)} scheduled events")
    _, history = run_training(cfg, topo, algo, run,
                              fault_injector=injector)
    print(f"[train] done: loss {history[0]['loss']:.4f} -> "
          f"{history[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
