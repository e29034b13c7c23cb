"""End-to-end R-FAST training driver (CPU-runnable at reduced scale).

Trains an LM with the R-FAST protocol over a selectable topology, with
checkpointing, in one of two execution regimes:

* **synchronous rounds** (default) — the production SPMD runtime
  (``core/runtime.py``): every round runs S1–S5 for all nodes, optional
  Bernoulli per-edge loss masks (``--loss-prob``).
* **fully asynchronous** (``--scenario <name>``) — the paper's actual
  regime: a :class:`~repro.core.scenario.NetworkScenario` (stragglers,
  latency, loss bursts, crash/recovery) is realized into a per-event
  trace, and the reduced LM trains through the wavefront simulator
  engine on the flat-parameter substrate (``core/paramvec.py``): the
  model pytree rides the engines as one ``(p,)`` lane per node, with
  per-event stale reads and send outcomes.  ``--steps N`` means N
  activations per node (K = N·nodes events).  Checkpoints hold the
  packed flat state and resume mid-schedule.

    PYTHONPATH=src python -m repro.launch.train \
        --arch rfast-100m --reduced --nodes 4 --steps 200 --topology binary_tree

    PYTHONPATH=src python -m repro.launch.train \
        --arch rfast-100m --reduced --nodes 4 --steps 200 --scenario straggler

``--impl pallas`` commits the protocol state through the fused
``kernels/rfast_update`` grid launch (compiled on TPU, its jnp
emulation twin off-TPU — see kernels/rfast_update/dispatch.py) in both
regimes; the default ``--impl jnp`` is the dense/scatter path.  Both are
the same protocol (core/protocol.py) over the same CommPlan.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, latest_step, save_checkpoint
from repro.core.paramvec import unravel
from repro import metrics as tracing
from repro.metrics import MetricsLogger, StepTimer
from repro.configs import ARCHS, get_config
from repro.core.protocol import IMPLS
from repro.core.runtime import edge_arrays, init_node_state, make_rfast_round
from repro.core.scenario import SCENARIOS, get_scenario
from repro.core.simulator import (run_epochs, run_rfast, run_sweep,
                                  zeros_state)
from repro.core.topology import get_topology
from repro.data.objectives import make_lm_problem
from repro.data.pipeline import LMShardConfig, node_batch
from repro.kernels.rfast_update import dispatch
from repro.launch.xla_env import configure_compile_cache, force_host_devices
from repro.models.transformer import init_params, loss_fn
from repro.optim.schedules import warmup_cosine


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rfast-100m", choices=ARCHS)
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer smoke variant (CI-scale)")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--topology", default="binary_tree")
    ap.add_argument("--gamma", type=float, default=3e-3)
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--loss-prob", type=float, default=0.0)
    ap.add_argument("--scenario", default="", metavar="NAME",
                    help="train asynchronously under a named "
                         f"NetworkScenario ({', '.join(sorted(SCENARIOS))}) "
                         "through the wavefront engine; default: "
                         "synchronous rounds")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="print the SCENARIOS registry (dynamic entries "
                         "marked) and exit")
    ap.add_argument("--impl", default="jnp", choices=IMPLS,
                    help="protocol backend: jnp (dense GSPMD mixing) or "
                         "pallas (fused update kernel)")
    ap.add_argument("--param-shards", type=int, default=1,
                    help="shard the flat parameter axis over this many "
                         "mesh devices (async regime only: routes through "
                         "the mesh-mapped run_sweep — DESIGN.md §13; on "
                         "CPU combine with --host-devices)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force this many XLA host-platform devices "
                         "before the backend initializes (the CPU dev "
                         "loop for --param-shards)")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--publish-dir", default="",
                    help="publish SERVING checkpoints (the unraveled "
                         "model pytree of the consensus average x̄, not "
                         "the packed protocol state) at every chunk "
                         "boundary through checkpoint/ckpt.py's atomic "
                         "npz+manifest protocol — the feed that "
                         "launch/serve.py polls and hot-swaps from")
    ap.add_argument("--metrics", default="",
                    help="JSONL metrics path; with --scenario the "
                         "engine's spans and counters also go to "
                         "<path stem>.trace.jsonl")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify-plans", action="store_true",
                    help="run the repro.analysis plan-invariant linter "
                         "over every compiled plan before training "
                         "(raises PlanInvariantError on any diagnostic)")
    args = ap.parse_args(argv)
    configure_compile_cache()
    if args.host_devices:
        force_host_devices(args.host_devices)

    if args.list_scenarios:
        for name in sorted(SCENARIOS):
            sc = get_scenario(name, 7)
            tag = "  [dynamic: joins/leaves/regional failures]" \
                if sc.dynamic else ""
            print(f"{name}{tag}")
        return {"mode": "list", "scenarios": sorted(SCENARIOS)}

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.publish_dir:
        if not args.scenario:
            ap.error("--publish-dir publishes the async consensus "
                     "average at chunk boundaries; the synchronous "
                     "rounds have no flat-parameter chunk hook (pass "
                     "--scenario)")
        if args.param_shards > 1:
            ap.error("--publish-dir rides the wavefront chunk callback, "
                     "which the mesh-mapped run_sweep path does not "
                     "expose; drop --param-shards or --publish-dir")
    if args.scenario:
        if args.loss_prob:
            ap.error("--loss-prob models loss in the synchronous rounds; "
                     "with --scenario the NetworkScenario owns the "
                     "loss/delay model")
        if args.momentum:
            ap.error("--momentum applies to the synchronous round engine "
                     "only; the event-level Algorithm 2 recursion has no "
                     "momentum term")
        if args.ckpt and get_scenario(args.scenario, args.nodes).dynamic:
            ap.error("--ckpt resume is not supported for dynamic "
                     "(membership) scenarios: the packed state layout "
                     "changes at every epoch boundary, so a mid-schedule "
                     "snapshot is not replayable")
        if args.param_shards > 1:
            if args.ckpt:
                ap.error("--param-shards trains through run_sweep(mesh="
                         "...), which has no mid-schedule resume; drop "
                         "--ckpt or --param-shards")
            if get_scenario(args.scenario, args.nodes).dynamic:
                ap.error("--param-shards is not supported for dynamic "
                         "(membership) scenarios yet")
        if not args.metrics:
            return _train_async(args, cfg)
        # the engine's spans and counters, written beside the metrics
        tracing.enable(True)
        try:
            return _train_async(args, cfg)
        finally:
            _write_record(args.metrics)
            tracing.enable(False)
            tracing.reset()
    if args.param_shards > 1:
        ap.error("--param-shards shards the wavefront engine's flat "
                 "parameter axis; the synchronous rounds already shard "
                 "the model pytree via GSPMD (pass --scenario for the "
                 "async regime)")
    return _train_sync(args, cfg)


def _write_record(path: str) -> None:
    """The program's spans and counts, one JSON object per line, in
    ``<path stem>.trace.jsonl``."""
    rec = tracing.record()
    with open(os.path.splitext(path)[0] + ".trace.jsonl", "w") as f:
        for kind in ("spans", "counts"):
            for r in rec[kind]:
                f.write(json.dumps(dict(kind=kind[:-1], **r)) + "\n")


class _EventRate:
    """Events per second between consecutive calls, from the engine's
    ``rfast.events`` counter (tracing is on whenever a logger is)."""

    def __init__(self):
        self.events, self.t = 0, time.perf_counter()

    def __call__(self) -> float:
        events, t = tracing.total("rfast.events"), time.perf_counter()
        rate = (events - self.events) / max(t - self.t, 1e-9)
        self.events, self.t = events, t
        return rate


# --------------------------------------------------------------------- #
# synchronous rounds (production SPMD runtime)
# --------------------------------------------------------------------- #
def _train_sync(args, cfg) -> dict:
    n = args.nodes
    topo = get_topology(args.topology, n)
    spec = edge_arrays(topo)
    shard_cfg = LMShardConfig(vocab=cfg.vocab,
                              batch_per_node=args.batch_per_node,
                              seq_len=args.seq, n_nodes=n, seed=args.seed)

    def grad_fn(params, batch, key):
        del key
        toks, labels = batch
        return jax.value_and_grad(
            lambda p: loss_fn(cfg, p, toks, labels))(params)

    def batches_at(step: int):
        toks, labels = zip(*(node_batch(shard_cfg, i, step)
                             for i in range(n)))
        return jnp.asarray(np.stack(toks)), jnp.asarray(np.stack(labels))

    gamma = warmup_cosine(args.gamma, warmup=max(1, args.steps // 20),
                          total=args.steps)
    robust = args.loss_prob > 0
    # donate=True: the protocol state (x/z/ρ/ρ̃ — 2·|params|·N + 2·E_pad
    # buffers) updates in place instead of double-buffering; the loop
    # below rebinds ``state`` every step and never replays an old one
    round_fn = make_rfast_round(
        spec, grad_fn, gamma=gamma, robust=robust,
        momentum=args.momentum, impl=args.impl, donate=True)

    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M nodes={n} "
          f"topo={topo.name} robust={robust} impl={args.impl}")

    state = init_node_state(spec, params, grad_fn, batches_at(0), key,
                            robust=robust, momentum=args.momentum)
    start = 0
    if args.ckpt and latest_step(args.ckpt) is not None:
        start = latest_step(args.ckpt)
        state = load_checkpoint(args.ckpt, state)
        print(f"resumed from step {start}")

    rng = np.random.default_rng(args.seed + 1)
    logger = MetricsLogger(args.metrics) if args.metrics else None
    timer = StepTimer()
    t0 = time.perf_counter()
    losses: list[float] = []
    for step in range(start, args.steps):
        masks = None
        if robust:
            masks = jnp.asarray(
                (rng.uniform(size=spec.e_pad) >= args.loss_prob),
                jnp.float32)
        keys = jax.random.split(jax.random.fold_in(key, step), n)
        state, metrics = round_fn(state, batches_at(step), keys, masks)
        timer.tick()
        if logger:
            logger.log(step + 1, loss=metrics["loss"],
                       sps=timer.steps_per_sec)
        if (step == start or (step + 1) % args.log_every == 0
                or step + 1 == args.steps):
            l = float(metrics["loss"])
            losses.append(l)
            dt = time.perf_counter() - t0
            print(f"step {step+1:5d} loss {l:.4f} "
                  f"({dt:.1f}s, {timer.steps_per_sec:.2f} it/s)", flush=True)
        if args.ckpt and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt, step + 1, state)
    if logger:
        logger.close()
    print("done")
    return {"mode": "sync", "losses": losses, "steps": args.steps}


# --------------------------------------------------------------------- #
# fully asynchronous (scenario trace through the wavefront engine)
# --------------------------------------------------------------------- #
def _train_async(args, cfg) -> dict:
    t0 = time.perf_counter()
    n = args.nodes
    topo = get_topology(args.topology, n)
    prob = make_lm_problem(cfg, n, batch_per_node=args.batch_per_node,
                           seq_len=args.seq, seed=args.seed)
    sc = get_scenario(args.scenario, n)
    K = args.steps * n
    if sc.dynamic:
        return _train_async_dynamic(args, cfg, prob, topo, sc, K)
    trace = sc.realize(topo, K, seed=args.seed)
    sched = trace.schedule
    # delivered fraction over *attempted* sends (the active agent's
    # out-edges per event), not over the all-False inactive rows
    outdeg = np.zeros((2, n))
    for g, edges in enumerate((topo.edges_W(), topo.edges_A())):
        for (j, _i) in edges:
            outdeg[g, j] += 1
    attempts = outdeg[:, sched.agent].sum()
    delivered = float((trace.send_ok_w.sum() + trace.send_ok_a.sum())
                      / max(1.0, attempts))
    # the commit's execution mode, resolved where the engines resolve it:
    # compiled on TPU, the jnp emulation twin elsewhere
    mode = dispatch.resolve_mode(None) if args.impl == "pallas" else "-"
    print(f"arch={cfg.name} p={prob.p} ({prob.spec.p_model} model) "
          f"nodes={n} topo={topo.name} scenario={args.scenario} "
          f"K={K} D={sched.D} T={sched.T} "
          f"send_ok={delivered:.2f} impl={args.impl} mode={mode}")

    mesh = replicated = None
    x0 = prob.x0_flat
    if args.param_shards > 1:
        # one lane, flat parameter axis sharded over `model`: the
        # p >= 100M path (DESIGN.md §13).  x0 is replicated, so no
        # device carries more of it than another.
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh(lanes=1, param_shards=args.param_shards)
        replicated = NamedSharding(mesh, PartitionSpec())
        x0 = jax.device_put(x0, replicated)
        print(f"mesh: 1x{args.param_shards} (lane x param shards) over "
              f"{len(jax.devices())} devices")
    # chunk (= eval/ckpt) boundaries: log_every activations per node
    eval_every = max(n, min(K, args.log_every * n))
    save_every_chunks = max(1, args.ckpt_every // max(1, args.log_every))

    state0 = None
    if args.ckpt and latest_step(args.ckpt) is not None:
        template = zeros_state(topo, prob.p, int(sched.D) + 2)
        state0 = load_checkpoint(args.ckpt, template)
        print(f"resumed from event {int(state0.k)}/{K}")

    logger = MetricsLogger(args.metrics) if args.metrics else None
    losses: list[float] = [float(prob.mean_loss(x0))]
    print(f"event {0:6d} loss {losses[0]:.4f} (init)", flush=True)
    rate = _EventRate()
    published: list[int] = []
    wall: list[float] = []           # seconds since start at each eval
    k0 = int(state0.k) if state0 is not None else 0

    def eval_and_log(x, t):
        # x: the (n, p) node iterates; the mean is reduced where x lives
        # (sharded over the mesh on the --param-shards path), and the
        # model then sees a replicated x̄: a sharded one makes XLA
        # partition the whole forward pass, a compile of minutes
        x_bar = x.mean(0)
        if replicated is not None:
            x_bar = jax.device_put(x_bar, replicated)
        losses.append(float(prob.mean_loss(x_bar)))
        ev = min(K, k0 + (len(losses) - 1) * eval_every)
        wall.append(time.perf_counter() - t0)
        print(f"event {ev:6d} loss {losses[-1]:.4f} "
              f"vtime {t:8.1f} ({wall[-1]:.1f}s)", flush=True)
        if logger:
            logger.log(ev, loss=losses[-1], events_per_s=rate())
        if args.publish_dir:
            # serving checkpoint: the consensus average x̄ unraveled back
            # to the model pytree — what launch/serve.py hot-swaps in
            save_checkpoint(args.publish_dir, ev,
                            unravel(prob.spec, x_bar))
            published.append(ev)
        return {"loss": losses[-1], "t": t}

    def chunk_cb(state, k):
        if k >= K or (k // eval_every) % save_every_chunks == 0:
            save_checkpoint(args.ckpt, k, state)

    if mesh is not None:
        # no chunk_cb/state0 hooks: --ckpt was rejected in main()
        states, _ = run_sweep(
            topo, [sched], prob, x0, args.gamma, seeds=[args.seed],
            eval_every=eval_every, eval_fn=eval_and_log, impl=args.impl,
            verify_plans=args.verify_plans, mesh=mesh)
        state = states[0]
    else:
        state, _ = run_rfast(
            topo, sched, prob, x0, args.gamma, seed=args.seed,
            eval_every=eval_every, eval_fn=eval_and_log, mode="wavefront",
            impl=args.impl, state0=state0,
            chunk_cb=chunk_cb if args.ckpt else None,
            verify_plans=args.verify_plans)
    if logger:
        logger.close()
    if len(losses) > 1:
        print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"over {K} events ({float(sched.times[-1]):.1f} vtime)")
    else:
        print("done (schedule already complete)")
    return {"mode": "async", "scenario": args.scenario,
            "losses": losses, "events": K, "published": published,
            "vtime": float(sched.times[-1]), "send_ok": delivered,
            "commit_mode": mode, "eval_wall": wall, "state": state}


# --------------------------------------------------------------------- #
# dynamic scenarios (membership epochs through run_epochs)
# --------------------------------------------------------------------- #
def _train_async_dynamic(args, cfg, prob, topo, sc, K) -> dict:
    """Train under a dynamic-membership scenario: the realized trace is
    partitioned into topology epochs (joins/leaves/regional failures,
    with root re-election when a common root enters a crash window) and
    run through :func:`run_epochs`, which migrates the packed state
    across every plan change.  ``--ckpt`` is rejected in :func:`main`:
    the packed layout changes at epoch boundaries, so a mid-schedule
    snapshot is not replayable."""
    n = args.nodes
    et = sc.realize_epochs(topo, K, seed=args.seed)
    print(f"arch={cfg.name} p={prob.p} ({prob.spec.p_model} model) "
          f"nodes={n} topo={topo.name} scenario={args.scenario} "
          f"K={K} epochs={len(et.epochs)} impl={args.impl}")
    for i, ep in enumerate(et.epochs):
        act = int(ep.topology.active_mask().sum())
        print(f"  epoch {i}: t0={ep.t0:7.1f} events {ep.k0}..{ep.k0+ep.K} "
              f"root={ep.root} active={act}/{n} graph={ep.topology.name}")

    x0 = prob.x0_flat
    eval_every = max(n, min(K, args.log_every * n))
    logger = MetricsLogger(args.metrics) if args.metrics else None
    t0 = time.perf_counter()
    losses: list[float] = [float(prob.mean_loss(x0))]
    print(f"event {0:6d} loss {losses[0]:.4f} (init)", flush=True)
    rate = _EventRate()

    vt = {"t": 0.0}

    def eval_and_log(x, t):
        l = float(prob.mean_loss(x.mean(0)))
        losses.append(l)
        vt["t"] = t
        return {"loss": l, "t": t}

    # run_epochs calls eval_fn then chunk_cb with the same global event
    # count, so the print lands here where k is known
    published: list[int] = []

    def chunk_cb(state, k):
        dt = time.perf_counter() - t0
        print(f"event {k:6d} loss {losses[-1]:.4f} vtime {vt['t']:8.1f} "
              f"({dt:.1f}s)", flush=True)
        if logger:
            logger.log(k, loss=losses[-1], events_per_s=rate())
        if args.publish_dir:
            save_checkpoint(args.publish_dir, k,
                            unravel(prob.spec, state.x.mean(0)))
            published.append(k)

    state, metrics = run_epochs(
        et, prob, x0, args.gamma,
        seed=args.seed, eval_every=eval_every, eval_fn=eval_and_log,
        impl=args.impl, chunk_cb=chunk_cb, verify_plans=args.verify_plans)
    if logger:
        logger.close()
    vtime = metrics[-1]["t"] if metrics else 0.0
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f} over {K} "
          f"events, {len(et.epochs)} epochs ({vtime:.1f} vtime)")
    return {"mode": "async-dynamic", "scenario": args.scenario,
            "losses": losses, "events": K, "epochs": len(et.epochs),
            "published": published, "vtime": float(vtime)}


if __name__ == "__main__":
    main()
