"""One run of an R-FAST training cell: set-up, the measured window, the
check against the plain reference, and the result line.

The window drives the engine entries that ``launch/train.py --scenario``
calls, with the cell's sizes: ``run_rfast(mode="wavefront",
impl="pallas")`` on one chip, and ``run_sweep(mesh=make_sweep_mesh(lanes=1,
param_shards=chips), impl="pallas")`` over several.  Their ``eval_fn``
hook reports the loss of the mean iterate at every chunk boundary, as
the training CLI's does.  The first chunk is the warm-up, run untimed
through the same engine call as the window; at its boundary the run
keeps what the check compares (the node iterates and the loss).  Whole
chunks are then timed until ``seconds`` have passed, and the window is
closed from inside ``eval_fn``.

Afterwards, with the engine's state freed, the plain reference
(``rfast_ref`` over the configuration's own reference model) replays
the warm-up chunk's events from the same weights, schedule and seed, and
each compared number is held against its limit in the cell's file.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import math
import statistics
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import counters, rfast_ref, spec, trace_reduce
from . import traffic as traffic_mod

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class WindowClosed(Exception):
    """Raised from ``eval_fn`` to leave the engine at a chunk boundary."""


@dataclasses.dataclass
class Context:
    """What a metric reader (``metrics/<name>.py``) may read."""

    cell: dict
    chips: int
    device_kind: str
    peaks: dict
    counts: dict                 # shape-derived counters (counters.py)
    setup_s: float
    compiles: list               # (perf_counter at the event, seconds)
    t_window: tuple              # (start, end) perf_counter
    chunk_ends: list             # perf_counter after each window eval
    eval_s: list                 # eval_fn body durations in the window
    events: int                  # events committed in the window
    tokens: int                  # events x batch x seq
    peak_bytes: list             # peak_bytes_in_use per device
    trace: object = None         # trace_reduce.Trace of a --trace 1 run

    @property
    def window_s(self) -> float:
        return self.t_window[1] - self.t_window[0]


def _model_config(cfg: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], n_layers=cfg["n_layers"], d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], n_kv_heads=cfg["n_kv_heads"],
        d_ff=cfg["d_ff"], vocab=cfg["vocab"], mixer="attn", mlp="swiglu",
        norm=cfg["norm"], tie_embeddings=cfg["tie_embeddings"],
        rope_theta=cfg["rope_theta"])


def _eval_batch(cfg: dict, cell: dict, seed: int):
    """Held-out batch of the loss the eval reports, from the seed."""
    probs = np.arange(1, cfg["vocab"] + 1, dtype=np.float64) ** -cell["zipf"]
    rng = np.random.default_rng([seed, 0xE7A1])
    toks = rng.choice(cfg["vocab"], size=(cell["eval_batch"], cell["seq"] + 1),
                      p=probs / probs.sum())
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def compare(prog_x, ref_x, x0, prog_loss, ref_loss, leaves) -> dict:
    """Numbers the check may hold against limits.

    Per node and parameter leaf: the change from x0 in the program
    (``dp``) and in the reference (``dr``).  Leaves the reference moves
    by less than a thousandth of its median leaf are left out (they move
    by round-off alone).  Each gap is taken against the reference's norm
    of that leaf or of the median leaf, whichever is larger, and the
    worst leaf gives the number."""
    gaps, diffs = [], []
    for i in range(prog_x.shape[0]):
        rows = []
        for name, off, size in leaves:
            dp = prog_x[i, off:off + size] - x0[off:off + size]
            dr = ref_x[i][off:off + size] - x0[off:off + size]
            rows.append((name, float(np.linalg.norm(dp)),
                         float(np.linalg.norm(dr)),
                         float(np.linalg.norm(dp - dr))))
        med = statistics.median(r for _, _, r, _ in rows)
        for name, p_norm, r_norm, d_norm in rows:
            if r_norm < 1e-3 * med:
                continue
            base = max(r_norm, med)
            gaps.append(abs(p_norm - r_norm) / base)
            diffs.append((d_norm / base, f"{i}:{name}"))
    return {"loss_gap": abs(prog_loss - ref_loss),
            "dx_norm_gap": max(gaps), "dx_diff": max(diffs)[0],
            "dx_norm_gap_med": statistics.median(gaps),
            "dx_diff_med": statistics.median(d for d, _ in diffs),
            "worst_leaf": max(diffs)[1],
            "loss": prog_loss, "ref_loss": ref_loss}


_COMPILES: list = []
_LISTENING: list = []


def _compile_log(jax) -> list:
    """``(perf_counter, seconds)`` of every backend compile in the process
    (the listener is registered once)."""
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **_: _COMPILES.append(
                (time.perf_counter(), secs)) if name == COMPILE_EVENT
            else None)
        _LISTENING.append(True)
    return _COMPILES


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, root: Path = spec.BENCH_DIR,
        leaf_dtype: str = "float32") -> dict:
    """One run; returns the result dict (see ``run.py``).  ``root`` holds
    the cell's files; ``seconds <= 0`` closes the window at once (the
    check alone, for ``calibrate.py``).  ``leaf_dtype="bfloat16"`` runs
    the program's own lower-precision path (parameter leaves, and so the
    forward and backward passes, in bfloat16 over the float32 state): the
    check's control."""
    import jax
    import jax.numpy as jnp
    from repro.launch.xla_env import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = _compile_log(jax)
    compiles.clear()

    from repro.core.schedule import Schedule
    from repro.core.simulator import run_rfast, run_sweep
    from repro.core.paramvec import make_ravel_spec
    from repro.core.topology import Topology
    from repro.data.objectives import LMProblem
    from repro.data.pipeline import LMShardConfig
    from repro.models.transformer import init_params

    setup_ann = jax.profiler.TraceAnnotation("bench.setup")
    setup_ann.__enter__()
    cell = spec.load_cell(cell_name, root)
    cfg = cell["config"]
    chips, n = cell["chips"], cell["nodes"]
    devices = jax.devices()[:chips]
    seed32 = seed % 2 ** 32
    ref = importlib.import_module(f"bench.configs.{cfg['reference']}")

    W, A, sched, toks, labels = _inputs(cell, seed)
    K = cell["events"]
    eval_every = cell["eval_every_steps"] * n
    topo = Topology(cell["topology"], n, W, A)
    prog_sched = Schedule(sched.agent, sched.stamp_v, sched.stamp_rho,
                          sched.times, sched.D, sched.T)

    # the problem: the program's LM objective, weights made by the
    # benchmark on the device(s) in one call from the seed
    mcfg = _model_config(cfg)
    shapes = jax.eval_shape(
        lambda k: init_params(mcfg, k, jnp.dtype(leaf_dtype)),
        jax.random.PRNGKey(0))
    rspec = make_ravel_spec(shapes, pad_to=ref.PAD_TO)
    want = [s for _, s in ref.layout(cfg)]
    if list(rspec.shapes) != want or rspec.p != ref.flat_width(cfg):
        raise RuntimeError("the program's parameter layout differs from the "
                           f"reference's: {rspec.shapes} vs {want}")
    prob = LMProblem(
        cfg=mcfg, spec=rspec, params0=None,
        shard=LMShardConfig(vocab=cfg["vocab"],
                            batch_per_node=cell["batch_per_node"],
                            seq_len=cell["seq"], n_nodes=n, seed=seed32,
                            zipf=cell["zipf"]),
        eval_tokens=jnp.asarray(toks), eval_labels=jnp.asarray(labels))
    mesh = replicated = None
    if cell["engine"] == "mesh":
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import make_sweep_mesh
        mesh = make_sweep_mesh(lanes=1, param_shards=chips,
                               devices=devices)
        replicated = NamedSharding(mesh, PartitionSpec())
    init = jax.jit(lambda key: ref.init_flat(cfg, key),
                   out_shardings=replicated)
    x0 = init(jax.random.PRNGKey(seed32))
    if mesh is None:
        # the engine copies x0 into its state; held on the host, it
        # leaves the chip's memory to the state and the engine's programs
        x0 = np.asarray(x0)

    # the window, driven from the engine's eval hook
    st = {"first": None, "t_ws": None, "t_we": None, "ends": [],
          "evals": [], "failed": 0, "chunk": None, "trace_dir": None}

    def eval_fn(x, t):
        if st["chunk"] is not None:
            st["chunk"].__exit__(None, None, None)
        with jax.profiler.TraceAnnotation("bench.wait"):
            x.block_until_ready()      # the chunk's waves and x's take
        with jax.profiler.TraceAnnotation("bench.eval"):
            t_in = time.perf_counter()
            x_bar = x.mean(0)
            if replicated is not None:
                x_bar = jax.device_put(x_bar, replicated)
            loss = float(prob.mean_loss(x_bar))
            t_out = time.perf_counter()
        if st["first"] is None:
            # end of the warm-up chunk: keep what the check compares
            st["first"] = {"loss": loss, "x": np.asarray(x)}
            if seconds <= 0:
                raise WindowClosed
            if trace:
                st["trace_dir"] = tempfile.mkdtemp(prefix="bench_trace_")
                trace_reduce.start(st["trace_dir"])
            setup_ann.__exit__(None, None, None)
            st["t_ws"] = time.perf_counter()
        else:
            st["evals"].append(t_out - t_in)
            st["ends"].append(t_out)
            if not math.isfinite(loss):
                st["failed"] += eval_every
            if t_out - st["t_ws"] >= seconds:
                st["t_we"] = t_out
                raise WindowClosed
        st["chunk"] = jax.profiler.TraceAnnotation("bench.chunk")
        st["chunk"].__enter__()
        return {"loss": loss, "t": t}

    gamma = cell["gamma"]
    try:
        if mesh is None:
            run_rfast(topo, prog_sched, prob, x0, gamma, seed=seed32,
                      eval_every=eval_every, eval_fn=eval_fn,
                      mode="wavefront", impl="pallas")
        else:
            run_sweep(topo, [prog_sched], prob, x0, gamma, seeds=[seed32],
                      eval_every=eval_every, eval_fn=eval_fn, impl="pallas",
                      mesh=mesh)
        raise RuntimeError(f"the window outran the schedule's {K} events; "
                           "the cell needs more events")
    except WindowClosed:
        pass
    if trace and st["trace_dir"]:
        jax.profiler.stop_trace()
    on_chip = devices[0].platform != "cpu"
    peak = [int(d.memory_stats()["peak_bytes_in_use"]) if on_chip else 0
            for d in devices]

    n_chunks = len(st["ends"])
    t_ws = st["t_ws"] or time.perf_counter()
    events = n_chunks * eval_every
    if n_chunks:
        gaps = np.diff([t_ws] + st["ends"])
        print("chunk seconds: " + " ".join(f"{g:.4f}" for g in gaps),
              file=sys.stderr, flush=True)
    ka, ko = counters.degrees(A)
    e_a = max(1, counters.n_edges(A))
    width = ref.flat_width(cfg)
    kind = devices[0].device_kind
    counts = {
        "flops_per_token": counters.flops_per_token(cfg, cell["seq"]),
        "commit_bytes_per_event_per_device":
            counters.commit_bytes(width // chips, ka, ko),
        "state_bytes_per_device":
            counters.state_rows(n, e_a, sched.D + 2) * width * 4 // chips,
    }
    ctx = Context(
        cell=cell, chips=chips, device_kind=kind,
        peaks=spec.peaks(kind) if on_chip else {},
        counts=counts, setup_s=t_ws - t_start, compiles=list(compiles),
        t_window=(t_ws, st["t_we"] or t_ws), chunk_ends=st["ends"],
        eval_s=st["evals"], events=events,
        tokens=events * cell["batch_per_node"] * cell["seq"],
        peak_bytes=peak)
    if trace and st["trace_dir"]:
        ctx.trace = trace_reduce.load(st["trace_dir"])
        trace_reduce.remove(st["trace_dir"])

    first, failed = st["first"], st["failed"]
    del st, eval_fn, prob
    gc.collect()
    t_ref = time.perf_counter()
    ref_x, ref_loss = _reference(cell, ref, sched, W, A, x0, seed32, toks,
                                 labels, devices)
    readings = compare(first["x"], ref_x, np.asarray(x0), first["loss"],
                       ref_loss, ref.segments(cfg))
    print(f"reference: {time.perf_counter() - t_ref:.2f} s for "
          f"{eval_every} events", file=sys.stderr, flush=True)
    return _result(ctx, readings, failed, trace)


def _inputs(cell: dict, seed: int):
    """The traffic of one run, from the seed: the graph's weights, the
    event schedule and the eval batch."""
    W, A = traffic_mod.TOPOLOGIES[cell["topology"]](cell["nodes"])
    sched = traffic_mod.realize(cell["traffic"], W, A, cell["events"], seed)
    toks, labels = _eval_batch(cell["config"], cell, seed)
    return W, A, sched, toks, labels


def _reference(cell, ref, sched, W, A, x0, seed32, toks, labels, devices):
    """The plain reference over the warm-up chunk's events: the final
    node iterates (host, float32) and the loss of their mean on the eval
    batch."""
    import jax
    cfg, n, K = cell["config"], cell["nodes"], cell["events"]
    k = cell["eval_every_steps"] * n
    cdf = rfast_ref.zipf_cdf(cfg["vocab"], cell["zipf"])
    ev_keys, init_keys = rfast_ref.program_keys(seed32, K, n)

    def batch(key, node):
        dev = next(iter(key.devices()))
        return rfast_ref.sample_batch(jax.device_put(cdf, dev), key, node,
                                      B=cell["batch_per_node"], S=cell["seq"])

    x = rfast_ref.train(
        loss_fn=lambda flat, t, lbl: ref.loss(cfg, flat, t, lbl), W=W, A=A,
        agent=sched.agent[:k], stamp_v=sched.stamp_v[:k],
        stamp_rho=sched.stamp_rho[:k],
        x0=np.asarray(x0), gamma=cell["gamma"],
        event_keys=ev_keys, init_keys=init_keys, batch=batch,
        devices=devices)
    x = [np.asarray(r, np.float32) for r in x]
    x_bar = sum(x[1:], x[0]) / np.float32(len(x))
    loss = jax.jit(lambda f, t, lbl: ref.loss(cfg, f, t, lbl))(
        jax.device_put(x_bar, devices[0]), jax.device_put(toks, devices[0]),
        jax.device_put(labels, devices[0]))
    return x, float(loss)


def _checks(cell: dict, readings, failed: int):
    """Each compared number beside its limit, and whether all hold."""
    if readings is None:
        return {}, False
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in cell.get("limits", {}).items()}
    correct = failed == 0 and bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return checks, correct


def _reader(name: str):
    path = spec.BENCH_DIR / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(cell_name: str, trace: bool, bench: dict) -> list:
    """``(name, unit)`` of the metrics BENCHMARK.json gives the cell:
    end-to-end ones with ``trace`` off, per-layer ones with it on."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [(m["name"], m["unit"]) for m in group
            if cell_name in m.get("workloads", [cell_name])]


def _result(ctx, readings, failed, trace) -> dict:
    metrics = {}
    for name, unit in cell_metrics(ctx.cell["name"], trace,
                                   spec.benchmark_json()):
        value = _reader(name)(ctx)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": unit}
    device = {"platform": "tpu" if ctx.peaks else "cpu",
              "kind": ctx.device_kind, "count": ctx.chips,
              "memory_peak_bytes": max(ctx.peak_bytes)}
    out = {"correct": False, "attempted": ctx.events, "failed": failed,
           "metrics": metrics, "device": device}
    if ctx.trace is not None:
        device["busy_s"] = sum(ctx.trace.busy_s()) / len(ctx.trace.ops)
        device["window_s"] = ctx.trace.window_s
        out["breakdown"] = {"device_ops": ctx.trace.top_ops(),
                            "idle_gaps": ctx.trace.idle_gaps()}
    checks, out["correct"] = _checks(ctx.cell, readings, failed)
    out["readings"] = readings
    out["checks"] = checks
    return out
