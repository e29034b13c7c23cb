"""Shared benchmark harness: paper §VI logistic-regression setup at
CPU-friendly scale, virtual-time accounting for speed comparisons, and
the suite-wide timing utilities (``perf_counter`` based, warmup separated
from measurement, median-of-k reporting)."""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (generate_schedule, get_topology, realize_batch,
                        run_rfast, run_sweep)
from repro.data import make_logistic_problem


# --------------------------------------------------------------------- #
# timing
# --------------------------------------------------------------------- #
def measure_us(fn, *args, warmup: int = 1, reps: int = 5, **kw) -> float:
    """Median wall time per call in µs.

    ``warmup`` calls run first (compile + caches) and are NOT measured;
    each of the ``reps`` measured calls is blocked on, and the median is
    reported so a stray scheduler hiccup cannot skew the row.
    """
    for _ in range(max(1, warmup)):
        jax.block_until_ready(fn(*args, **kw))
    ts = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6


def measure_us_paired(fns: dict, *args, warmup: int = 1, reps: int = 5,
                      **kw) -> dict:
    """Median wall time per call in µs for SEVERAL callables, measured in
    interleaved rounds (one call of each per round, same arguments).

    Host speed drifts between measurement windows (turbo/thermal state,
    allocator pressure from earlier suites) — timing impl A's reps and
    then impl B's puts the drift entirely on one side and corrupts the
    A/B *ratio* the committed rows gate on.  Interleaving lands every
    drift regime on every callable equally, so ratios stay honest even
    when absolute numbers move.

    Every timed call starts COLD: the callables here share input
    arrays, so whichever one runs second finds them warm in LLC — a
    systematic bias worth 2x+ on shared-cache hosts, and no ordering
    scheme fixes it (mixed warm/cold samples are bimodal, so the
    median jumps regimes between runs).  A 64 MB host-memory sweep
    before each timed call evicts the shared state instead, making
    every sample the same (cold) measurement."""
    scrub = np.zeros(1 << 23, dtype=np.float64)          # 64 MB
    for fn in fns.values():
        for _ in range(max(1, warmup)):
            jax.block_until_ready(fn(*args, **kw))
    ts: dict = {k: [] for k in fns}
    for _ in range(max(1, reps)):
        for k, fn in fns.items():
            scrub += 1.0                                 # LLC eviction
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args, **kw))
            ts[k].append(time.perf_counter() - t0)
    return {k: float(np.median(v)) * 1e6 for k, v in ts.items()}


@contextmanager
def stopwatch():
    """``with stopwatch() as sw: ...`` — ``sw['s']`` holds elapsed seconds
    (``perf_counter``; for one-shot sections where median-of-k is not
    affordable, e.g. whole training runs)."""
    box: dict = {}
    t0 = time.perf_counter()
    try:
        yield box
    finally:
        box["s"] = time.perf_counter() - t0


def logistic_setup(n: int, *, het: bool = True, d: int = 64, m: int = 2800,
                   batch: int = 16, seed: int = 0):
    prob = make_logistic_problem(n, m=m, d=d, batch=batch,
                                 heterogeneous=het, seed=seed)
    return prob


def time_to_loss(metrics: list[dict], target: float) -> float:
    """First virtual time at which mean loss <= target (inf if never)."""
    for m in metrics:
        if m["loss"] <= target:
            return m["t"]
    return float("inf")


def time_to_sustained_loss(metrics: list[dict], target: float) -> float:
    """First virtual time from which mean loss STAYS <= target through
    the end of the run (inf if the last eval is still above).

    The dynamic-membership rows need this instead of the first-crossing
    metric: a crash/departure mid-run makes the trajectory non-monotone
    (a pre-crash dip can touch the target, then the disruption pushes
    the loss back up), and a frozen-plan run must not get credit for a
    transient it cannot hold."""
    t = float("inf")
    for m in metrics:
        if m["loss"] <= target:
            if not np.isfinite(t):
                t = m["t"]
        else:
            t = float("inf")
    return t


def eval_fn_for(prob):
    """Uniform eval hook: every algorithm hands over its *iterate* —
    an (n, p) per-node stack or a (p,) single model."""
    def eval_fn(x, t):
        xb = jnp.asarray(x)
        if xb.ndim == 2:
            xb = xb.mean(0)
        return {"loss": float(prob.mean_loss(xb)),
                "acc": float(prob.accuracy(xb)), "t": t}
    return eval_fn


def _x0_for(prob):
    """Per-node start iterate: the provider's ``x0_flat`` when it has one
    (real models start at their init), else the zero vector (the convex
    objectives)."""
    x0_flat = getattr(prob, "x0_flat", None)
    if x0_flat is None:
        return jnp.zeros((prob.n, prob.p), jnp.float32)
    return jnp.tile(jnp.asarray(x0_flat, jnp.float32)[None], (prob.n, 1))


def run_rfast_problem(prob, topo_name: str, K: int, *, gamma=5e-3,
                      scenario=None, compute_time=None, loss_prob=0.0,
                      seed=0, eval_every=500, mode="wavefront"):
    """Run R-FAST on any GradProvider (LogisticProblem, LMProblem, ...);
    x0 comes from :func:`_x0_for`."""
    n = prob.n
    topo = get_topology(topo_name, n)
    if scenario is not None:
        if compute_time is not None or loss_prob != 0.0:
            raise ValueError("pass either scenario= or the legacy "
                             "compute_time/loss_prob kwargs, not both")
        sched = generate_schedule(topo, K, scenario=scenario, seed=seed)
    else:
        sched = generate_schedule(topo, K, compute_time=compute_time,
                                  loss_prob=loss_prob, latency=0.3, seed=seed)
    x0 = _x0_for(prob)
    with stopwatch() as sw:
        state, metrics = run_rfast(topo, sched, prob, x0, gamma,
                                   eval_every=eval_every,
                                   eval_fn=eval_fn_for(prob), seed=seed,
                                   mode=mode)
        jax.block_until_ready(state.x)
    return state, metrics, sw["s"]


# kept name: the logistic suites predate the substrate-generic runner
run_rfast_logistic = run_rfast_problem


def run_sweep_problem(prob, topo_name: str, K: int, *, scenario,
                      gamma=5e-3, seeds=(0, 1, 2), eval_every=500,
                      impl="jnp"):
    """Run a fleet of seeds of one (problem, topology, scenario) through
    the sweep engine: one compiled program, one seed per lane.

    Returns ``(states, metrics_lanes, wall_s)`` with one final state and
    one metrics list per seed — feed ``metrics_lanes`` through
    :func:`time_to_loss` per lane and report the median."""
    n = prob.n
    topo = get_topology(topo_name, n)
    traces = realize_batch(topo, K, scenario=scenario, seeds=seeds)
    scheds = [t.schedule for t in traces]
    x0 = _x0_for(prob)
    with stopwatch() as sw:
        states, metrics = run_sweep(topo, scheds, prob, x0, gamma,
                                    seeds=list(seeds),
                                    eval_every=eval_every,
                                    eval_fn=eval_fn_for(prob), impl=impl)
        jax.block_until_ready(states[-1].x)
    return states, metrics, sw["s"]


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
