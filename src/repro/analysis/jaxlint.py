"""Jaxpr auditing of the traced engines (RF201–RF206).

The plan linter rejects bad *inputs*; this pass rejects bad *programs*:
it walks the jaxprs that :func:`~repro.core.simulator.rfast_scan`,
:func:`~repro.core.simulator.rfast_wavefront_scan`,
:func:`~repro.core.simulator.rfast_sweep_scan` (the ``run_epochs``
body) and the :func:`~repro.kernels.rfast_update.grid.commit_grid`
call site actually trace to, plus the runtime contracts tracing cannot
see (donation aliasing, dispatch-cache steady state).

Everything here is trace-only: nothing is compiled or executed except
:func:`audit_dispatch`, which replays a caller-provided thunk against
the dispatch counters.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from .diagnostics import Diagnostic

__all__ = ["iter_eqns", "audit_jaxpr", "audit_donation",
           "audit_dispatch", "audit_serve_cache",
           "audit_mesh_collectives", "audit_engines"]

# host round-trip primitives (RF201) and loop primitives they must not
# appear inside
_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "python_callback",
    "callback", "host_callback_call", "outside_call"})
_LOOP_PRIMS = frozenset({"scan", "while"})
_WIDE_DTYPES = ("float64", "complex128")
# default RF203 threshold: a materialized rank>=3 intermediate of 16M
# elements (64 MiB at f32) is never the fused path
DEFAULT_BROADCAST_THRESHOLD = 1 << 24
# RF206: collectives whose OUTPUT can materialize beyond-shard data
# inside a fully-manual shard_map region (ppermute is excluded — it only
# moves shard-sized data, it cannot grow it)
_COLLECTIVE_PRIMS = frozenset({"all_gather", "all_to_all", "psum",
                               "pmax", "pmin"})


def _sub_jaxprs(params: dict):
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def iter_eqns(jaxpr, *, in_loop=False):
    """Yield ``(eqn, inside_loop_body)`` over a jaxpr and every nested
    sub-jaxpr (pjit/scan/while/cond bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        nested = in_loop or eqn.primitive.name in _LOOP_PRIMS
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub, in_loop=nested)


def audit_jaxpr(closed, *, subject,
                broadcast_elems_threshold=DEFAULT_BROADCAST_THRESHOLD
                ) -> list[Diagnostic]:
    """RF201 (host callbacks in loop bodies), RF202 (f64/c128
    intermediates), RF203 (materialized rank>=3 broadcast/gather blowups
    above the element threshold) over one traced jaxpr."""
    jaxpr = closed.jaxpr if isinstance(closed, jax.extend.core.ClosedJaxpr) \
        else closed
    diags = []
    wide_seen = collections.Counter()
    for eqn, in_loop in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name in _CALLBACK_PRIMS and in_loop:
            diags.append(Diagnostic(
                "RF201", subject,
                f"host callback primitive {name!r} inside a scan/while "
                "body: one host round-trip per iteration",
                {"primitive": name}))
        for v in eqn.outvars:
            dt = getattr(getattr(v, "aval", None), "dtype", None)
            if dt is not None and str(dt) in _WIDE_DTYPES:
                wide_seen[(str(dt), name)] += 1
        if name in ("broadcast_in_dim", "gather"):
            out = eqn.outvars[0].aval
            if getattr(out, "ndim", 0) < 3:
                continue
            out_sz = int(np.prod(out.shape))
            in_sz = max((int(np.prod(v.aval.shape))
                         for v in eqn.invars
                         if getattr(v, "aval", None) is not None
                         and getattr(v.aval, "shape", None) is not None),
                        default=0)
            if out_sz >= broadcast_elems_threshold and out_sz > in_sz:
                diags.append(Diagnostic(
                    "RF203", subject,
                    f"{name} materializes a rank-{out.ndim} "
                    f"intermediate of {out_sz} elements "
                    f"(shape {tuple(out.shape)}) — the neighbour-stack "
                    "pattern the fused commit removed",
                    {"primitive": name, "shape": tuple(out.shape),
                     "elements": out_sz}))
    for (dt, name), count in sorted(wide_seen.items()):
        diags.append(Diagnostic(
            "RF202", subject,
            f"{count} {dt} intermediate(s) (first producer: {name}) "
            "under the f32 policy — a weak-typed constant or np.float64 "
            "leaked into the trace",
            {"dtype": dt, "primitive": name, "count": count}))
    return diags


def audit_donation(fn, args, donate_argnums, *, subject
                   ) -> list[Diagnostic]:
    """RF204: donation is only honored when each donated input leaf can
    alias a *distinct* output leaf of identical shape and dtype; any
    unmatched donated leaf silently degrades to a copy (and the caller
    has still lost the buffer)."""
    out = jax.eval_shape(fn, *args)
    avail = collections.Counter(
        (tuple(leaf.shape), np.dtype(leaf.dtype).name)
        for leaf in jax.tree_util.tree_leaves(out))
    diags = []
    for i in donate_argnums:
        for leaf in jax.tree_util.tree_leaves(args[i]):
            key = (tuple(leaf.shape), np.dtype(leaf.dtype).name)
            if avail[key] > 0:
                avail[key] -= 1
            else:
                diags.append(Diagnostic(
                    "RF204", subject,
                    f"donated leaf of arg {i} (shape {key[0]}, dtype "
                    f"{key[1]}) has no matching output buffer to alias "
                    "— donation is declared but cannot be honored",
                    {"arg": i, "shape": key[0], "dtype": key[1]}))
    return diags


def audit_dispatch(run_once, *, subject, expect_entries=1, repeats=2,
                   cache=None) -> list[Diagnostic]:
    """RF205: ``run_once()`` must settle the compiled-plan cache at
    ``expect_entries`` entries, and replays must be pure cache hits.

    ``cache`` is any module/object with the ``stats()``/``clear()``
    contract — the commit-grid dispatch cache by default, or
    ``repro.serve.cache`` (the serving executables) via
    :func:`audit_serve_cache`."""
    if cache is None:
        from ..kernels.rfast_update import dispatch as cache
    cache.clear()
    diags = []
    try:
        run_once()
        first = dict(cache.stats())
        if first["entries"] > expect_entries:
            diags.append(Diagnostic(
                "RF205", subject,
                f"first run created {first['entries']} cache entries "
                f"(expected <= {expect_entries}): the cache key varies "
                "within one fleet shape", dict(first)))
        for _ in range(max(0, repeats - 1)):
            run_once()
        after = dict(cache.stats())
        if after["misses"] > first["misses"]:
            diags.append(Diagnostic(
                "RF205", subject,
                f"replaying with unchanged shapes missed the cache "
                f"{after['misses'] - first['misses']} more time(s) — "
                "recompilation in steady state", dict(after)))
    finally:
        cache.clear()
    return diags


def audit_serve_cache(*, seed=0, buckets=(4, 8)) -> tuple[list[Diagnostic],
                                                          list[str]]:
    """RF205 over the SERVING executable cache (``repro.serve.cache``).

    Runs a tiny engine over a fixed mixed-length workload — prompts
    spanning every configured bucket — and requires the cache to settle
    at exactly ``1 + len(buckets)`` entries (one fused decode executable
    plus one prefill executable per prompt-length bucket) with replays
    hitting only.  Passing ``buckets=None`` disables bucketing, so every
    distinct prompt length builds its own executable and the audit
    fires — the mutation ``tests/test_analysis.py`` pins.
    """
    from ..models.config import ModelConfig
    from ..models.transformer import init_params
    from ..serve import Request, ServeEngine, WeightStore
    from ..serve import cache as serve_cache

    cfg = ModelConfig(name="serve-audit", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    params = init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lengths = [1, 2, 3, 5, 7, 8]          # spans both default buckets
    max_b = max(buckets) if buckets else max(lengths)
    lengths = [min(l, max_b) for l in lengths]

    def run_once():
        eng = ServeEngine(cfg, WeightStore(params), batch=2, max_len=16,
                          buckets=buckets)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab, size=l,
                                            ).astype(np.int32),
                        gen=2, arrive_s=0.0)
                for i, l in enumerate(lengths)]
        eng.run(reqs)

    expect = 1 + (len(buckets) if buckets else 0)
    if buckets is None:
        expect = 1 + 1          # the tightest defensible floor: decode
        #                         + ONE prefill; every extra length fires
    diags = audit_dispatch(run_once, subject="serve_engine[cache]",
                           expect_entries=expect, cache=serve_cache)
    return diags, ["serve_engine[cache]"]


def audit_mesh_collectives(closed, *, subject, state_bytes_threshold
                           ) -> list[Diagnostic]:
    """RF206: no collective inside the mesh-mapped wave body materializes
    (or reduces over) state-sized data.

    Inside a fully-manual shard_map region the ONLY way a device can
    obtain data beyond its own shard is a collective, so auditing the
    collectives' output sizes is a complete check for the "accidentally
    replicated" failure mode: an ``all_gather`` of the packed
    ``(S_loc·n, 4, p)`` state (or a state-sized ``psum``) means the
    parameter sharding silently degenerated to replication.

    ``state_bytes_threshold`` is one lane group's node state at FULL
    parameter width (``S_loc · n · 4 · p_pad · itemsize``).  The
    legitimate per-wave gradient gather reconstructs only the mixed
    iterates — at most ``S_loc·n`` rows of ONE of the four node slots,
    i.e. <= threshold/4 — so a collective at or above the threshold is
    never the designed data flow.
    """
    jaxpr = closed.jaxpr if isinstance(closed, jax.extend.core.ClosedJaxpr) \
        else closed
    diags = []
    for eqn, _ in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name not in _COLLECTIVE_PRIMS:
            continue
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape is None:
                continue
            nbytes = int(np.prod(shape, dtype=np.int64)
                         * np.dtype(aval.dtype).itemsize)
            if nbytes >= state_bytes_threshold:
                diags.append(Diagnostic(
                    "RF206", subject,
                    f"collective {name!r} materializes {nbytes} bytes "
                    f"(shape {tuple(shape)}) inside the mesh-mapped wave "
                    f"body — >= the {state_bytes_threshold}-byte "
                    "full-width state threshold: the shard layout has "
                    "degenerated to replication",
                    {"primitive": name, "shape": tuple(shape),
                     "bytes": nbytes,
                     "threshold": state_bytes_threshold}))
    return diags


# ------------------------------------------------------------------ #
# the standard engine audit the CLI runs
# ------------------------------------------------------------------ #
def audit_engines(*, n=5, p=8, K=48, seed=0,
                  broadcast_elems_threshold=DEFAULT_BROADCAST_THRESHOLD
                  ) -> tuple[list[Diagnostic], list[str]]:
    """Trace every engine at a small size and run all RF2xx checks.

    Returns ``(diagnostics, audited_subjects)``.  Sizes are tiny on
    purpose: the properties audited (callbacks, dtypes, donation
    structure, materialization *pattern*, cache-key stability) are
    shape-generic, so a small trace certifies the program family.
    """
    from ..core.plan import build_comm_plan, pad_comm_plan
    from ..core.scenario import get_scenario
    from ..core.schedule import (build_wavefront_plan, flatten_plans,
                                 stack_plans)
    from ..core.simulator import (PackedState, init_state, pack_state,
                                  rfast_scan, rfast_sweep_scan,
                                  rfast_wavefront_scan, wave_inputs)
    from ..core.topology import get_topology
    from ..kernels.rfast_update.grid import commit_grid
    from ..kernels.rfast_update.kernel import LANE

    rng = np.random.default_rng(seed)
    C = jnp.asarray(rng.normal(0, 1, (n, p)), jnp.float32)
    gfn = lambda i, x, key: x - C[i]
    gamma = 1e-2

    topo = get_topology("binary_tree", n)
    sched = get_scenario("uniform", n).realize(topo, K, seed=seed).schedule
    plan = build_comm_plan(topo)
    H = int(sched.D) + 2
    st = init_state(plan, jnp.zeros((n, p), jnp.float32), gfn,
                    jax.random.PRNGKey(seed), H)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), K)
    diags, audited = [], []
    kw = dict(broadcast_elems_threshold=broadcast_elems_threshold)

    # event-serial engine
    eng = rfast_scan(plan, gfn, gamma, H, donate=False)
    cj = jax.make_jaxpr(eng)(st, jnp.asarray(sched.agent),
                             jnp.asarray(sched.stamp_v),
                             jnp.asarray(sched.stamp_rho), keys)
    diags += audit_jaxpr(cj, subject="rfast_scan", **kw)
    audited.append("rfast_scan")
    diags += audit_donation(rfast_scan(plan, gfn, gamma, H, donate=True),
                            (st, jnp.asarray(sched.agent),
                             jnp.asarray(sched.stamp_v),
                             jnp.asarray(sched.stamp_rho), keys), (0,),
                            subject="rfast_scan[donate]")
    audited.append("rfast_scan[donate]")

    # wavefront engine, both impls (pallas resolves to the emulate
    # dispatch path off-TPU; the audited scan structure is the same)
    wf = build_wavefront_plan(sched, plan, H)
    packed = pack_state(st)
    waves = wave_inputs(wf, keys)
    for impl in ("jnp", "pallas"):
        runner = rfast_wavefront_scan(plan, gfn, gamma, donate=False,
                                      p_real=p,
                                      impl=impl)
        cj = jax.make_jaxpr(runner)(packed, waves)
        diags += audit_jaxpr(cj, subject=f"rfast_wavefront_scan[{impl}]",
                             **kw)
        audited.append(f"rfast_wavefront_scan[{impl}]")
    diags += audit_donation(
        rfast_wavefront_scan(plan, gfn, gamma, donate=True, p_real=p),
        (packed, waves), (0,), subject="rfast_wavefront_scan[donate]")
    audited.append("rfast_wavefront_scan[donate]")

    # fleet (run_sweep / run_epochs) engine over a flattened 2-lane plan
    topo_b = get_topology("line", n)
    plan_b = build_comm_plan(topo_b)
    kw_max = max(plan.kw, plan_b.kw)
    ka_max = max(plan.ka, plan_b.ka)
    ko_max = max(plan.ko, plan_b.ko)
    pads = [pad_comm_plan(c, kw=kw_max, ka=ka_max, ko=ko_max)
            for c in (plan, plan_b)]
    sched_b = get_scenario("straggler", n).realize(topo_b, K,
                                                   seed=seed).schedule
    H_f = max(H, int(sched_b.D) + 2)
    e_a = max(max(1, c.n_edges_a) for c in pads)
    wfs = [build_wavefront_plan(s, c, H_f, e_a=e_a)
           for s, c in zip((sched, sched_b), pads)]
    fleet = flatten_plans(stack_plans(wfs))
    S = 2
    row = (-(-p // LANE), LANE)
    fpacked = PackedState(
        nodes=jnp.zeros((S * n, 4) + row, jnp.float32),
        rho2=jnp.zeros((2 * S * e_a,) + row, jnp.float32),
        v_hist=jnp.zeros((H_f, S * n) + row, jnp.float32),
        rho_hist=jnp.zeros((H_f, S * e_a) + row, jnp.float32))
    fwaves = wave_inputs(fleet, jnp.zeros((S * K, 2), jnp.uint32))
    for impl in ("jnp", "pallas"):
        sweep = rfast_sweep_scan(gfn, gamma, ko=ko_max, n_per_lane=n,
                                 donate=False, p_real=p, impl=impl)
        cj = jax.make_jaxpr(sweep)(fpacked, fwaves)
        diags += audit_jaxpr(cj, subject=f"rfast_sweep_scan[{impl}]",
                             **kw)
        audited.append(f"rfast_sweep_scan[{impl}]")
    diags += audit_donation(
        rfast_sweep_scan(gfn, gamma, ko=ko_max, n_per_lane=n,
                         donate=True, p_real=p), (fpacked, fwaves), (0,),
        subject="rfast_sweep_scan[donate]")
    audited.append("rfast_sweep_scan[donate]")

    # mesh-mapped sweep engine (RF206 + the standard RF2xx checks) on a
    # single-device (1,1) mesh — shard_map bodies are reachable through
    # iter_eqns, and the collective/size audit is shape-generic
    from ..core.simulator import _mesh_sweep_scan
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    mpacked = jax.tree.map(lambda a: a[None], fpacked)
    mwaves = jax.tree.map(lambda a: a[None], fwaves)
    state_bytes = S * n * 4 * row[0] * LANE * np.dtype(np.float32).itemsize
    for impl in ("jnp", "pallas"):
        mrunner = _mesh_sweep_scan(gfn, gamma, ko=ko_max, n_per_lane=n,
                                   mesh=mesh, donate=False, p_real=p,
                                   impl=impl)
        cj = jax.make_jaxpr(mrunner)(mpacked, mwaves)
        diags += audit_jaxpr(cj, subject=f"mesh_sweep_scan[{impl}]", **kw)
        diags += audit_mesh_collectives(
            cj, subject=f"mesh_sweep_scan[{impl}]",
            state_bytes_threshold=state_bytes)
        audited.append(f"mesh_sweep_scan[{impl}]")
    diags += audit_donation(
        _mesh_sweep_scan(gfn, gamma, ko=ko_max, n_per_lane=n, mesh=mesh,
                         donate=True, p_real=p), (mpacked, mwaves), (0,),
        subject="mesh_sweep_scan[donate]")
    audited.append("mesh_sweep_scan[donate]")

    # run_epochs body: the same sweep engine over an epoch topology
    # with an active mask (isolated nodes exercise the sentinel paths)
    sc = get_scenario("churn", max(n, 7))
    topo_e = get_topology("robust_tree", max(n, 7))
    try:
        et = sc.realize_epochs(topo_e, 40 * max(n, 7), seed=seed)
    except ValueError:
        et = None
    if et is not None and len(et.epochs) > 1:
        ep = et.epochs[1]
        plan_e = build_comm_plan(ep.topology)
        sched_e = ep.trace.schedule
        H_e = int(sched_e.D) + 2
        wf_e = build_wavefront_plan(sched_e, plan_e, H_e)
        n_e = plan_e.n
        st_e = init_state(plan_e, jnp.zeros((n_e, p), jnp.float32),
                          lambda i, x, key: x,
                          jax.random.PRNGKey(seed), H_e)
        runner_e = rfast_wavefront_scan(plan_e, lambda i, x, key: x,
                                        gamma, donate=False, p_real=p)
        cj = jax.make_jaxpr(runner_e)(
            pack_state(st_e),
            wave_inputs(wf_e, jax.random.split(jax.random.PRNGKey(0),
                                               wf_e.K)))
        diags += audit_jaxpr(cj, subject="run_epochs[wave body]", **kw)
        audited.append("run_epochs[wave body]")

    # commit_grid call site: traced program + dispatch steady state
    B, ka_g, ko_g, rows, Pf = 4, 2, 2, 8, 16
    r2 = np.random.default_rng(seed + 2)
    f = lambda s: jnp.asarray(r2.normal(0, 1, s), jnp.float32)
    i = lambda s, hi: jnp.asarray(r2.integers(0, hi, s), jnp.int32)
    grid_args = (i((B,), rows), i((B,), rows), i((B, ka_g), rows),
                 i((B, ka_g), rows), i((B, ko_g), rows),
                 f((B,)), jnp.ones((B, ka_g), jnp.float32),
                 f((B, ko_g)), f((rows, Pf)), f((B, Pf)), f((rows, Pf)),
                 f((rows, Pf)), f((rows, Pf)), f((rows, Pf)))
    cj = jax.make_jaxpr(
        lambda *a: commit_grid(*a, mode="emulate"))(*grid_args)
    diags += audit_jaxpr(cj, subject="commit_grid[emulate]", **kw)
    audited.append("commit_grid[emulate]")
    diags += audit_dispatch(
        lambda: jax.block_until_ready(
            commit_grid(*grid_args, mode="emulate")),
        subject="commit_grid[dispatch]", expect_entries=1)
    audited.append("commit_grid[dispatch]")
    return diags, audited
