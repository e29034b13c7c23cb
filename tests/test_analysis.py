"""repro.analysis: the linter lints, and each RF code fires on exactly
the bug class it owns.

Three layers of pinning:

* CLEAN — real plans from the registry matrix (and their transform
  compositions) produce zero diagnostics, bit-for-bit roundtrips hold,
  and the engine wiring (``verify_plans=True``) passes end to end.
* MUTATION — for every diagnostic code, a minimal surgical corruption
  of an otherwise-clean artifact makes its owning pass report exactly
  that code and nothing else.  This is what keeps the codes *stable*:
  a refactor that silently widens or narrows a check trips here.
* WIRING — ``check_or_raise`` raises :class:`PlanInvariantError`, the
  topology builders blame themselves by name, and ``audit_engines``
  stays clean over the shipped engines.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.analysis import CODES, PlanInvariantError, planlint
from repro.analysis.planlint import unflatten_plans
from repro.core import binary_tree, get_scenario, run_rfast, run_sweep
from repro.core.plan import build_comm_plan, pad_comm_plan
from repro.core.schedule import (_WAVE_FIELDS, build_wavefront_plan,
                                 concat_plans, flatten_plans, pad_plan,
                                 slice_plan, stack_plans)
from repro.core.topology import get_topology

jax.config.update("jax_enable_x64", False)

N = 7
K = 96


def codes(diags):
    return sorted({d.code for d in diags})


def _wf_setup(topo_name="binary_tree", scenario="uniform", seed=0, n=N):
    topo = get_topology(topo_name, n)
    sched = get_scenario(scenario, n).realize(topo, K, seed=seed).schedule
    comm = build_comm_plan(topo)
    H = int(sched.D) + 2
    wf = build_wavefront_plan(sched, comm, H)
    return topo, sched, comm, H, wf


def _fleet_setup(seed=0, n=N):
    """Two heterogeneous lanes through the sweep engine's exact plumbing:
    pad_comm_plan -> build_wavefront_plan(e_a=) -> stack -> flatten."""
    names = ("binary_tree", "line")
    topos = [get_topology(t, n) for t in names]
    comms = [build_comm_plan(t) for t in topos]
    kw = max(c.kw for c in comms)
    ka = max(c.ka for c in comms)
    ko = max(c.ko for c in comms)
    padded = [pad_comm_plan(c, kw=kw, ka=ka, ko=ko) for c in comms]
    scheds = [get_scenario("uniform", n).realize(t, K, seed=seed + s).schedule
              for s, t in enumerate(topos)]
    e_a = max(max(1, c.n_edges_a) for c in padded)
    H = max(int(s.D) + 2 for s in scheds)
    wfs = [build_wavefront_plan(s, c, H, e_a=e_a)
           for s, c in zip(scheds, padded)]
    stacked = stack_plans(wfs)
    return padded, scheds, H, stacked, flatten_plans(stacked)


# ------------------------------------------------------------------ #
# catalog
# ------------------------------------------------------------------ #
def test_code_catalog_complete():
    assert sorted(CODES) == [f"RF10{i}" for i in range(1, 7)] \
        + [f"RF20{i}" for i in range(1, 7)]
    for info in CODES.values():
        assert info.owner and info.title and info.invariant
        assert info.motivation  # every code cites the bug that earned it


# ------------------------------------------------------------------ #
# clean plans stay clean (property layer)
# ------------------------------------------------------------------ #
@settings(max_examples=8, deadline=None)
@given(
    topo_name=st.sampled_from(["binary_tree", "line", "directed_ring",
                               "undirected_ring", "exponential",
                               "robust_tree"]),
    scenario=st.sampled_from(["uniform", "straggler", "packet_loss"]),
    seed=st.integers(min_value=0, max_value=10_000),
)
def test_transform_compositions_stay_clean(topo_name, scenario, seed):
    """pad/slice/concat over any realized plan: zero diagnostics, and the
    composed plan still matches the schedule it came from."""
    topo, sched, comm, H, wf = _wf_setup(topo_name, scenario, seed)
    e_a = max(1, comm.n_edges_a)
    assert planlint.lint_comm_plan(comm, topo) == []
    assert planlint.lint_wavefront_plan(
        wf, comm=comm, schedule=sched, H=H) == []
    pp = pad_plan(wf, width=wf.width + 2, n_waves=wf.n_waves + 3,
                  e_a=e_a + 4)
    assert planlint.lint_wavefront_plan(
        pp, comm=comm, schedule=sched, H=H) == []
    mid = max(1, pp.n_waves // 2)
    rejoined = concat_plans([slice_plan(pp, 0, mid),
                             slice_plan(pp, mid, pp.n_waves)])
    assert planlint.lint_wavefront_plan(
        rejoined, comm=comm, schedule=sched, H=H) == []


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_flatten_roundtrip_bit_for_bit(seed):
    """unflatten_plans(flatten_plans(stacked)) == stacked exactly, for
    every table except the aggregate-only event_start/sizes."""
    _, _, H, stacked, flat = _fleet_setup(seed)
    back = unflatten_plans(flat, stacked.agent.shape[0])
    for f in _WAVE_FIELDS:
        if f in ("event_start", "sizes"):
            continue
        a, b = np.asarray(getattr(stacked, f)), np.asarray(getattr(back, f))
        np.testing.assert_array_equal(a, b, err_msg=f)
    assert planlint.lint_flatten(stacked, flat) == []
    assert planlint.lint_wavefront_plan(flat, H=H) == []


# ------------------------------------------------------------------ #
# mutation layer: each code fires, and only it
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def wf_env():
    return _wf_setup()


def _mutate(wf, **arrs):
    return dataclasses.replace(wf, **arrs)


def test_rf101_duplicate_lane_write_write_race(wf_env):
    topo, sched, comm, H, wf = wf_env
    n = topo.n
    ag = np.asarray(wf.agent)
    w = next(w for w in range(wf.n_waves) if (ag[w] != n).sum() >= 2)
    l0, l1 = np.nonzero(ag[w] != n)[0][:2]
    arrs = {}
    for f in _WAVE_FIELDS:
        a = np.array(getattr(wf, f))
        if a.ndim >= 2:
            a[w, l1] = a[w, l0]
            arrs[f] = a
    diags = planlint.lint_wavefront_plan(
        _mutate(wf, **arrs), comm=comm, schedule=sched, H=H)
    assert codes(diags) == ["RF101"], diags


def test_rf102_ring_slot_alias(wf_env):
    topo, sched, comm, H, wf = wf_env
    rs = np.array(wf.rslot_v)
    wi = np.asarray(wf.w_in)
    w, l, c = [x[0] for x in np.nonzero(wi != 0)]
    rs[w, l, c] = (rs[w, l, c] + 1) % H
    diags = planlint.lint_wavefront_plan(
        _mutate(wf, rslot_v=rs), comm=comm, schedule=sched, H=H)
    assert codes(diags) == ["RF102"], diags


def test_rf103_out_of_range_agent(wf_env):
    topo, sched, comm, H, wf = wf_env
    n = topo.n
    ag = np.array(wf.agent)
    w = next(w for w in range(wf.n_waves) if (ag[w] != n).any())
    l = np.nonzero(ag[w] != n)[0][0]
    ag[w, l] = n + 3
    diags = planlint.lint_wavefront_plan(
        _mutate(wf, agent=ag), comm=comm, schedule=sched, H=H)
    assert codes(diags) == ["RF103"], diags


def test_rf104_flatten_offset_corruption():
    _, _, _, stacked, flat = _fleet_setup()
    agf = np.array(flat.agent)
    # a live slot whose lane-local agent is not the last node, so +1
    # stays in-range within the block but breaks the bijection
    wv, sl = [x[0] for x in np.nonzero((agf != flat.n) & (agf % N < N - 1))]
    agf[wv, sl] += 1
    diags = planlint.lint_flatten(
        stacked, dataclasses.replace(flat, agent=agf))
    assert codes(diags) == ["RF104"], diags


def test_rf105_mass_conservation_broken(wf_env):
    topo, _, comm, _, _ = wf_env
    we = np.array(comm.w_edge)
    we[0] += 0.25
    diags = planlint.lint_comm_plan(
        dataclasses.replace(comm, w_edge=we), topo)
    assert codes(diags) == ["RF105"], diags


def test_rf106_epoch_coverage_gap():
    et = get_scenario("churn", N).realize_epochs(
        get_topology("robust_tree", N), 1400, seed=0)
    assert planlint.lint_epoch_trace(et) == []
    eps = list(et.epochs)
    eps[1] = dataclasses.replace(eps[1], joined=np.zeros(N, bool))
    diags = planlint.lint_epoch_trace(
        dataclasses.replace(et, epochs=tuple(eps)))
    assert codes(diags) == ["RF106"], diags


def test_rf201_callback_in_scan():
    from repro.analysis import jaxlint

    def body(c, x):
        y = jax.pure_callback(lambda v: np.asarray(v) * 2,
                              jax.ShapeDtypeStruct((), jnp.float32), x)
        return c + y, y

    cj = jax.make_jaxpr(
        lambda xs: jax.lax.scan(body, jnp.float32(0), xs))(jnp.ones(4))
    assert codes(jaxlint.audit_jaxpr(cj, subject="m")) == ["RF201"]


def test_rf202_f64_promotion():
    from repro.analysis import jaxlint
    with jax.enable_x64(True):
        cj = jax.make_jaxpr(lambda x: x * np.float64(1.5))(np.float64(2.0))
    assert codes(jaxlint.audit_jaxpr(cj, subject="m")) == ["RF202"]


def test_rf203_materialized_broadcast():
    from repro.analysis import jaxlint
    g = lambda x: (jnp.broadcast_to(x[None, None, :],
                                    (8, 4, x.shape[0])) * 2.0).sum()
    cj = jax.make_jaxpr(g)(jnp.ones(32))
    assert codes(jaxlint.audit_jaxpr(
        cj, subject="m", broadcast_elems_threshold=64)) == ["RF203"]
    # same jaxpr, default threshold: too small to flag
    assert jaxlint.audit_jaxpr(cj, subject="m") == []


def test_rf204_unhonorable_donation():
    from repro.analysis import jaxlint
    h = jax.jit(lambda s: s[:1].sum(), donate_argnums=(0,))
    diags = jaxlint.audit_donation(h, (jnp.ones((4, 4)),), (0,),
                                   subject="m")
    assert codes(diags) == ["RF204"]


def test_rf205_dispatch_cache_churn():
    from repro.analysis import jaxlint
    from repro.kernels.rfast_update import dispatch

    state = {"i": 0}

    def churn():
        state["i"] += 1
        dispatch.lookup(("k", state["i"]), lambda: (lambda: None))()

    diags = jaxlint.audit_dispatch(churn, subject="m", expect_entries=1)
    assert codes(diags) == ["RF205"]

    def steady():
        dispatch.lookup(("k",), lambda: (lambda: None))()

    assert jaxlint.audit_dispatch(steady, subject="m") == []


def test_rf205_serve_cache_clean_and_unbucketized_mutation():
    """The serving executable cache passes the RF205 audit with length
    bucketing on, and the mutation — ``buckets=None``, so every distinct
    prompt length compiles its own prefill executable — fires it."""
    from repro.analysis import jaxlint

    diags, audited = jaxlint.audit_serve_cache()
    assert diags == []
    assert audited == ["serve_engine[cache]"]

    diags, _ = jaxlint.audit_serve_cache(buckets=None)
    assert codes(diags) == ["RF205"]
    assert "cache key varies" in diags[0].message


def test_rf206_state_sized_collective_in_mesh_body():
    from jax.sharding import PartitionSpec as P

    from repro.analysis import jaxlint

    def _shard_map(fn, mesh, in_specs, out_specs, axes):
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names=set(axes),
                             check_vma=False)

    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    nodes = jnp.zeros((1, 10, 4, 8), jnp.float32)   # (D, S_loc*n, 4, p)
    threshold = 10 * 4 * 8 * 4                       # full-width bytes

    # MUTATION: the "accidentally replicated" body — all_gather the
    # whole packed node state over the param axis before using it
    def bad(st):
        full = jax.lax.all_gather(st[0], "model", axis=2, tiled=True)
        return (full.sum(2) * 2.0)[None]

    spec = P("data", None, None, "model")
    cj = jax.make_jaxpr(_shard_map(
        bad, mesh, (spec,), P("data", None, None),
        ("data", "model")))(nodes)
    diags = jaxlint.audit_mesh_collectives(
        cj, subject="m", state_bytes_threshold=threshold)
    assert codes(diags) == ["RF206"]
    assert diags[0].data["primitive"] == "all_gather"

    # the designed flow — gather ONE of the four node slots (the mixed
    # iterates, threshold/4 bytes) — stays below the line
    def good(st):
        x = jax.lax.all_gather(st[0, :, 0], "model", axis=1, tiled=True)
        return (st * x.sum())

    cj = jax.make_jaxpr(_shard_map(
        good, mesh, (spec,), spec, ("data", "model")))(nodes)
    assert jaxlint.audit_mesh_collectives(
        cj, subject="m", state_bytes_threshold=threshold) == []

    # a state-sized psum is replication traffic too, all_gather or not
    def psum_bad(st):
        return st + jax.lax.psum(st, "model")

    cj = jax.make_jaxpr(_shard_map(
        psum_bad, mesh, (spec,), spec, ("data", "model")))(nodes)
    diags = jaxlint.audit_mesh_collectives(
        cj, subject="m", state_bytes_threshold=threshold)
    assert codes(diags) == ["RF206"]
    assert diags[0].data["primitive"] == "psum"


# ------------------------------------------------------------------ #
# wiring
# ------------------------------------------------------------------ #
def test_check_or_raise_wraps_diagnostics(wf_env):
    topo, _, comm, _, _ = wf_env
    we = np.array(comm.w_edge)
    we[0] += 0.25
    diags = planlint.lint_comm_plan(
        dataclasses.replace(comm, w_edge=we), topo)
    with pytest.raises(PlanInvariantError) as ei:
        planlint.check_or_raise(diags, "test")
    assert codes(ei.value.diagnostics) == ["RF105"]
    assert "RF105" in str(ei.value)
    planlint.check_or_raise([], "test")  # clean is a no-op


def test_engines_verify_plans_flag():
    """verify_plans=True on the real engines over real plans: no raise,
    same trajectory as the unverified run."""
    n, p = 5, 4
    rng = np.random.default_rng(0)
    C = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    gfn = lambda i, x, key: x - C[i]
    x0 = jnp.zeros((n, p), jnp.float32)
    topo = binary_tree(n)
    sched = get_scenario("uniform", n).realize(topo, 80, seed=0).schedule
    st_v, _ = run_rfast(topo, sched, gfn, x0, 1e-2, seed=0,
                        verify_plans=True)
    st_p, _ = run_rfast(topo, sched, gfn, x0, 1e-2, seed=0)
    np.testing.assert_array_equal(np.asarray(st_v.x), np.asarray(st_p.x))
    topos = [binary_tree(n), get_topology("line", n)]
    scheds = [get_scenario("uniform", n).realize(t, 80, seed=s).schedule
              for s, t in enumerate(topos)]
    run_sweep(topos, scheds, gfn, x0, 1e-2, seeds=[0, 1],
              verify_plans=True)


def test_builder_errors_name_the_builder(monkeypatch):
    import repro.core.topology as T

    orig = T._row_stochastic_from_in_edges

    def broken(n, in_edges):
        W = orig(n, in_edges)
        W[0] *= 2.0
        return W

    monkeypatch.setattr(T, "_row_stochastic_from_in_edges", broken)
    with pytest.raises(ValueError, match=r"'binary_tree' \(n=5\)"):
        T.binary_tree(5)


@pytest.mark.slow
def test_run_plan_matrix_quick_subset_clean():
    from repro.analysis.runner import run_plan_matrix
    diags, stats = run_plan_matrix(
        n=5, K=64, K_epochs=600, seeds=(0,),
        scenarios=("uniform", "churn"),
        topologies=("binary_tree", "robust_tree"))
    assert codes(diags) == [], [d.to_json() for d in diags]
    assert stats["wavefront_plans"] > 0 and stats["fleets"] > 0
    assert stats["epoch_traces"] > 0


@pytest.mark.slow
def test_audit_engines_clean():
    from repro.analysis import jaxlint
    diags, audited = jaxlint.audit_engines(n=5, p=8, K=48)
    assert codes(diags) == [], [d.to_json() for d in diags]
    assert len(audited) >= 8, audited
