"""Fleet-lane sweep engine: padding inertness + per-lane equivalence.

The sweep engine (`run_sweep`) runs S independent experiments as one
compiled program by normalizing CommPlans to common degree maxima
(`pad_comm_plan`), padding WavefrontPlans to shared wave/width/ρ-layout
maxima (`pad_plan`), and stacking them (`stack_plans`).  Two families of
guarantees are pinned here:

* padding is INERT — padded waves, lanes, and ρ rows commit zero delta,
  so a padded plan realizes exactly the trajectory of the unpadded one;
* each fleet lane matches an individual ``run_rfast`` wavefront run of
  the same (scenario, seed, topology) to fp32 tolerance, across a
  randomized matrix that includes crash/recovery windows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (NetworkScenario, binary_tree, directed_ring,
                        exponential, get_scenario, realize_batch,
                        run_rfast, run_sweep, undirected_ring)
from repro.core.plan import build_comm_plan, pad_comm_plan
from repro.core.schedule import (build_wavefront_plan, pad_plan,
                                 stack_plans)
from repro.core.simulator import (init_state, pack_state,
                                  rfast_wavefront_scan, unpack_state,
                                  wave_inputs)
from tests.test_simulator import quad_grad_fn

jax.config.update("jax_enable_x64", False)


def _trees_close(a, b, *, rtol=0.0, atol=1e-7, msg=""):
    for name, x, y in zip(a._fields, a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"{msg}{name}")


# ------------------------------------------------------------------ #
# padding inertness
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("seed,loss", [(0, 0.0), (7, 0.2)])
def test_padded_waves_and_lanes_commit_zero_delta(seed, loss, impl):
    """pad_plan'ed waves/lanes/ρ-rows are no-op commits: running the
    padded plan from the same packed state yields the same final state
    (real-lane arithmetic is untouched — per-lane ops never reduce
    across lanes, and every padded commit scatters to a drop sentinel).
    ``impl='pallas'`` pins the same inertness through the fleet-grid
    commit path (sentinel lanes clamp their gather rows in-kernel)."""
    n, p, K = 7, 5, 300
    topo = binary_tree(n)
    gfn, _ = quad_grad_fn(n, p, noise=0.1)
    sc = NetworkScenario(latency=0.4, loss=loss)
    sched = sc.realize(topo, K, seed=seed).schedule
    plan = build_comm_plan(topo)
    H = int(sched.D) + 2
    wf = build_wavefront_plan(sched, plan, H)

    key = jax.random.PRNGKey(seed)
    key, init_key = jax.random.split(key)
    step_keys = jax.random.split(key, K)
    state0 = init_state(plan, jnp.zeros((n, p), jnp.float32), gfn,
                        init_key, H)
    runner = rfast_wavefront_scan(plan, gfn, 0.02, donate=False, impl=impl,
                                  p_real=p)

    base = runner(pack_state(state0), wave_inputs(wf, step_keys))

    # widen lanes + append all-padded waves
    wf_pad = pad_plan(wf, width=wf.width + 2, n_waves=wf.n_waves + 3)
    out = runner(pack_state(state0), wave_inputs(wf_pad, step_keys))
    _trees_close(out, base, msg="wave/lane pad: ")

    # ρ-layout padding: extra state rows are never touched
    e_a2 = wf.e_a + 3
    wf_rho = pad_plan(wf, e_a=e_a2)
    out2 = runner(pack_state(state0, e_a=e_a2),
                  wave_inputs(wf_rho, step_keys))
    e_a = wf.e_a
    np.testing.assert_allclose(np.asarray(out2.nodes),
                               np.asarray(base.nodes), atol=1e-7)
    np.testing.assert_allclose(np.asarray(out2.rho2[:e_a]),
                               np.asarray(base.rho2[:e_a]), atol=1e-7)
    np.testing.assert_allclose(np.asarray(out2.rho2[e_a2:e_a2 + e_a]),
                               np.asarray(base.rho2[e_a:]), atol=1e-7)
    np.testing.assert_allclose(np.asarray(out2.rho_hist[:, :e_a]),
                               np.asarray(base.rho_hist), atol=1e-7)
    # the pad rows themselves hold exactly zero (nothing ever scattered)
    assert not np.asarray(out2.rho2[e_a:e_a2]).any()
    assert not np.asarray(out2.rho_hist[:, e_a:]).any()


def test_stack_plans_shapes_and_sentinels():
    """Stacked fleet plans: common (S, n_waves, B, ...) shapes, per-lane
    event coverage preserved in order, tail padding carries sentinels."""
    n, K = 7, 400
    topos = [binary_tree(n), directed_ring(n), exponential(n)]
    plans = [build_comm_plan(t) for t in topos]
    kw = max(pl.kw for pl in plans)
    ka = max(pl.ka for pl in plans)
    ko = max(pl.ko for pl in plans)
    e_a = max(pl.n_edges_a for pl in plans)
    scheds = [get_scenario("uniform", n).realize(t, K, seed=s).schedule
              for s, t in enumerate(topos)]
    H = max(int(s.D) for s in scheds) + 2
    wfs = [build_wavefront_plan(sch, pad_comm_plan(pl, kw=kw, ka=ka, ko=ko),
                                H, e_a=e_a)
           for sch, pl in zip(scheds, plans)]
    fleet = stack_plans(wfs)
    S, NW, B = 3, max(w.n_waves for w in wfs), max(w.width for w in wfs)
    assert fleet.agent.shape == (S, NW, B)
    assert fleet.rslot_v.shape == (S, NW, B, kw)
    assert fleet.rho_gidx.shape == (S, NW, B, ko + ka)
    assert fleet.n_waves == NW and fleet.n_lanes == S
    assert (fleet.width, fleet.n, fleet.e_a, fleet.K) == (B, n, e_a, K)
    for s in range(S):
        sizes = fleet.sizes[s]
        assert sizes.sum() == K
        covered = [int(k) for w in range(NW)
                   for k in fleet.kidx[s, w, :sizes[w]]]
        assert covered == list(range(K))
        # every pad slot (wave tail or appended wave) is a sentinel lane
        lane_pad = np.arange(B)[None, :] >= sizes[:, None]
        assert np.all(fleet.agent[s][lane_pad] == n)
        assert np.all(fleet.kidx[s][lane_pad] == K)
        assert np.all(fleet.rho_gidx[s][lane_pad] == 2 * e_a)


def test_pad_comm_plan_inert_columns():
    plan = build_comm_plan(binary_tree(7))
    padded = pad_comm_plan(plan, kw=plan.kw + 2, ka=plan.ka + 1,
                           ko=plan.ko + 3)
    assert (padded.kw, padded.ka, padded.ko) == (plan.kw + 2, plan.ka + 1,
                                                 plan.ko + 3)
    assert not padded.in_w_wt[:, plan.kw:].any()
    assert not padded.in_a_val[:, plan.ka:].any()
    assert not padded.out_a_val[:, plan.ko:].any()
    # real columns untouched, dense edge arrays shared
    np.testing.assert_array_equal(padded.in_w_wt[:, :plan.kw], plan.in_w_wt)
    np.testing.assert_array_equal(padded.src_a, plan.src_a)
    with pytest.raises(ValueError):
        pad_comm_plan(plan, kw=plan.kw - 1)


# ------------------------------------------------------------------ #
# per-lane equivalence with run_rfast
# ------------------------------------------------------------------ #
def _lane_matches(state, sched, topo, gfn, seed, eval_every, metrics=None,
                  ref_kw=None):
    ref, ms_ref = run_rfast(topo, sched, gfn,
                            jnp.zeros(state.x.shape, jnp.float32), 0.02,
                            seed=seed, eval_every=eval_every,
                            **(ref_kw or {}))
    for f in ("x", "v", "z", "g_prev", "rho", "rho_buf"):
        np.testing.assert_allclose(
            np.asarray(getattr(state, f)), np.asarray(getattr(ref, f)),
            rtol=2e-5, atol=2e-5, err_msg=f"seed {seed}: {f}")
    return ms_ref


def test_run_sweep_matches_run_rfast_fast():
    """Two heterogeneous lanes (different topology AND scenario AND
    seed) reproduce their individual wavefront runs."""
    n, p, K = 5, 4, 160
    gfn, _ = quad_grad_fn(n, p, noise=0.1)
    topos = [binary_tree(n), directed_ring(n)]
    scs = [get_scenario("uniform", n), get_scenario("packet_loss", n)]
    seeds = [0, 4]
    scheds = [sc.realize(t, K, seed=s).schedule
              for sc, t, s in zip(scs, topos, seeds)]
    x0 = jnp.zeros((n, p), jnp.float32)
    states, _ = run_sweep(topos, scheds, gfn, x0, 0.02, seeds=seeds,
                          eval_every=80)
    for s in range(2):
        _lane_matches(states[s], scheds[s], topos[s], gfn, seeds[s], 80)


@pytest.mark.slow
def test_run_sweep_randomized_matrix():
    """The acceptance matrix: a randomized (scenario, seed, topology)
    fleet — uniform / straggler / packet_loss / crash_recovery windows —
    where every lane must match its individual run_rfast trajectory AND
    its per-chunk eval series."""
    n, p, K = 7, 6, 600
    gfn, _ = quad_grad_fn(n, p, noise=0.1)
    # crash windows sized to the realized horizon (K/n compute units)
    crash = NetworkScenario(
        latency=0.3, failures=((n - 1, 15.0, 40.0), (2, 55.0, 70.0)),
        name="crash_recovery")
    lanes = [
        (get_scenario("uniform", n), binary_tree(n), 0),
        (get_scenario("straggler", n), directed_ring(n), 11),
        (get_scenario("packet_loss", n), exponential(n), 5),
        (crash, binary_tree(n), 3),
        (crash, undirected_ring(n), 8),
    ]
    scheds = [sc.realize(t, K, seed=s).schedule for sc, t, s in lanes]
    x0 = jnp.zeros((n, p), jnp.float32)
    ev = 150

    def eval_fn(x, t):
        return {"xm": float(jnp.mean(x)), "t": t}

    states, metrics = run_sweep([t for _, t, _ in lanes], scheds, gfn, x0,
                                0.02, seeds=[s for _, _, s in lanes],
                                eval_every=ev, eval_fn=eval_fn)
    for i, (sc, topo, seed) in enumerate(lanes):
        ms_ref = _lane_matches(states[i], scheds[i], topo, gfn, seed, ev,
                               ref_kw={"eval_fn": eval_fn})
        assert len(metrics[i]) == len(ms_ref) == K // ev
        for a, b in zip(metrics[i], ms_ref):
            assert a["t"] == b["t"] and a["k"] == b["k"]
            assert abs(a["xm"] - b["xm"]) < 1e-4


def test_run_sweep_pallas_matches_jnp():
    """impl='pallas' (one fleet-grid commit launch per wave) realizes
    the same trajectories."""
    n, p, K = 5, 6, 120
    gfn, _ = quad_grad_fn(n, p, noise=0.1)
    topos = [binary_tree(n), directed_ring(n)]
    scheds = [get_scenario("uniform", n).realize(t, K, seed=s).schedule
              for s, t in enumerate(topos)]
    x0 = jnp.zeros((n, p), jnp.float32)
    s_j, _ = run_sweep(topos, scheds, gfn, x0, 0.02, seeds=[0, 1])
    s_p, _ = run_sweep(topos, scheds, gfn, x0, 0.02, seeds=[0, 1],
                       impl="pallas")
    for a, b in zip(s_j, s_p):
        for f in ("x", "v", "z", "g_prev", "rho", "rho_buf"):
            np.testing.assert_allclose(
                np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
                rtol=2e-5, atol=2e-5, err_msg=f)


@pytest.mark.slow
def test_run_sweep_pallas_randomized_matrix():
    """The tentpole acceptance matrix through the grid path: a
    randomized (topology × scenario × seed) fleet where every
    ``run_sweep(impl='pallas')`` lane must match its individual
    ``run_rfast`` trajectory."""
    n, p, K = 7, 6, 600
    gfn, _ = quad_grad_fn(n, p, noise=0.1)
    crash = NetworkScenario(
        latency=0.3, failures=((n - 1, 15.0, 40.0), (2, 55.0, 70.0)),
        name="crash_recovery")
    lanes = [
        (get_scenario("uniform", n), binary_tree(n), 2),
        (get_scenario("straggler", n), directed_ring(n), 13),
        (get_scenario("packet_loss", n), exponential(n), 6),
        (crash, undirected_ring(n), 9),
    ]
    scheds = [sc.realize(t, K, seed=s).schedule for sc, t, s in lanes]
    x0 = jnp.zeros((n, p), jnp.float32)
    states, _ = run_sweep([t for _, t, _ in lanes], scheds, gfn, x0, 0.02,
                          seeds=[s for _, _, s in lanes], eval_every=150,
                          impl="pallas")
    for i, (sc, topo, seed) in enumerate(lanes):
        _lane_matches(states[i], scheds[i], topo, gfn, seed, 150)


def test_run_sweep_pallas_single_dispatch_signature():
    """The dispatch contract: one fleet sweep resolves to ONE grid-launch
    signature (heterogeneous lanes are padded to shared maxima), and a
    re-run over the same schedules with different RNG seeds re-traces
    onto the cached entry — zero new misses."""
    from tests.helpers.recompiles import assert_no_recompiles

    n, p, K = 5, 6, 120
    gfn, _ = quad_grad_fn(n, p, noise=0.1)
    topos = [binary_tree(n), directed_ring(n), exponential(n)]
    scheds = [get_scenario("uniform", n).realize(t, K, seed=s).schedule
              for s, t in enumerate(topos)]
    x0 = jnp.zeros((n, p), jnp.float32)

    # one signature for the whole heterogeneous fleet: every chunk of
    # every lane rides the same padded wave shape
    with assert_no_recompiles(expect_entries=1) as rec:
        run_sweep(topos, scheds, gfn, x0, 0.02, seeds=[0, 1, 2],
                  impl="pallas")
    assert rec.misses == 1, rec

    # same schedules, new seeds: new trace, same cached launch
    with assert_no_recompiles(expect_entries=0, fresh=False) as rec2:
        run_sweep(topos, scheds, gfn, x0, 0.02, seeds=[7, 8, 9],
                  impl="pallas")
    assert rec2.misses == 0, rec2
    assert rec2.hits > 0, rec2


def test_wavefront_pallas_block_padded_p_is_inert():
    """The compiled-mode contract on CPU: zero-padding the flat
    parameter axis to a block multiple (pack_state(p_pad=...) +
    p_real=p threading) realizes the exact trajectory of the default
    (one LANE row) padding, and the pad tail stays identically zero."""
    from repro.kernels.rfast_update.grid import block_pad_width

    n, p, K = 5, 7, 150
    topo = binary_tree(n)
    gfn, _ = quad_grad_fn(n, p, noise=0.1)
    sched = get_scenario("uniform", n).realize(topo, K, seed=1).schedule
    plan = build_comm_plan(topo)
    H = int(sched.D) + 2
    wf = build_wavefront_plan(sched, plan, H)
    key = jax.random.PRNGKey(1)
    key, init_key = jax.random.split(key)
    step_keys = jax.random.split(key, K)
    state0 = init_state(plan, jnp.zeros((n, p), jnp.float32), gfn,
                        init_key, H)
    waves = wave_inputs(wf, step_keys)

    # p_real must slice before grad_fn: quad_grad_fn rejects padded x
    base = rfast_wavefront_scan(plan, gfn, 0.02, donate=False,
                                impl="pallas",
                                p_real=p)(pack_state(state0), waves)
    Pp = block_pad_width(p)
    padded = rfast_wavefront_scan(
        plan, gfn, 0.02, donate=False, impl="pallas",
        p_real=p)(pack_state(state0, p_pad=Pp), waves)
    base_st = unpack_state(base, 0, p=p)
    whole = unpack_state(padded, 0)
    for name, a, b in list(zip(base_st._fields, base_st, whole))[1:]:
        np.testing.assert_allclose(np.asarray(a), np.asarray(b[..., :p]),
                                   rtol=1e-6, atol=1e-6, err_msg=name)
        assert not np.asarray(b[..., p:]).any(), name


def test_run_sweep_validation():
    n, p, K = 5, 4, 60
    gfn, _ = quad_grad_fn(n, p)
    topo = binary_tree(n)
    sched = get_scenario("uniform", n).realize(topo, K, seed=0).schedule
    x0 = jnp.zeros((n, p), jnp.float32)
    with pytest.raises(ValueError):      # node counts must agree
        run_sweep([topo, binary_tree(n + 2)], [sched, sched], gfn, x0, 0.02)
    short = get_scenario("uniform", n).realize(topo, K - 10, seed=0).schedule
    with pytest.raises(ValueError):      # K must agree
        run_sweep(topo, [sched, short], gfn, x0, 0.02)
    with pytest.raises(ValueError):      # one seed per lane
        run_sweep(topo, [sched, sched], gfn, x0, 0.02, seeds=[0])


def test_realize_batch_modes():
    n, K = 5, 40
    topo = binary_tree(n)
    tr = realize_batch(topo, K, scenario="uniform", seeds=(0, 1))
    assert len(tr) == 2 and all(t.schedule.K == K for t in tr)
    # seed 0 lane is bit-identical to a direct realize
    direct = get_scenario("uniform", n).realize(topo, K, seed=0)
    np.testing.assert_array_equal(tr[0].schedule.agent,
                                  direct.schedule.agent)
    sweep = realize_batch(topo, K, scenarios=("uniform", "straggler"),
                          seeds=(0, 1, 2))
    assert len(sweep) == 6               # scenario-major, seed-minor
    np.testing.assert_array_equal(sweep[0].schedule.agent,
                                  tr[0].schedule.agent)
    with pytest.raises(ValueError):
        realize_batch(topo, K, seeds=(0,))
    with pytest.raises(ValueError):
        realize_batch(topo, K, scenario="uniform",
                      scenarios=("straggler",))
