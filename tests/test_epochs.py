"""Epochized engine tests (dynamic membership, PR 7): oracle equality on
static traces, mass conservation across migrations, the root-failover
re-election claim (epochized converges, frozen-plan provably stalls),
and the one-compile contract for the pallas dispatch cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    binary_tree, get_scenario, init_state, migrate_state,
    realize_epochs_batch, robust_tree, run_epochs, run_rfast,
    run_sweep_epochs,
)
from repro.core.plan import as_comm_plan
from repro.data import make_logistic_problem

jax.config.update("jax_enable_x64", False)


def _problem(n, seed=0):
    return make_logistic_problem(n, m=700, d=16, batch=8,
                                 heterogeneous=True, seed=seed)


def _quad_gfn(n, p, seed=0):
    """Cheap deterministic quadratic for the fast-tier migration tests."""
    rng = np.random.default_rng(seed)
    C = jnp.asarray(rng.normal(0, 1, (n, p)), jnp.float32)

    def gfn(i, x, key):
        del key
        return x - C[i]

    return gfn


# ------------------------------------------------------------------ #
# static traces: the epochized engine IS run_rfast
# ------------------------------------------------------------------ #
@pytest.mark.slow
@pytest.mark.parametrize("sc_name", ["uniform", "straggler"])
def test_single_epoch_matches_run_rfast_oracle(sc_name):
    n, K = 7, 400
    prob = _problem(n)
    topo = binary_tree(n)
    sc = get_scenario(sc_name, n)
    tr = sc.realize(topo, K, seed=3)
    et = sc.realize_epochs(topo, K, seed=3)
    assert len(et.epochs) == 1
    x0 = jnp.zeros((n, prob.p), jnp.float32)
    ev = lambda x, t: {"m": float(jnp.sum(jnp.abs(x))), "t": t}
    st_o, ms_o = run_rfast(topo, tr.schedule, prob, x0, 5e-3, seed=3,
                           eval_every=100, eval_fn=ev, mode="wavefront")
    st_e, ms_e = run_epochs(et, prob, x0, 5e-3, seed=3,
                            eval_every=100, eval_fn=ev)
    np.testing.assert_allclose(np.asarray(st_o.x), np.asarray(st_e.x),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(st_o.z), np.asarray(st_e.z),
                               rtol=1e-6, atol=1e-6)
    assert [m["t"] for m in ms_o] == [m["t"] for m in ms_e]
    np.testing.assert_allclose([m["m"] for m in ms_o],
                               [m["m"] for m in ms_e], rtol=1e-6)


# ------------------------------------------------------------------ #
# migration invariants
# ------------------------------------------------------------------ #
def test_migrate_state_conserves_tracked_mass():
    """Σz + Σ(ρ−ρ̃) − Σg_prev is invariant under migration: in-flight
    mass settles at receivers, a departed node's surplus moves to the
    new root, joiners enter neutrally (z = g_prev = 0)."""
    n, p = 8, 5
    topo = robust_tree(n)
    sc = get_scenario("root_failover", n)
    et = sc.realize_epochs(topo, 1200, seed=1)
    ep0, ep1 = et.epochs
    H = 6
    st = init_state(as_comm_plan(ep0.topology), jnp.zeros((n, p)),
                    _quad_gfn(n, p), jax.random.PRNGKey(0), H)
    # fake undelivered in-flight mass on the ρ/ρ̃ buffers
    e0 = max(1, as_comm_plan(ep0.topology).n_edges_a)
    st = st._replace(rho=st.rho.at[:e0].add(0.37),
                     rho_buf=st.rho_buf.at[: e0 // 2].add(0.11))

    def surplus(s):
        return (float(jnp.sum(s.z)) + float(jnp.sum(s.rho - s.rho_buf))
                - float(jnp.sum(s.g_prev)))

    before = surplus(st)
    mig = migrate_state(st, ep0.topology, ep1, H=H)
    assert abs(surplus(mig) - before) < 1e-3
    # departed root zeroed out, nothing in flight, v carried in slot 0
    assert float(jnp.sum(jnp.abs(mig.z[0]))) == 0.0
    assert float(jnp.sum(jnp.abs(mig.rho))) == 0.0
    assert bool(jnp.all(mig.v_hist[0] == mig.v))


def test_migrate_state_joiner_adopts_root_iterate():
    n, p = 7, 5
    topo = robust_tree(n)
    sc = get_scenario("churn", n)
    et = sc.realize_epochs(topo, 1400, seed=0)
    e0, e1 = et.epochs[0], et.epochs[1]
    assert e1.joined.any()
    j = int(np.nonzero(e1.joined)[0][0])
    H = 6
    st = init_state(as_comm_plan(e0.topology), jnp.zeros((n, p)),
                    _quad_gfn(n, p), jax.random.PRNGKey(0), H)
    st = st._replace(x=st.x.at[:].add(
        jnp.arange(n, dtype=jnp.float32)[:, None]))
    mig = migrate_state(st, e0.topology, e1, H=H)
    np.testing.assert_array_equal(np.asarray(mig.x[j]),
                                  np.asarray(st.x[e1.root]))
    assert float(jnp.sum(jnp.abs(mig.z[j]))) == 0.0
    assert float(jnp.sum(jnp.abs(mig.g_prev[j]))) == 0.0


# ------------------------------------------------------------------ #
# the headline claim: re-election converges, frozen plan stalls
# ------------------------------------------------------------------ #
@pytest.mark.slow
def test_root_failover_epochized_converges_frozen_stalls():
    n, rounds, gamma = 8, 150, 2e-3
    K = rounds * n
    prob = make_logistic_problem(n, m=2800, d=64, batch=16,
                                 heterogeneous=True, seed=0)
    topo = robust_tree(n)
    sc = get_scenario("root_failover", n)
    x0 = jnp.zeros((n, prob.p), jnp.float32)
    ev = lambda x, t: {"loss": float(prob.mean_loss(jnp.mean(x, 0))),
                       "t": t}
    et = sc.realize_epochs(topo, K, seed=0)
    assert len(et.epochs) == 2 and et.epochs[1].root != 0
    _, ms_e = run_epochs(et, prob, x0, gamma, seed=0,
                         eval_every=max(100, K // 40), eval_fn=ev)
    tr = sc.realize(topo, K, seed=0)
    _, ms_f = run_rfast(topo, tr.schedule, prob, x0, gamma, seed=0,
                        eval_every=max(100, K // 40), eval_fn=ev,
                        mode="wavefront")
    post_e = [m["loss"] for m in ms_e if m["t"] > 40.0]
    post_f = [m["loss"] for m in ms_f if m["t"] > 40.0]
    # epochized: still descending after the crash — the last post-crash
    # loss is well below the first
    assert ms_e[-1]["loss"] < 0.7 * post_e[0]
    # frozen: provably stalled — the plateau never moves more than 5%
    # from its post-crash level, and ends far above the epochized run
    assert max(post_f) < 1.05 * min(post_f)
    assert ms_f[-1]["loss"] > 1.5 * ms_e[-1]["loss"]


# ------------------------------------------------------------------ #
# fleet + one-compile contract
# ------------------------------------------------------------------ #
@pytest.mark.slow
def test_sweep_epochs_lane_matches_solo_run():
    n, K = 8, 900
    prob = _problem(n)
    topo = robust_tree(n)
    seeds = (0, 1)
    traces = realize_epochs_batch(topo, K,
                                  scenario=get_scenario("root_failover", n),
                                  seeds=seeds)
    x0 = jnp.zeros((n, prob.p), jnp.float32)
    ev = lambda x, t: {"m": float(jnp.sum(jnp.abs(x))), "t": t}
    sts, mss = run_sweep_epochs(traces, prob, x0, 5e-3, seeds=list(seeds),
                                eval_every=300, eval_fn=ev)
    st0, ms0 = run_epochs(traces[0], prob, x0, 5e-3, seed=0,
                          eval_every=300, eval_fn=ev)
    np.testing.assert_allclose(np.asarray(sts[0].x), np.asarray(st0.x),
                               rtol=1e-6, atol=1e-6)
    assert [m["m"] for m in mss[0]] == [m["m"] for m in ms0]


@pytest.mark.slow
def test_churn_dispatch_cache_one_entry_per_shape():
    """A 3-epoch churn run under impl='pallas' must reuse ONE compiled
    commit_grid entry: epoch transitions change data, never shapes."""
    from tests.helpers.recompiles import assert_no_recompiles
    n, K = 7, 1400
    prob = _problem(n)
    topo = robust_tree(n)
    et = get_scenario("churn", n).realize_epochs(topo, K, seed=0)
    assert len(et.epochs) == 3
    x0 = jnp.zeros((n, prob.p), jnp.float32)
    with assert_no_recompiles(expect_entries=1):
        st_p, _ = run_epochs(et, prob, x0, 5e-3, seed=0, impl="pallas")
    # and the pallas path agrees with the jnp path on the same trace
    st_j, _ = run_epochs(et, prob, x0, 5e-3, seed=0, impl="jnp")
    np.testing.assert_allclose(np.asarray(st_p.x), np.asarray(st_j.x),
                               rtol=2e-5, atol=2e-5)
