"""Wavefront-batched simulator vs the event-serial oracle, and the
commit-only fused kernel vs the full kernel.

The wavefront engine (delta histories + host-resolved stale reads +
vmapped lanes) must realize Algorithm 2's exact semantics: final states
equal to the one-event-per-step snapshot engine to fp32 tolerance on
randomized schedules with stragglers, packet loss, and crash windows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (binary_tree, directed_ring, exponential,
                        get_topology, generate_schedule, run_rfast,
                        tracked_mass)
from repro.core.plan import build_comm_plan
from repro.core.schedule import build_wavefront_plan
from repro.kernels.rfast_update.ops import rfast_update

jax.config.update("jax_enable_x64", False)


def quad_grad_fn(n: int, p: int, *, noise: float = 0.1, seed: int = 0):
    rng = np.random.default_rng(seed)
    C = jnp.asarray(rng.normal(0, 1, (n, p)), jnp.float32)
    S = jnp.asarray(rng.uniform(0.5, 2.0, (n, 1)), jnp.float32)

    def gfn(i, x, key):
        g = S[i] * (x - C[i])
        if noise > 0:
            g = g + noise * jax.random.normal(key, x.shape)
        return g

    return gfn


# randomized-schedule matrix: stragglers, loss, crash windows, big fanout
SCENARIOS = [
    pytest.param(dict(builder=binary_tree, n=7, loss=0.0, compute=None,
                      failures=None, latency=0.3, seed=0), id="uniform"),
    pytest.param(dict(builder=directed_ring, n=5, loss=0.3, compute=None,
                      failures=None, latency=0.7, seed=1), id="loss"),
    pytest.param(dict(builder=binary_tree, n=7, loss=0.0,
                      compute=[1.0] * 6 + [4.0], failures=None,
                      latency=0.5, seed=2), id="straggler"),
    pytest.param(dict(builder=exponential, n=8, loss=0.15,
                      compute=[1.0] * 7 + [3.0],
                      failures=[(2, 30.0, 90.0)], latency=0.6, seed=3),
                 id="loss+straggler+crash"),
]


@pytest.mark.slow
@pytest.mark.parametrize("sc", SCENARIOS)
def test_wavefront_matches_event_serial(sc):
    n, p, K = sc["n"], 6, 600
    topo = sc["builder"](n)
    gfn = quad_grad_fn(n, p)
    sched = generate_schedule(topo, K, loss_prob=sc["loss"],
                              latency=sc["latency"],
                              compute_time=sc["compute"],
                              failures=sc["failures"], seed=sc["seed"])
    x0 = jnp.zeros((n, p), jnp.float32)
    # eval chunking exercises the wave-padding path in both modes
    s_ev, _ = run_rfast(topo, sched, gfn, x0, 0.02, mode="event",
                        eval_every=150)
    s_wf, _ = run_rfast(topo, sched, gfn, x0, 0.02, mode="wavefront",
                        eval_every=150)
    assert int(s_wf.k) == int(s_ev.k) == K
    for f in ("x", "v", "z", "g_prev", "rho", "rho_buf"):
        np.testing.assert_allclose(
            np.asarray(getattr(s_wf, f)), np.asarray(getattr(s_ev, f)),
            rtol=2e-5, atol=2e-5, err_msg=f)
    # Lemma 3 holds on the wavefront state too
    np.testing.assert_allclose(
        np.asarray(tracked_mass(s_wf)),
        np.asarray(s_wf.g_prev.sum(axis=0)), rtol=1e-4, atol=1e-4)


def test_wavefront_plan_invariants():
    """Waves cover every event exactly once, in order; agents are distinct
    within a wave; every consumed stamp predates its wave's start."""
    n, K = 7, 800
    topo = binary_tree(n)
    sched = generate_schedule(topo, K, loss_prob=0.1, latency=0.8, seed=5)
    plan = build_comm_plan(topo)
    wf = build_wavefront_plan(sched, plan, int(sched.D) + 2,
                              break_every=250)
    assert wf.sizes.sum() == K
    covered = []
    for w in range(wf.n_waves):
        size = int(wf.sizes[w])
        lanes = wf.kidx[w, :size]
        covered.extend(lanes.tolist())
        agents = wf.agent[w, :size]
        assert len(set(agents.tolist())) == size, "duplicate agent in wave"
        # padding lanes carry the sentinel agent
        assert np.all(wf.agent[w, size:] == n)
        start = int(wf.event_start[w])
        # waves never span a forced (eval) boundary
        assert start // 250 == (start + size - 1) // 250
        for k in lanes:
            a = int(sched.agent[k])
            for e in range(plan.n_edges_w):
                if plan.dst_w[e] == a and plan.w_edge[e] != 0:
                    assert sched.stamp_v[k, e] <= start
            for e in range(plan.n_edges_a):
                if plan.dst_a[e] == a:
                    assert sched.stamp_rho[k, e] <= start
    assert covered == list(range(K)), "events must be covered in order"
    # forced breaks at eval boundaries
    for b in range(250, K, 250):
        assert b in set(wf.event_start.tolist())


def test_wavefront_deterministic_round_robin():
    """Round-robin (Remark 2) compiles to full-width waves and still
    matches the oracle."""
    from repro.core import round_robin_schedule
    n, p = 5, 4
    topo = directed_ring(n)
    gfn = quad_grad_fn(n, p, noise=0.0)
    sched = round_robin_schedule(topo, 10)
    x0 = jnp.asarray(np.random.default_rng(0).normal(0, 1, (n, p)),
                     jnp.float32)
    s_ev, _ = run_rfast(topo, sched, gfn, x0, 0.05, mode="event")
    s_wf, _ = run_rfast(topo, sched, gfn, x0, 0.05, mode="wavefront")
    np.testing.assert_allclose(np.asarray(s_wf.x), np.asarray(s_ev.x),
                               rtol=2e-5, atol=2e-5)


def test_wavefront_pallas_commit_matches_jnp():
    """impl="pallas" (lanes committed through the fused rfast_commit
    kernel on the flat buffer) realizes the same trajectory as the jnp
    scatter path and the event oracle."""
    n, p, K = 7, 6, 250
    topo = binary_tree(n)
    gfn = quad_grad_fn(n, p)
    sched = generate_schedule(topo, K, loss_prob=0.15, latency=0.5, seed=4)
    x0 = jnp.zeros((n, p), jnp.float32)
    s_j, _ = run_rfast(topo, sched, gfn, x0, 0.02, mode="wavefront",
                       eval_every=100)
    s_p, _ = run_rfast(topo, sched, gfn, x0, 0.02, mode="wavefront",
                       eval_every=100, impl="pallas")
    s_e, _ = run_rfast(topo, sched, gfn, x0, 0.02, mode="event")
    for f in ("x", "v", "z", "g_prev", "rho", "rho_buf"):
        np.testing.assert_allclose(
            np.asarray(getattr(s_p, f)), np.asarray(getattr(s_j, f)),
            rtol=2e-5, atol=2e-5, err_msg=f"pallas vs jnp: {f}")
        np.testing.assert_allclose(
            np.asarray(getattr(s_p, f)), np.asarray(getattr(s_e, f)),
            rtol=2e-5, atol=2e-5, err_msg=f"pallas vs event: {f}")
    # the event oracle rejects the kernel backend explicitly
    with pytest.raises(ValueError):
        run_rfast(topo, sched, gfn, x0, 0.02, mode="event", impl="pallas")


# ------------------------------------------------------------------ #
# commit-only kernel vs full kernel
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("P,Kw,Ka,Ko", [(37, 1, 2, 3), (1000, 2, 3, 1),
                                        (32768, 3, 1, 2)])
def test_commit_matches_full_kernel(impl, P, Kw, Ka, Ko):
    r = np.random.default_rng(P + Kw)
    a = lambda *s: jnp.asarray(r.normal(0, 1, s), jnp.float32)
    kw = dict(x=a(P), z=a(P), g_new=a(P), g_old=a(P), v_in=a(Kw, P),
              w_in=jnp.asarray(r.uniform(0, .5, Kw), jnp.float32),
              rho_in=a(Ka, P), rho_buf=a(Ka, P),
              mask=jnp.asarray(r.integers(0, 2, Ka), jnp.float32),
              rho_out=a(Ko, P),
              a_out=jnp.asarray(r.uniform(0, .5, Ko), jnp.float32),
              gamma=0.02, w_self=0.5, a_self=0.4)
    full = rfast_update(**kw, impl=impl)
    commit = rfast_update(**kw, impl=impl, outputs="commit")
    assert len(commit) == 3
    # commit returns (z', rho_out', rho_buf') == full[2:]
    for c, f in zip(commit, full[2:]):
        np.testing.assert_allclose(np.asarray(c), np.asarray(f),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_commit_kernel_protocol_round_random_topologies():
    """The pallas protocol round (now commit-only) still matches the jnp
    backend on random topologies under random loss masks."""
    from repro.core.runtime import init_node_state, make_rfast_round
    rng = np.random.default_rng(0)
    for name, n in [("exponential", 8), ("mesh2d", 9), ("binary_tree", 7)]:
        topo = get_topology(name, n)
        plan = build_comm_plan(topo)
        p = 40
        C = jnp.asarray(rng.normal(0, 1, (n, p)), jnp.float32)

        def grad_fn(params, batch, key):
            del key
            d = params["w"] - batch
            return 0.5 * jnp.sum(d * d), {"w": d}

        params = {"w": jnp.zeros((p,), jnp.float32)}
        key = jax.random.PRNGKey(1)
        keys = jax.random.split(key, n)
        masks = jnp.asarray(rng.uniform(size=plan.e_pad) > 0.4, jnp.float32)
        outs = {}
        for impl in ("jnp", "pallas"):
            state = init_node_state(plan, params, grad_fn, C, key,
                                    robust=True)
            rf = jax.jit(make_rfast_round(plan, grad_fn, gamma=0.01,
                                          robust=True, impl=impl))
            for step in range(3):
                state, _ = rf(state, C, keys, masks)
            outs[impl] = state
        for f in ("x", "z", "g_prev", "rho", "rho_buf"):
            np.testing.assert_allclose(
                np.asarray(getattr(outs["jnp"], f)["w"]),
                np.asarray(getattr(outs["pallas"], f)["w"]),
                rtol=1e-4, atol=1e-4, err_msg=f"{name}:{f}")


def test_donated_round_updates_in_place_semantics():
    """donate=True rounds must produce the same trajectory as undonated
    ones (state rebound every step, old buffers never reused)."""
    from repro.core.runtime import init_node_state, make_rfast_round
    n, p = 5, 16
    topo = directed_ring(n)
    plan = build_comm_plan(topo)
    rng = np.random.default_rng(2)
    C = jnp.asarray(rng.normal(0, 1, (n, p)), jnp.float32)

    def grad_fn(params, batch, key):
        del key
        d = params["w"] - batch
        return 0.5 * jnp.sum(d * d), {"w": d}

    params = {"w": jnp.zeros((p,), jnp.float32)}
    key = jax.random.PRNGKey(3)
    keys = jax.random.split(key, n)
    finals = {}
    for donate in (False, True):
        state = init_node_state(plan, params, grad_fn, C, key)
        rf = make_rfast_round(plan, grad_fn, gamma=0.05, donate=donate)
        if not donate:
            rf = jax.jit(rf)
        for _ in range(4):
            state, _ = rf(state, C, keys, None)
        finals[donate] = np.asarray(state.x["w"])
    np.testing.assert_allclose(finals[False], finals[True],
                               rtol=1e-6, atol=1e-6)


def _drop_writes(state, w, node_new, rho_new, buf_new, *, ko):
    """The drop-mode scatter formulation of one wave's state write: each
    sentinel index drops its row."""
    from repro.core.simulator import PackedState
    rho_commit = jnp.concatenate([rho_new, buf_new], axis=1)
    return PackedState(
        nodes=state.nodes.at[w.agent].set(jnp.stack(node_new, axis=1),
                                          mode="drop"),
        rho2=state.rho2.at[w.rho_gidx].set(rho_commit, mode="drop"),
        v_hist=state.v_hist.at[w.wslot, w.agent].set(node_new[1],
                                                     mode="drop"),
        rho_hist=state.rho_hist.at[w.wslot[:, None], w.rho_gidx[:, :ko]]
        .set(rho_new, mode="drop"))


def _random_waves(rng, *, n_waves, B, N, E, H, ko, ka, kw=2):
    """Wave tables as a plan builds them: distinct real agents and ρ rows
    within a wave, sentinel agent N and sentinel rows 2E on padding
    lanes (at random positions), and sentinel rows on some real lanes
    too (nodes of a lower degree)."""
    from repro.core.simulator import _WaveInputs
    agent = np.full((n_waves, B), N)
    gidx = np.full((n_waves, B, ko + ka), 2 * E)
    for t in range(n_waves):
        real = rng.random(B) < 0.6
        agent[t, real] = rng.choice(N, real.sum(), replace=False)
        for cols, lo in ((range(ko), 0), (range(ko, ko + ka), E)):
            free = list(rng.permutation(E) + lo)
            for b in np.flatnonzero(real):
                for c in cols:
                    if free and rng.random() < 0.7:
                        gidx[t, b, c] = free.pop()
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    f32 = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.float32)
    return _WaveInputs(
        agent=i32(agent), wslot=i32(rng.integers(0, H, (n_waves, B))),
        w_self=f32(n_waves, B), a_self=f32(n_waves, B),
        rslot_v=i32(rng.integers(0, H, (n_waves, B, kw))),
        src_v=i32(rng.integers(0, N, (n_waves, B, kw))),
        w_in=f32(n_waves, B, kw),
        rslot_rho=i32(rng.integers(0, H, (n_waves, B, ka))),
        hist_epos=i32(rng.integers(0, E, (n_waves, B, ka))),
        a_val=jnp.asarray(rng.random((n_waves, B, ka)) < 0.5, jnp.float32),
        rho_gidx=i32(gidx), out_wt=f32(n_waves, B, ko),
        keys=jnp.asarray(rng.integers(0, 2**31, (n_waves, B, 2)),
                         jnp.uint32))


@pytest.mark.parametrize("engine", ["wavefront", "sweep"])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("E", [1, 3])
@pytest.mark.parametrize("ko", [1, 2])
@pytest.mark.parametrize("B", [1, 3, 8, 12])
def test_state_write_matches_drop_scatters(monkeypatch, B, ko, E, impl,
                                           engine):
    """The in-place row writes leave the packed state bit-identical to
    drop-mode scatters, over several waves of random state and tables
    (B = 12 takes the lane loop, the others the unrolled lanes)."""
    from repro.core import simulator
    from repro.core.plan import pad_comm_plan
    N, H, ka, R = 10, 3, 2, 2
    rng = np.random.default_rng([B, ko, E])
    row = (R, 128)
    st = simulator.PackedState(*(
        jnp.asarray(rng.normal(0, 1, s + row), jnp.float32)
        for s in [(N, 4), (2 * E,), (H, N), (H, E)]))
    waves = _random_waves(rng, n_waves=4, B=B, N=N, E=E, H=H, ko=ko,
                          ka=ka)
    grad = lambda i, x, key: 0.5 * x - 0.01 * i

    def run():
        if engine == "sweep":
            runner = simulator.rfast_sweep_scan(
                grad, 0.05, ko=ko, n_per_lane=N, donate=False, impl=impl)
        else:
            plan = pad_comm_plan(build_comm_plan(binary_tree(N)), ko=ko)
            runner = simulator.rfast_wavefront_scan(
                plan, grad, 0.05, donate=False, impl=impl)
        return runner(st, waves)

    new = run()
    monkeypatch.setattr(simulator, "_write_rows", _drop_writes)
    old = run()
    for f, a, b in zip(simulator.PackedState._fields, new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f)
