"""Program spans and counters (``repro.metrics``) and the engine's
device scopes: off, tracing records nothing; on, the engine loops record
their plan, init, per-chunk spans and event counts, and the compiled
wave program carries the ``rfast.*`` scopes in its op-name metadata."""
import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import metrics
from repro.core import binary_tree, generate_schedule, run_rfast
from repro.core.simulator import run_sweep

jax.config.update("jax_enable_x64", False)


@pytest.fixture
def tracing():
    metrics.reset()
    metrics.enable(True)
    yield metrics
    metrics.enable(False)
    metrics.reset()


def test_off_records_nothing_and_returns_the_shared_no_op():
    metrics.reset()
    metrics.enable(False)
    a, b = metrics.span("x"), metrics.span("y", id=3)
    assert a is b
    with a:
        metrics.count("n", 5)
    assert metrics.record() == {"spans": [], "counts": []}
    assert not metrics.enabled()


def test_on_spans_nest_with_parents_and_ids(tracing):
    with metrics.span("outer"):
        for i in range(2):
            with metrics.span("inner", id=i):
                metrics.count("n", 2, id=i)
    rec = metrics.record()
    names = [(s["name"], s["id"], s["parent"]) for s in rec["spans"]]
    assert names == [("inner", 0, "outer"), ("inner", 1, "outer"),
                     ("outer", None, None)]
    outer = rec["spans"][-1]
    assert all(outer["t0"] <= s["t0"] <= s["t1"] <= outer["t1"]
               for s in rec["spans"][:2])
    assert [c["n"] for c in rec["counts"]] == [2, 2]
    assert metrics.total("n") == 4 and metrics.total("other") == 0
    json.dumps(rec)                      # the exporter writes it as JSON
    metrics.reset()
    assert metrics.record() == {"spans": [], "counts": []}


def _quad(n, p):
    rng = np.random.default_rng(0)
    C = jnp.asarray(rng.normal(0, 1, (n, p)), jnp.float32)
    return lambda i, x, key: x - C[i] + 0.1 * jax.random.normal(key, x.shape)


def _run(engine):
    n, p, K, every = 3, 8, 30, 9
    topo = binary_tree(n)
    sched = generate_schedule(topo, K, latency=0.3, seed=1)
    x0 = jnp.zeros((p,), jnp.float32)
    held = []

    def eval_fn(x, t):
        held.append(weakref.ref(x))
        return {"x": float(x.sum())}

    def chunk_cb(state, k):
        # the engine lets go of a chunk's iterates once eval_fn has
        # returned: they must not sit in device memory through the next
        assert held and held[-1]() is None

    if engine == "run_rfast":
        run_rfast(topo, sched, _quad(n, p), x0, 0.02, eval_every=every,
                  eval_fn=eval_fn, mode="wavefront", impl="pallas",
                  chunk_cb=chunk_cb)
    else:
        run_sweep(topo, [sched], _quad(n, p), x0, 0.02, seeds=[0],
                  eval_every=every, eval_fn=eval_fn, impl="pallas")
    return K, -(-K // every)


@pytest.mark.parametrize("engine", ["run_rfast", "run_sweep"])
def test_engine_records_plan_init_chunks_and_events(tracing, engine):
    """The spans and counts of one run; the engine holds no chunk's
    iterates past its eval_fn."""
    K, chunks = _run(engine)
    rec = metrics.record()
    by = {}
    for s in rec["spans"]:
        by.setdefault(s["name"], []).append(s)
    assert len(by["rfast.plan"]) == len(by["rfast.init_state"]) == 1
    assert by["rfast.plan"][0]["t1"] <= by["rfast.init_state"][0]["t0"]
    per_chunk = ["rfast.chunk_inputs", "rfast.run_waves",
                 "rfast.read_iterates", "rfast.eval_fn"]
    if engine == "run_rfast":                    # run_sweep has no hook
        per_chunk.append("rfast.chunk_cb")
    assert set(by) == {"rfast.plan", "rfast.init_state", *per_chunk}
    for name in per_chunk:
        assert [s["id"] for s in by[name]] == list(range(chunks)), name
        assert all(s["parent"] is None for s in by[name])
    assert metrics.total("rfast.events") == K
    counts = {}
    for c in rec["counts"]:
        counts.setdefault(c["name"], []).append(c)
    assert set(counts) == {"rfast.events", "rfast.pad_lanes"}
    for name, cs in counts.items():
        assert [c["id"] for c in cs] == list(range(chunks)), name
    # every chunk runs the same number of wave lanes: each one either
    # commits an event or is a padding lane whose writes are skipped
    lanes = {e["n"] + q["n"] for e, q in zip(counts["rfast.events"],
                                             counts["rfast.pad_lanes"])}
    assert len(lanes) == 1
    assert metrics.total("rfast.pad_lanes") > 0


def _compiled_text(program):
    """The optimized HLO text of one engine program at a test size."""
    from repro.core.plan import as_comm_plan
    from repro.core.schedule import build_wavefront_plan
    from repro.core.simulator import (_init_packed, _take,
                                      rfast_wavefront_scan, wave_inputs)
    if program == "eval":
        from repro.configs import get_config
        from repro.data.objectives import make_lm_problem
        prob = make_lm_problem(get_config("rfast-100m").reduced(), 2,
                               seq_len=8, eval_batch=2)
        return prob._eval.lower(prob.x0_flat, prob.eval_tokens,
                                prob.eval_labels).compile().as_text()
    n, p, K = 3, 8, 12
    topo = binary_tree(n)
    sched = generate_schedule(topo, K, latency=0.3, seed=1)
    plan = as_comm_plan(topo)
    H = int(sched.D) + 2
    packed = _init_packed(_quad(n, p), jnp.zeros((p,)),
                          jax.random.PRNGKey(1)[None], S=1, n=n, H=H,
                          e_a=max(1, plan.n_edges_a), p_pad=128)
    if program == "iterates":
        return _take.lower(packed.nodes, ((0, n), 0), p).compile().as_text()
    wf = build_wavefront_plan(sched, plan, H)
    waves = wave_inputs(wf, jax.random.split(jax.random.PRNGKey(0), K))
    runner = rfast_wavefront_scan(plan, _quad(n, p), 0.02,
                                  impl=program.split("/")[1], p_real=p)
    return runner.lower(packed, waves).compile().as_text()


@pytest.mark.parametrize("program,scopes", [
    ("run_waves/pallas", ["rfast.mix", "rfast.grad", "rfast.commit",
                          "rfast.state_write"]),
    ("run_waves/jnp", ["rfast.mix", "rfast.grad", "rfast.commit",
                       "rfast.state_write"]),
    ("iterates", ["rfast.iterates"]),
    ("eval", ["rfast.eval"]),
])
def test_programs_carry_their_scopes(program, scopes):
    """The op-name metadata the device trace is split by; a refactor
    that drops a scope fails here."""
    hlo = _compiled_text(program)
    for scope in scopes:
        assert f"/{scope}/" in hlo, (program, scope)


def test_train_metrics_writes_events_per_s_and_the_record(tmp_path):
    from repro.launch.train import main
    from repro.metrics import read_metrics
    path = tmp_path / "run.jsonl"
    out = main(["--reduced", "--nodes", "2", "--steps", "4", "--seq", "16",
                "--batch-per-node", "2", "--log-every", "2",
                "--scenario", "straggler", "--metrics", str(path)])
    logged = list(read_metrics(str(path)))
    assert [r["step"] for r in logged] == [4, 8]
    assert all(r["events_per_s"] > 0 and "sps" not in r for r in logged)
    rec = [json.loads(line) for line in
           (tmp_path / "run.trace.jsonl").read_text().splitlines()]
    spans = [r["name"] for r in rec if r["kind"] == "span"]
    assert spans.count("rfast.plan") == spans.count("rfast.init_state") == 1
    assert spans.count("rfast.run_waves") == 2
    assert sum(r["n"] for r in rec if r["kind"] == "count"
               and r["name"] == "rfast.events") == out["events"] == 8
    assert not metrics.enabled() and metrics.record()["spans"] == []
