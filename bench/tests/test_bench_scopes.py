"""The program's scopes and spans in a reduced trace (``bench/scopes.py``):
on a hand-made trace, on the trace recorded on a TPU v5e before the
engine had scopes (where they change nothing), and on one recorded with
them."""
import re

import pytest

from bench import scopes, spec, trace_reduce

MS = 1_000_000      # ns


def _op(instr, scope=None):
    meta = "" if scope is None else \
        f', metadata={{op_name="jit(run_waves)/while/body/{scope}/x"}}'
    return f"%{instr} = f32[8] {instr.split('.')[0]}(f32[8] %p){meta}"


def _raw():
    """One device, a 100 ms window (chunk 0-80 ms, eval 80-100 ms): a
    loop 10-70 holding the gradient 10-30, an XLA copy 30-40 (no
    scope), the commit 40-50 and a state write 50-60; the eval 85-95."""
    dev = [("%while.1 = (s32[]) while((s32[]) %t)", 10 * MS, 60 * MS),
           (_op("fusion.1", "rfast.grad"), 10 * MS, 20 * MS),
           (_op("copy.2"), 30 * MS, 10 * MS),
           (_op("custom-call.3", "rfast.commit"), 40 * MS, 10 * MS),
           (_op("fusion.4", "rfast.state_write"), 50 * MS, 10 * MS),
           (_op("fusion.5", "rfast.eval"), 85 * MS, 10 * MS)]
    spans = [("bench.chunk", 0, 80 * MS), ("bench.eval", 80 * MS, 20 * MS)]
    return {"devices": {"/device:TPU:0": dev}, "spans": spans}


# program spans on the trace's clock (seconds): the chunk's inputs and
# dispatch, then the eval hook that holds the harness's eval
PROGRAM = [("rfast.chunk_inputs", 0.000, 0.004),
           ("rfast.run_waves", 0.004, 0.009),
           ("rfast.eval_fn", 0.079, 0.100)]


@pytest.fixture
def trace():
    return trace_reduce.reduce(_raw())


@pytest.mark.parametrize("name,scope", [
    ("fusion.1", "rfast.grad"), ("custom-call.3", "rfast.commit"),
    ("copy.2", None), ("while.1", None)])
def test_scope_of_reads_the_innermost_op_name_scope(name, scope):
    assert scopes.scope_of(_op(name, scope)) == scope
    assert scopes.scope_of(_op(name, "rfast.mix/" + (scope or "x"))) \
        == (scope or "rfast.mix")


def test_scope_seconds_cover_the_leaf_ops(trace):
    per = {s: scopes.scope_seconds(trace, s)[0] for s in
           ("rfast.grad", "rfast.commit", "rfast.state_write",
            "rfast.eval", "rfast.mix")}
    assert per == pytest.approx({"rfast.grad": 0.020, "rfast.commit": 0.010,
                                 "rfast.state_write": 0.010,
                                 "rfast.eval": 0.010, "rfast.mix": 0.0})
    # the loop's own event is not a leaf; the copy carries no scope
    assert scopes.unscoped_seconds(trace) == pytest.approx([0.010])
    leaves = sum(e - s for _, s, e, _, leaf in trace.ops[0] if leaf)
    assert sum(per.values()) + scopes.unscoped_seconds(trace)[0] \
        == pytest.approx(leaves)


def test_top_ops_carry_their_scope(trace):
    top = dict(scopes.top_ops(trace))
    assert top["%fusion.1 fusion@rfast.grad"] == pytest.approx(0.020)
    assert top["%copy.2 copy"] == pytest.approx(0.010)
    assert top["%while.1 while"] == pytest.approx(0.010)     # 60 - 50


def test_idle_gaps_name_the_program_span(trace):
    gaps = scopes.idle_gaps(trace, PROGRAM)
    # 70-85 (the eval hook opens at 79), 0-10 (inputs 0-4, dispatch
    # 4-9: the inputs overlap it less), 95-100 (inside the eval hook)
    assert gaps == [["bench.chunk>rfast.eval_fn", pytest.approx(0.015)],
                    ["bench.chunk>rfast.run_waves", pytest.approx(0.010)],
                    ["bench.eval>rfast.eval_fn", pytest.approx(0.005)]]
    assert scopes.idle_gaps(trace, []) == trace.idle_gaps()


@pytest.fixture(scope="module")
def unscoped_recording():
    """The first recorded trace: the engine before it had scopes."""
    raw = trace_reduce.load_raw(
        spec.BENCH_DIR / "tests" / "data" / "trace_v5e_rfast100m_chunk.json.gz")
    return trace_reduce.reduce(raw)


def test_labels_unchanged_without_program_names(unscoped_recording):
    tr = unscoped_recording
    assert not any(scopes.scope_of(n) for n, *_ in tr.ops[0])
    assert scopes.top_ops(tr) == tr.top_ops()
    assert scopes.idle_gaps(tr, []) == tr.idle_gaps()


# --------------------------------------------------------------------- #
# a trace recorded on a TPU v5e with the engine's scopes: one window
# chunk (20 events) and its eval of rfast100m.n2.straggler.tok512; each
# name is the op's HLO text up to its operands, the commit kernel's
# custom-call target, and the op_name (cut after the rfast scope) of the
# compiled program that ran; ``program_spans`` are the engine's host
# spans on the trace's clock
# --------------------------------------------------------------------- #
SCOPES = ["rfast.mix", "rfast.grad", "rfast.commit", "rfast.state_write",
          "rfast.iterates", "rfast.eval"]
KERNEL = r'custom_call_target="tpu_custom_call"'   # the commit readers'


@pytest.fixture(scope="module")
def scoped():
    raw = trace_reduce.load_raw(
        spec.BENCH_DIR / "tests" / "data"
        / "trace_v5e_rfast100m_scoped.json.gz")
    prog = [(n, s * 1e-9, (s + d) * 1e-9) for n, s, d in raw["program_spans"]]
    return raw, trace_reduce.reduce(raw), prog


def test_recorded_scopes_and_unscoped_cover_the_leaf_ops(scoped):
    _, tr, _ = scoped
    per = [scopes.scope_seconds(tr, s)[0] for s in SCOPES]
    assert all(t > 0 for t in per), dict(zip(SCOPES, per))
    leaves = sum(e - s for _, s, e, _, leaf in tr.ops[0] if leaf)
    assert sum(per) + scopes.unscoped_seconds(tr)[0] == \
        pytest.approx(leaves, rel=1e-9)


def test_recorded_gap_labels_start_with_a_harness_span(scoped):
    _, tr, prog = scoped
    gaps = scopes.idle_gaps(tr, prog)
    assert len(gaps) == 10
    assert all(label.startswith("bench.") for label, _ in gaps)
    assert [s for _, s in gaps] == [s for _, s in tr.idle_gaps()]
    assert any(">rfast." in label for label, _ in gaps)


def test_recorded_top_ops_carry_scopes(scoped):
    _, tr, _ = scoped
    top = scopes.top_ops(tr)
    assert [t for _, t in top] == pytest.approx([t for _, t in tr.top_ops()])
    assert sum("@rfast." in n for n, _ in top) >= 5


def test_recorded_kernel_name_keeps_the_commit_readers_match(scoped):
    """The kernel is named ``rfast_commit_grid``; the pattern the commit
    readers search for still finds exactly its 20 launches."""
    raw, tr, _ = scoped
    hits = [n for n, _, _ in raw["devices"]["/device:TPU:0"]
            if re.search(KERNEL, n)]
    assert len(hits) == 20
    assert all(n.startswith("%rfast_commit_grid") for n in hits)
    assert all(scopes.scope_of(n) == "rfast.commit" for n in hits)
    commit = scopes.scope_seconds(tr, "rfast.commit")[0]
    assert 0.5 * commit < tr.op_seconds(KERNEL)[0] < commit
