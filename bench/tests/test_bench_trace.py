"""The reduction from a profiler trace to per-layer numbers: on a
hand-made trace, and on a small trace recorded on a TPU v5e."""
import pytest

from bench import spec, trace_reduce

MS = 1_000_000      # ns
KERNEL = r'custom_call_target="tpu_custom_call"'


def _raw():
    """Two devices, a 100 ms window (chunk 0-80 ms, eval 80-100 ms).
    Device 0: a loop 10-60 holding a gradient fusion 10-40, the commit
    kernel 40-50 and an all-gather 50-60, which a fusion 58-62 overlaps;
    an eval op 85-90.  Device 1: the gradient 10-60, the commit 60-70."""
    kern = ('%closed_call.3 = (f32[1,8,128]) custom-call(f32[1] %a), '
            'custom_call_target="tpu_custom_call"')
    dev0 = [("%while.1 = (s32[]) while((s32[]) %t)", 10 * MS, 50 * MS),
            ("%fusion.1 = f32[8] fusion(f32[8] %p)", 10 * MS, 30 * MS),
            (kern, 40 * MS, 10 * MS),
            ("%all-gather.2 = f32[8] all-gather(f32[2] %x)", 50 * MS, 10 * MS),
            ("%fusion.7 = f32[8] fusion(f32[8] %all-gather.2)", 58 * MS,
             4 * MS),
            ("%fusion.9 = f32[] fusion(f32[8] %r)", 85 * MS, 5 * MS)]
    dev1 = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 10 * MS, 50 * MS),
            (kern, 60 * MS, 10 * MS)]
    spans = [("bench.chunk", 0, 80 * MS), ("bench.eval", 80 * MS, 20 * MS)]
    return {"devices": {"/device:TPU:1": dev1, "/device:TPU:0": dev0},
            "spans": spans}


def test_busy_union_and_idle_share():
    tr = trace_reduce.reduce(_raw())
    assert tr.window == pytest.approx((0.0, 0.1))
    # device 0: 10-62 and 85-90 -> 57 ms; device 1: 10-70 -> 60 ms
    assert tr.busy_s() == pytest.approx([0.057, 0.060])
    assert tr.idle_frac() == pytest.approx(1 - 0.0585 / 0.1)


def test_kernel_time_counts_leaf_operations():
    tr = trace_reduce.reduce(_raw())
    assert tr.op_seconds(KERNEL) == pytest.approx([0.010, 0.010])
    # the loop's own event encloses the gradient but is not a leaf
    assert tr.op_seconds(r"^%while") == [0, 0]


def test_exposed_collective_time():
    tr = trace_reduce.reduce(_raw())
    # 10 ms of all-gather, 2 of them under fusion.7 (which reads the
    # gather's result: an operand is not the instruction; the loop around
    # both does not count as another operation)
    assert tr.exposed_seconds(r"^%all-gather") == pytest.approx([0.008, 0.0])


def test_self_time_and_breakdown():
    tr = trace_reduce.reduce(_raw())
    top = dict(tr.top_ops())
    assert top["%fusion.1 fusion"] == pytest.approx(0.040)
    # the loop's 50 ms less its children's 30 + 10 + 10
    assert top["%while.1 while"] == pytest.approx(0.0)
    assert top["%closed_call.3 custom-call"] == pytest.approx(0.010)


def test_idle_gaps_are_labelled_by_the_open_span():
    tr = trace_reduce.reduce(_raw())
    gaps = tr.idle_gaps()
    assert gaps[0] == ["bench.chunk", pytest.approx(0.023)]   # 62-85
    assert gaps[1] == ["bench.chunk", pytest.approx(0.010)]   # 0-10
    assert gaps[2] == ["bench.eval", pytest.approx(0.010)]    # 90-100


def test_no_device_op_gives_no_trace():
    raw = _raw()
    raw["devices"] = {k: [] for k in raw["devices"]}
    assert trace_reduce.reduce(raw) is None


# --------------------------------------------------------------------- #
# a trace recorded on a TPU v5e: ``trace_reduce.raw_events`` of a
# ``--trace 1`` run of rfast100m.n2.straggler.tok512, cut to its first
# chunk (20 events) and that chunk's eval, operation names shortened by
# ``short_name`` (the commit kernel's keeping its custom-call target)
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def recorded():
    raw = trace_reduce.load_raw(
        spec.BENCH_DIR / "tests" / "data" / "trace_v5e_rfast100m_chunk.json.gz")
    return raw, trace_reduce.reduce(raw)


def test_recorded_window_and_busy_union(recorded):
    import numpy as np
    raw, tr = recorded
    spans = {n: (s, s + d) for n, s, d in raw["spans"]}
    assert tr.window == pytest.approx((spans["bench.chunk"][0] * 1e-9,
                                       spans["bench.eval"][1] * 1e-9))
    # the busy union, counted independently on a microsecond grid
    lo = int(spans["bench.chunk"][0] // 1000)
    grid = np.zeros(int(spans["bench.eval"][1] // 1000) - lo + 1, bool)
    for _, s, d in raw["devices"]["/device:TPU:0"]:
        grid[max(0, int(s // 1000) - lo):max(0, int((s + d) // 1000) - lo)] = 1
    assert tr.busy_s()[0] == pytest.approx(grid.sum() * 1e-6, abs=2e-3)
    assert 0.0 < tr.idle_frac() < 0.05


def test_recorded_commit_kernel(recorded):
    from bench import counters
    raw, tr = recorded
    launches = [d for n, _, d in raw["devices"]["/device:TPU:0"]
                if "tpu_custom_call" in n]
    assert len(launches) == 20                    # one per event
    assert tr.op_seconds(KERNEL) == pytest.approx([sum(launches) * 1e-9])
    need = 20 * counters.commit_bytes(124_668_672, 1, 1)
    share = need / 819e9 / tr.op_seconds(KERNEL)[0]
    assert 0.5 < share < 1.0
    assert tr.exposed_seconds(r"^%all-gather") == [0]


def test_recorded_breakdown(recorded):
    _, tr = recorded
    top = tr.top_ops()
    assert len(top) == 10
    assert sum(t for _, t in top) <= tr.busy_s()[0] + 1e-9
    assert any("custom_call_target" in n for n, _ in top)
    labels = {label for label, _ in tr.idle_gaps()}
    assert labels <= {"bench.chunk", "bench.wait", "bench.eval"}
