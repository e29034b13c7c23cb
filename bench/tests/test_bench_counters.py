"""The shape counters give hand-worked numbers for the configuration and
for a tied, non-parametric-LayerNorm test configuration."""
import json

import numpy as np
import pytest

from bench import counters, spec, traffic
from bench.configs import dense_decoder


def _cfg(name):
    with open(spec.BENCH_DIR / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_rfast_100m_sizes():
    cfg = _cfg("rfast-100m")
    # per layer: q 768*768, k and v 768*256 each, o 768*768, mlp 3*768*2048
    per_layer = 589_824 + 2 * 196_608 + 589_824 + 4_718_592
    assert counters.matmul_params(cfg) == 12 * per_layer + 768 * 32_000
    # plus the embedding and 25 norm scales of 768
    assert dense_decoder.n_params(cfg) == 124_668_672
    assert dense_decoder.flat_width(cfg) == 124_668_672
    assert counters.flops_per_token(cfg, 128) == \
        6 * 100_073_472 + 12 * 12 * 128 * 768


def test_tied_nonparam_ln_sizes():
    with open(spec.BENCH_DIR / "tests" / "data" / "configs"
              / "tiny-mha-tied.json") as f:
        cfg = json.load(f)
    # per layer: q, k, v, o 64*64 each (MHA), mlp 3*64*128; no norm
    # scales, and the tied embedding is the head
    per_layer = 4 * 64 * 64 + 3 * 64 * 128
    assert counters.matmul_params(cfg) == 2 * per_layer + 64 * 512
    assert dense_decoder.n_params(cfg) == 2 * per_layer + 64 * 512
    assert dense_decoder.flat_width(cfg) == 2 * per_layer + 64 * 512
    assert counters.flops_per_token(cfg, 16) == \
        6 * (2 * per_layer + 64 * 512) + 12 * 2 * 16 * 64


def test_binary_tree_of_two_state_rows_and_commit_bytes():
    W, A = traffic.tree_weights(2)
    np.testing.assert_allclose(W, [[1.0, 0.0], [0.5, 0.5]])
    np.testing.assert_allclose(A, [[1.0, 0.5], [0.0, 0.5]])
    assert counters.degrees(A) == (1, 1)
    assert counters.n_edges(A) == 1
    # D_max = 2 -> H = 4: 4n + 2 E_A + H (n + E_A) = 8 + 2 + 12
    assert counters.state_rows(2, 1, 4) == 22
    # 4 + 3 ka + 2 ko = 9 rows of the width, float32
    assert counters.commit_bytes(1000, 1, 1) == 9 * 1000 * 4


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 19])
def test_straggler_schedule_is_capped_by_D_max(seed):
    with open(spec.BENCH_DIR / "traffic" / "straggler.dmax2.json") as f:
        tr = json.load(f)
    W, A = traffic.tree_weights(2)
    s = traffic.realize(tr, W, A, 2000, seed)
    assert s.D == 2
    assert 0.75 < (s.agent == 0).mean() < 0.85   # node 1 computes 4x slower
    k = np.arange(2000)
    reader_w = s.agent == 1              # node 1 reads v_0 on W-edge 0
    assert (k[reader_w] - s.stamp_v[reader_w, 0] <= 2).all()
    assert (np.diff(s.stamp_rho[:, 0]) >= 0).all()


@pytest.mark.parametrize("mix", ["straggler.dmax1", "straggler.dmax2"])
@pytest.mark.parametrize("seed", [5, 2 ** 31 + 19])
def test_traffic_copy_equals_the_program_scenario(mix, seed):
    """``traffic.realize`` is a frozen copy of the program's scenario
    clock: for the cells' mixes it gives the program's ``straggler``
    schedule, event for event, and the program's binary-tree weights."""
    import dataclasses

    from repro.core.scenario import get_scenario
    from repro.core.topology import get_topology

    with open(spec.BENCH_DIR / "traffic" / f"{mix}.json") as f:
        tr = json.load(f)
    topo = get_topology("binary_tree", 2)
    W, A = traffic.tree_weights(2)
    np.testing.assert_array_equal(W, topo.W)
    np.testing.assert_array_equal(A, topo.A)
    ours = traffic.realize(tr, W, A, 3000, seed)
    scen = dataclasses.replace(get_scenario("straggler", 2),
                               D_max=tr["D_max"])
    theirs = scen.realize(topo, 3000, seed=seed).schedule
    for field in ("agent", "stamp_v", "stamp_rho", "times"):
        np.testing.assert_array_equal(getattr(ours, field),
                                      getattr(theirs, field), err_msg=field)
    assert (ours.D, ours.T) == (theirs.D, theirs.T)
