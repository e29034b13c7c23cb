"""Compile the fleet-grid commit kernel with the TPU's own compiler, for a
described (not attached) v5e, at the widths the chip path runs.

No chip is needed: ``jax.experimental.topologies`` describes a ``v5e:2x2``
and ``jit(...).lower(shapes).compile()`` runs Mosaic and XLA:TPU on it, so
a block that breaks the TPU's tiling rules, a kernel that needs more fast
memory than it may use, or a program that does not fit the chip's HBM
fails here.  Nothing runs: these tests say nothing about results or
times.  The topology is described inside a module fixture, never at
import: only the worker that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.rfast_update.grid import block_pad_width, commit_grid
from repro.kernels.rfast_update.kernel import LANE

# rfast-100m's flat parameter count (make_lm_problem, pad_to=128)
P_100M = 124_668_672


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no TPU library
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _commit_structs(sharding, *, B, width, ka, ko, node_rows, hist_rows,
                    rho_rows):
    """Shapes of one wave's commit_grid call over lane-dense sources."""
    row = (width // LANE, LANE)
    i = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
    return (i(B), i(B), i(B, ka), i(B, ka), i(B, ko),
            f(B), f(B, ka), f(B, ko),
            f(node_rows, *row), f(B, *row), f(hist_rows, *row),
            f(rho_rows, *row))


def _commit(iz, ig, iri, irb, iro, a_self, mask, a_out, nodes, g_new,
            hist, rho2):
    # the engine's call: nodes serve as both z and g_old sources, rho2 as
    # both the ρ̃ buffers and the ρ running sums
    return commit_grid(iz, ig, iri, irb, iro, a_self, mask, a_out,
                       nodes, g_new, nodes, hist, rho2, rho2,
                       mode="compiled")


@pytest.mark.parametrize("case", [
    # one chip, full width: n=2 binary tree (wave width 2, one edge)
    dict(B=2, width=block_pad_width(P_100M), ka=1, ko=1,
         node_rows=8, hist_rows=3, rho_rows=2),
    # a wide fleet wave (128 lanes) at a 64-way shard of the full width
    dict(B=128, width=block_pad_width(P_100M, 64) // 64, ka=2, ko=2,
         node_rows=4 * 64, hist_rows=4 * 63, rho_rows=2 * 63),
], ids=["full_width_one_chip", "wide_wave_b128"])
def test_commit_grid_compiles_for_v5e(topo, case):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(_commit).lower(
        *_commit_structs(one_chip, **case)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # lane-dense sources feed the kernel as they are: no relayout copy
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_commit_grid_compiles_per_shard_on_2x2_mesh(topo):
    """--param-shards 4: each device commits its 31,195,136-wide shard of
    the full width inside the mesh engine's shard_map region."""
    width = block_pad_width(P_100M, 4)
    assert width // 4 == 31_195_136
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(1, 4),
                             ("data", "model"))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(None, "model", None))
    B, ka, ko = 2, 2, 2
    structs = list(_commit_structs(rep, B=B, width=width, ka=ka, ko=ko,
                                   node_rows=16, hist_rows=21, rho_rows=6))
    structs[8:] = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rows)
                   for s in structs[8:]]
    tables = (P(),) * 8
    src = (P(None, "model", None),) * 4
    out = (P(None, "model", None), P(None, None, "model", None),
           P(None, None, "model", None))
    fn = jax.shard_map(_commit, mesh=mesh, in_specs=tables + src,
                       out_specs=out, check_vma=False)
    compiled = jax.jit(fn).lower(*structs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 16 * 2**30


def _wave_structs(sharding, *, N, E, H, B, kw, ka, ko, width, waves=20):
    """Shapes of one chunk of ``run_waves``: the packed state and the
    wave tables."""
    from repro.core.simulator import PackedState, _WaveInputs
    row = (width // LANE, LANE)
    s = lambda dt, *sh: jax.ShapeDtypeStruct(sh, dt, sharding=sharding)
    f = lambda *sh: s(jnp.float32, *sh)
    i = lambda *sh: s(jnp.int32, waves, B, *sh)
    fw = lambda *sh: s(jnp.float32, waves, B, *sh)
    state = PackedState(nodes=f(N, 4, *row), rho2=f(2 * E, *row),
                        v_hist=f(H, N, *row), rho_hist=f(H, E, *row))
    return state, _WaveInputs(
        agent=i(), wslot=i(), w_self=fw(), a_self=fw(), rslot_v=i(kw),
        src_v=i(kw), w_in=fw(kw), rslot_rho=i(ka), hist_epos=i(ka),
        a_val=fw(ka), rho_gidx=i(ko + ka), out_wt=fw(ko),
        keys=s(jnp.uint32, waves, B, 2))


@pytest.mark.parametrize("case", ["full_width_one_chip", "wide_wave_b128"])
def test_state_writes_compile_in_place_for_v5e(topo, case):
    """The wave's row writes compile to in-place updates: no copy of a
    whole state array, no loop but the chunk's scan (and, on a wide
    wave, its lane loop), no scatter, and no write that selects between
    the new row and the old one it read back."""
    import re
    from jax.sharding import SingleDeviceSharding
    from repro.core import binary_tree
    from repro.core.plan import build_comm_plan
    from repro.core.simulator import (_UNROLL_LANES, rfast_sweep_scan,
                                      rfast_wavefront_scan)
    one_chip = SingleDeviceSharding(topo.devices[0])
    grad = lambda i, x, key: 0.5 * x
    if case == "full_width_one_chip":
        # the benchmark cell: n=2 binary tree, H=3, one lane per wave
        plan = build_comm_plan(binary_tree(2))
        run = rfast_wavefront_scan(plan, grad, 0.1, impl="pallas",
                                   interpret=False, p_real=P_100M)
        shape = dict(N=2, E=max(1, plan.n_edges_a), H=3, B=1, kw=plan.kw,
                     ka=plan.ka, ko=plan.ko, width=block_pad_width(P_100M))
    else:
        run = rfast_sweep_scan(grad, 0.1, ko=2, n_per_lane=4,
                               impl="pallas", interpret=False)
        shape = dict(N=4 * 64, E=63, H=4, B=128, kw=2, ka=2, ko=2,
                     width=block_pad_width(P_100M, 512) // 512)
    state, waves = _wave_structs(one_chip, **shape)
    text = run.lower(state, waves).compile().as_text()

    whole = {tuple(a.shape) for a in state}
    copies = [tuple(int(d) for d in m.split(",")) for m in re.findall(
        r"= f32\[([0-9,]+)\]\{[^}]*\} copy\(", text)]
    assert not [c for c in copies if c in whole], copies
    loops = 1 + (shape["B"] > _UNROLL_LANES)
    assert len(re.findall(r" while\(", text)) == loops
    assert " scatter(" not in text
    assert not re.findall(r"= f32\[[^\]]*\]\{[^}]*\} select\([^)]*"
                          r"dynamic-slice", text)
