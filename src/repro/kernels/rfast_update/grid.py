"""Fleet-grid R-FAST commit: ONE Pallas launch per wavefront commit.

The per-node commit kernel (:mod:`.kernel`) pays a launch and a
host-side neighbour gather per node per event — ``vmap``-ing it across a
wavefront (or a whole fleet wave) multiplies that overhead by the lane
count.  This module replaces the vmap with a single launch whose grid
spans **(lane, p-tile)**: scalar-prefetched int32 slot tables drive the
``BlockSpec`` index maps, so each grid step gathers its lane's z/g/ρ/ρ̃
block rows directly from the packed state arrays (each row the flat
parameter axis, stored lane-dense as ``(p // LANE, LANE)`` by the
engines) —

* ``z_src``/``go_src`` — the ``S·n·4`` node-state rows (the wavefront
  engines pass the same array twice; the protocol round passes its
  separate z/g leaves),
* ``ri_src``           — the ``H·S·e_a`` delta-history rows,
* ``rb_src``/``ro_src`` — the ``2·S·e_a`` ρ/ρ̃ rows

— instead of materializing ``(B, k, p)`` neighbour stacks host-side.
Per-lane float parameters (a_self, mask, a_out) ride along whole in
SMEM (Mosaic scalar prefetch is int32-only, and a ``(1, k)`` VMEM block
of a ``(B, k)`` table breaks the TPU's (8, 128) tiling rule).

Three execution modes share this entry point (see
:mod:`.dispatch`): ``compiled`` (the real TPU launch), ``interpret``
(the Pallas-interpreter oracle), and ``emulate`` (a jnp twin with
identical gather tables and blend math — the off-TPU default, so CPU
rows measure the grid data flow, not interpreter overhead).  Launches
are shape-specialized and cached through :func:`.dispatch.lookup`.

Commit math per lane b (identical to :func:`.ref.rfast_commit_ref`):

  recv    = Σ_k mask[b,k] · (ri[b,k] − rb[b,k])
  z_half  = z[b] + recv + g_new[b] − g_old[b]
  z'      = a_self[b] · z_half
  ρ_out'  [k] = ro[b,k] + a_out[b,k] · z_half
  ρ̃'     [k] = mask[b,k] · ri[b,k] + (1 − mask[b,k]) · rb[b,k]

Index tables must be pre-clamped into their source's row range by the
caller (:func:`repro.core.schedule.grid_gather_tables`): drop-sentinel
lanes clamp to a valid row, read garbage weighted by zero, and the
caller skips their writes — exactly the inertness contract of the jnp
wavefront path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch
from .kernel import BLK_R, LANE

__all__ = ["commit_grid", "block_pad_width"]


def block_pad_width(p: int, shards: int = 1) -> int:
    """Smallest flat width >= p that tiles into (BLK_R, LANE) blocks.

    With ``shards > 1`` the width is additionally a multiple of
    ``shards`` whose *per-shard* slice still tiles into whole blocks, so
    a parameter axis split over a ``model`` mesh axis hands each device a
    launch-compatible local width (``block_pad_width(p, M) // M``).
    """
    per = BLK_R * LANE
    loc = -(-int(p) // int(shards))
    return int(shards) * (-(-loc // per) * per)


def _grid_kernel(ka: int, ko: int):
    """Kernel body for one (lane, p-tile) grid step.  The five prefetch
    refs (consumed by the index maps) arrive first; the whole per-lane
    float tables (SMEM, read at this step's lane) and the gathered
    (1, BLK_R, LANE) source blocks follow."""

    def kernel(*refs):
        (a_self_ref, mask_ref, a_out_ref,
         z_ref, gn_ref, go_ref, *rest) = refs[5:]
        ri = rest[:ka]
        rb = rest[ka:2 * ka]
        ro = rest[2 * ka:2 * ka + ko]
        z_o, ro_o, rb_o = rest[2 * ka + ko:]

        b = pl.program_id(0)
        f32 = jnp.float32
        z = z_ref[0].astype(f32)
        recv = jnp.zeros_like(z)
        for k in range(ka):
            m = mask_ref[b, k]
            recv += m * (ri[k][0].astype(f32) - rb[k][0].astype(f32))
        z_half = z + recv + gn_ref[0].astype(f32) - go_ref[0].astype(f32)

        z_o[0] = (a_self_ref[b] * z_half).astype(z_o.dtype)
        for k in range(ko):
            ro_o[0, k] = (ro[k][0].astype(f32)
                          + a_out_ref[b, k] * z_half).astype(ro_o.dtype)
        for k in range(ka):
            m = mask_ref[b, k]
            rb_o[0, k] = (m * ri[k][0].astype(f32)
                          + (1.0 - m) * rb[k][0].astype(f32)
                          ).astype(rb_o.dtype)

    return kernel


def _z_map(b, t, iz, ig, iri, irb, iro):
    return (iz[b], t, 0)


def _g_map(b, t, iz, ig, iri, irb, iro):
    return (ig[b], t, 0)


def _gn_map(b, t, iz, ig, iri, irb, iro):
    return (b, t, 0)


def _ri_map(k, b, t, iz, ig, iri, irb, iro):
    return (iri[b, k], t, 0)


def _rb_map(k, b, t, iz, ig, iri, irb, iro):
    return (irb[b, k], t, 0)


def _ro_map(k, b, t, iz, ig, iri, irb, iro):
    return (iro[b, k], t, 0)


def _out_z_map(b, t, iz, ig, iri, irb, iro):
    return (b, t, 0)


def _out_k_map(b, t, iz, ig, iri, irb, iro):
    return (b, 0, t, 0)


def _build_launch(B: int, T: int, ka: int, ko: int, dtypes: tuple,
                  interpret: bool):
    """Construct the (B, T)-grid pallas_call for one shape signature."""
    z_dt, ro_dt, rb_dt = dtypes
    R = T * BLK_R
    blk = lambda idx_fn: pl.BlockSpec((1, BLK_R, LANE), idx_fn)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        smem, smem, smem,                     # a_self (B,), mask, a_out
        blk(_z_map), blk(_gn_map), blk(_g_map),
    ]
    in_specs += [blk(functools.partial(_ri_map, k)) for k in range(ka)]
    in_specs += [blk(functools.partial(_rb_map, k)) for k in range(ka)]
    in_specs += [blk(functools.partial(_ro_map, k)) for k in range(ko)]
    out_specs = (
        blk(_out_z_map),
        pl.BlockSpec((1, ko, BLK_R, LANE), _out_k_map),
        pl.BlockSpec((1, ka, BLK_R, LANE), _out_k_map),
    )
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B, T),
        in_specs=in_specs, out_specs=out_specs)
    return pl.pallas_call(
        _grid_kernel(ka, ko), grid_spec=gs,
        out_shape=(jax.ShapeDtypeStruct((B, R, LANE), z_dt),
                   jax.ShapeDtypeStruct((B, ko, R, LANE), ro_dt),
                   jax.ShapeDtypeStruct((B, ka, R, LANE), rb_dt)),
        interpret=interpret, name="rfast_commit_grid")


def _emulate(idx_z, idx_g, idx_ri, idx_rb, idx_ro, a_self, mask, a_out,
             z_src, g_new, go_src, ri_src, rb_src, ro_src):
    """jnp twin of the grid kernel: same flat-row gather tables, same
    masked blend — an XLA program per launch instead of a kernel, with
    bit-matching semantics (fp32 accumulation over the tiny k axis)."""
    f32 = jnp.float32
    bc = lambda a: a.astype(f32).reshape(a.shape + (1,) * (g_new.ndim - 1))
    z = z_src[idx_z].astype(f32)                       # (B, *row)
    go = go_src[idx_g].astype(f32)
    ri = ri_src[idx_ri].astype(f32)                    # (B, ka, *row)
    rb = rb_src[idx_rb].astype(f32)
    ro = ro_src[idx_ro].astype(f32)
    m = bc(mask)
    recv = jnp.sum(m * (ri - rb), axis=1)
    z_half = z + recv + g_new.astype(f32) - go
    z_o = (bc(a_self) * z_half).astype(z_src.dtype)
    ro_o = (ro + bc(a_out) * z_half[:, None]).astype(ro_src.dtype)
    rb_o = (m * ri + (1.0 - m) * rb).astype(rb_src.dtype)
    return z_o, ro_o, rb_o


def commit_grid(idx_z, idx_g, idx_ri, idx_rb, idx_ro,
                a_self, mask, a_out,
                z_src, g_new, go_src, ri_src, rb_src, ro_src,
                *, mode: str | None = None):
    """One fused commit over B lanes gathered from flat source arrays.

    Args:
      idx_z / idx_g: (B,) int32 rows of ``z_src`` / ``go_src``.
      idx_ri: (B, ka) int32 rows of ``ri_src`` (delivered ρ payloads).
      idx_rb: (B, ka) int32 rows of ``rb_src`` (receiver ρ̃ buffers).
      idx_ro: (B, ko) int32 rows of ``ro_src`` (sender ρ running sums).
      a_self: (B,); mask: (B, ka) 0/1; a_out: (B, ko) floats.
      z_src/go_src/ri_src/rb_src/ro_src: (rows, *row) sources, each row
        either flat ``(Pf,)`` or lane-dense ``(Pf // LANE, LANE)`` —
        aliasing is fine (the engines pass one array several times).
        Lane-dense rows are the kernel's own block layout; flat rows
        cost the TPU a relayout copy of every source.
      g_new: (B, *row) — this lane's fresh gradient, indexed by lane.
      mode: dispatch mode (see :mod:`.dispatch`); None autodetects.
        ``compiled``/``interpret`` require ``Pf`` to be a multiple of
        ``BLK_R·LANE`` (pre-pad with :func:`block_pad_width` — the zero
        tail is inert under the linear commit); ``emulate`` takes any Pf.

    Returns ``(z' (B, *row), rho_out' (B, ko, *row), rho_buf' (B, ka,
    *row))`` in the respective source dtypes.  All index tables are clamped into
    their source's row range (the caller must skip the writes of
    drop-sentinel lanes).
    """
    if mode is None:
        mode = dispatch.resolve_mode(None)
    if mode not in dispatch.MODES:
        raise ValueError(f"mode must be one of {dispatch.MODES}, "
                         f"got {mode!r}")
    B, ka = idx_ri.shape
    ko = idx_ro.shape[1]
    row = g_new.shape[1:]
    Pf = math.prod(row)
    i32 = lambda a, hi: jnp.clip(a.astype(jnp.int32), 0, hi - 1)
    idx_z = i32(idx_z, z_src.shape[0])
    idx_g = i32(idx_g, go_src.shape[0])
    idx_ri = i32(idx_ri, ri_src.shape[0])
    idx_rb = i32(idx_rb, rb_src.shape[0])
    idx_ro = i32(idx_ro, ro_src.shape[0])
    dtypes = (z_src.dtype, ro_src.dtype, rb_src.dtype)

    key = ("commit_grid", mode, B, Pf, ka, ko,
           z_src.shape[0], go_src.shape[0], ri_src.shape[0],
           rb_src.shape[0], ro_src.shape[0],
           tuple(str(d) for d in dtypes), str(g_new.dtype))
    if mode == "emulate":
        fn = dispatch.lookup(key, lambda: _emulate)
        return fn(idx_z, idx_g, idx_ri, idx_rb, idx_ro,
                  a_self, mask, a_out, z_src, g_new, go_src,
                  ri_src, rb_src, ro_src)

    if Pf % (BLK_R * LANE):
        raise ValueError(
            f"mode={mode!r} needs the flat width to tile into "
            f"(BLK_R={BLK_R}, LANE={LANE}) blocks; got Pf={Pf} — pad to "
            f"block_pad_width(Pf)={block_pad_width(Pf)} first")
    T = Pf // (BLK_R * LANE)
    R = T * BLK_R
    launch = dispatch.lookup(
        key, lambda: _build_launch(B, T, ka, ko, dtypes,
                                   interpret=(mode == "interpret")))
    b3 = lambda a: a.reshape(a.shape[0], R, LANE)
    f32 = jnp.float32
    z_o, ro_o, rb_o = launch(
        idx_z, idx_g, idx_ri, idx_rb, idx_ro,
        a_self.astype(f32), mask.astype(f32), a_out.astype(f32),
        b3(z_src), b3(g_new), b3(go_src),
        *([b3(ri_src)] * ka), *([b3(rb_src)] * ka), *([b3(ro_src)] * ko))
    return (z_o.reshape((B,) + row), ro_o.reshape((B, ko) + row),
            rb_o.reshape((B, ka) + row))
