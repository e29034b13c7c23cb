"""Global-view (Algorithm 2) R-FAST simulator.

Executes the *exact* R-FAST recursion under an arbitrary realized
asynchronous schedule (activations + per-edge payload stamps produced by
``schedule.py``), entirely in JAX with a ``lax.scan``.  The simulator is
the faithful-reproduction engine: every update is S.1–S.5 of Algorithm 2
verbatim — the formulas themselves live in :mod:`repro.core.protocol`;
this engine owns only the *delayed-read* realization (history buffers
indexed by payload stamps) over a :class:`repro.core.plan.CommPlan`.

Two execution modes share one state layout:

* ``mode="wavefront"`` (default) — the schedule is compiled host-side
  (:func:`repro.core.schedule.build_wavefront_plan`) into groups of
  events with distinct agents whose payload stamps predate the group;
  each scan step vmaps the per-agent update across one group and commits
  **O(p) delta rows** into the histories (``v_hist[slot, agent]`` /
  ``rho_hist[slot, out-edge]``) instead of full-array snapshots.  Stale
  reads are pre-resolved to ring slots by the host pass, so the device
  never materializes an O(n·p) snapshot per event.
* ``mode="event"`` — the original one-event-per-step engine with full
  ``(H, n, p)`` / ``(H, E_A, p)`` snapshot commits; kept as the oracle
  the wavefront path is tested against.

A third entry point batches at the *experiment* level: :func:`run_sweep`
runs a fleet of S independent (topology, schedule, seed) experiments as
ONE compiled program — per-lane plans are degree-normalized, padded to
shared wave maxima, stacked into dense ``(S, ...)`` arrays
(``schedule.pad_plan`` / ``stack_plans``), and then *flattened*
(``schedule.flatten_plans``) into one wider single-experiment program:
the fleet state is the ``(S, n, 4, p)`` lane stack realized as
block-concatenated ``(S·n, 4, p)`` rows, and the scan body is the
ordinary wave step at width S·B — so the fleet pays ONE compile, not S.
Each lane reproduces its individual :func:`run_rfast` trajectory to fp32
tolerance.

State representation (flat parameter vectors, ``p`` = dimension):

* ``x, v, z, g_prev`` — ``(n, p)`` per-node model / intermediate / tracking /
  last-sampled-gradient variables.
* ``rho``       — ``(E_A, p)`` running sums ρ_{ji} held at the *sender* of
  each A-edge; ``rho_buf`` — the receiver's buffers ρ̃_{ij}.
* ``v_hist`` / ``rho_hist`` — history rings (``H ≥ D+2``) realizing the
  delayed reads ``v_j^{k-d}``, ``ρ^{k-d}``; snapshot-indexed in event
  mode, per-writer-counter delta-indexed in wavefront mode.

Mass-conservation invariant (Lemma 3), checked in tests under arbitrary
delay/loss schedules::

    Σ_i z_i + Σ_e (ρ_e − ρ̃_e)  ==  Σ_i ∇f_i(x_i^k; ζ_i^k)
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import metrics as tracing
from ..kernels.rfast_update import dispatch
from ..kernels.rfast_update.grid import block_pad_width, commit_grid
from ..kernels.rfast_update.kernel import LANE

SUBLANES = 8        # rows of one fp32 TPU tile: (SUBLANES, LANE)
_UNROLL_LANES = 8   # wider waves write their rows in a fori_loop
from ..kernels.rfast_update.ops import rfast_commit
from .paramvec import GradProvider, as_grad_fn
from .plan import CommPlan, as_comm_plan, pad_comm_plan
from .runtime_sharded import packed_sweep_specs
from .protocol import consensus_mix, descent_step, mailbox_merge, tracking_step
from .schedule import (Schedule, build_wavefront_plan, concat_plans,
                       flatten_plans, grid_gather_tables, pad_plan,
                       slice_plan, stack_plans)
from .topology import Topology

__all__ = ["RFASTState", "PackedState", "init_state", "zeros_state",
           "pack_state", "unpack_state", "wave_inputs", "rfast_scan",
           "rfast_wavefront_scan", "rfast_sweep_scan", "run_rfast",
           "run_sweep", "migrate_state", "run_epochs", "run_sweep_epochs",
           "tracked_mass"]

GradFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]
# grad_fn(node_id, x_node, rng_key) -> gradient, all traced.
# Every engine entry point also accepts a paramvec.GradProvider (e.g.
# LogisticProblem, LMProblem): the objective is resolved ONCE through
# paramvec.as_grad_fn, so the engines are objective-agnostic — a bare
# callable (the pre-substrate API) passes through bit-exact.
Objective = GradFn | GradProvider


class RFASTState(NamedTuple):
    k: jnp.ndarray        # () int32 global iteration
    x: jnp.ndarray        # (n, p)
    v: jnp.ndarray        # (n, p)
    z: jnp.ndarray        # (n, p)
    g_prev: jnp.ndarray   # (n, p)
    rho: jnp.ndarray      # (E_A, p)
    rho_buf: jnp.ndarray  # (E_A, p)
    v_hist: jnp.ndarray   # (H, n, p)
    rho_hist: jnp.ndarray # (H, E_A, p)


class _Prepared(NamedTuple):
    """CommPlan slices as device constants, converted once per engine
    build (not once per trace)."""

    w_diag: jnp.ndarray
    a_diag: jnp.ndarray
    src_w: jnp.ndarray; dst_w: jnp.ndarray; w_edge: jnp.ndarray
    src_a: jnp.ndarray; dst_a: jnp.ndarray; a_edge: jnp.ndarray
    in_w_src: jnp.ndarray; in_w_wt: jnp.ndarray
    in_a_epos: jnp.ndarray; in_a_val: jnp.ndarray
    out_a_epos: jnp.ndarray; out_a_wt: jnp.ndarray; out_a_val: jnp.ndarray


def _prepare(plan: CommPlan) -> _Prepared:
    ew = max(1, plan.n_edges_w)
    ea = max(1, plan.n_edges_a)
    # the schedule's per-edge stamp arrays are sized (K, max(1, E)) — the
    # dense edge slices must match them, hence the unpadded leading cut
    return _Prepared(
        w_diag=jnp.asarray(plan.w_diag), a_diag=jnp.asarray(plan.a_diag),
        src_w=jnp.asarray(plan.src_w[:ew]), dst_w=jnp.asarray(plan.dst_w[:ew]),
        w_edge=jnp.asarray(plan.w_edge[:ew]),
        src_a=jnp.asarray(plan.src_a[:ea]), dst_a=jnp.asarray(plan.dst_a[:ea]),
        a_edge=jnp.asarray(plan.a_edge[:ea]),
        in_w_src=jnp.asarray(plan.in_w_src), in_w_wt=jnp.asarray(plan.in_w_wt),
        in_a_epos=jnp.asarray(plan.in_a_epos),
        in_a_val=jnp.asarray(plan.in_a_val),
        out_a_epos=jnp.asarray(plan.out_a_epos),
        out_a_wt=jnp.asarray(plan.out_a_wt),
        out_a_val=jnp.asarray(plan.out_a_val),
    )


def init_state(
    topo: Topology | CommPlan,
    x0: jnp.ndarray,
    grad_fn: Objective,
    key: jax.Array,
    H: int,
) -> RFASTState:
    """Paper init: z_i^0 = ∇f_i(x_i^0; ζ_i^0); v = ρ = ρ̃ = 0."""
    grad_fn = as_grad_fn(grad_fn)
    plan = as_comm_plan(topo)
    n = plan.n
    # copy (not asarray): the state may be donated by the engines, and the
    # caller's x0 buffer must survive the run
    x0 = jnp.array(x0, jnp.float32)
    if x0.ndim == 1:
        x0 = jnp.tile(x0[None, :], (n, 1))
    p = x0.shape[1]
    e_a = max(1, plan.n_edges_a)
    keys = jax.random.split(key, n)
    g0 = jax.vmap(grad_fn)(jnp.arange(n), x0, keys)
    zeros_np = jnp.zeros((n, p), jnp.float32)
    return RFASTState(
        k=jnp.zeros((), jnp.int32),
        x=x0,
        v=zeros_np,
        z=g0,
        g_prev=jnp.copy(g0),   # distinct buffer: donation forbids aliases
        rho=jnp.zeros((e_a, p), jnp.float32),
        rho_buf=jnp.zeros((e_a, p), jnp.float32),
        v_hist=jnp.zeros((H, n, p), jnp.float32),
        rho_hist=jnp.zeros((H, e_a, p), jnp.float32),
    )


def zeros_state(topo: Topology | CommPlan, p: int, H: int) -> RFASTState:
    """Structure-only all-zeros state: shapes/dtypes of a run over
    ``topo`` with flat dimension ``p`` and history depth ``H``.  The
    checkpoint-restore template (``load_checkpoint(dir, like=...)``) —
    no gradient evaluation, unlike :func:`init_state`."""
    plan = as_comm_plan(topo)
    n, e_a = plan.n, max(1, plan.n_edges_a)
    zn = lambda *s: jnp.zeros(s, jnp.float32)
    return RFASTState(
        k=jnp.zeros((), jnp.int32),
        x=zn(n, p), v=zn(n, p), z=zn(n, p), g_prev=zn(n, p),
        rho=zn(e_a, p), rho_buf=zn(e_a, p),
        v_hist=zn(H, n, p), rho_hist=zn(H, e_a, p),
    )


# --------------------------------------------------------------------- #
# event-serial engine (snapshot histories) — the equivalence oracle
# --------------------------------------------------------------------- #
def _step(
    state: RFASTState,
    inputs,
    *,
    pp: _Prepared,
    grad_fn: GradFn,
    gamma: float,
    H: int,
) -> tuple[RFASTState, None]:
    agent, stamp_v, stamp_rho, key = inputs
    a = agent
    k = state.k

    # (S.1) local descent ------------------------------------------------
    v_new = descent_step(state.x[a], state.z[a], gamma)

    # (S.2a) consensus pull over G(W) with stale payloads ------------------
    vals_v = state.v_hist[stamp_v % H, pp.src_w, :]       # (E_W, p)
    mask_w = (pp.dst_w == a).astype(vals_v.dtype)[:, None]
    x_a = consensus_mix(pp.w_diag[a], v_new, mask_w * pp.w_edge[:, None],
                        vals_v)

    # (S.2b) robust gradient tracking -------------------------------------
    g_new = grad_fn(a, x_a, key)
    vals_rho = state.rho_hist[stamp_rho % H,
                              jnp.arange(pp.src_a.shape[0]), :]
    mask_a_in = (pp.dst_a == a).astype(vals_rho.dtype)[:, None]
    recv = jnp.sum(mask_a_in * (vals_rho - state.rho_buf), axis=0)
    z_half = tracking_step(state.z[a], recv, g_new, state.g_prev[a])

    # (S.2c) keep own share; push mass onto out-edges ----------------------
    z_a = pp.a_diag[a] * z_half
    mask_a_out = (pp.src_a == a).astype(vals_rho.dtype)[:, None]
    rho = state.rho + mask_a_out * pp.a_edge[:, None] * z_half[None, :]

    # (S.4) buffers take the consumed values -------------------------------
    rho_buf = mailbox_merge(vals_rho, state.rho_buf, mask_a_in)

    # commit --------------------------------------------------------------
    x = state.x.at[a].set(x_a)
    v = state.v.at[a].set(v_new)
    z = state.z.at[a].set(z_a)
    g_prev = state.g_prev.at[a].set(g_new)
    v_hist = state.v_hist.at[(k + 1) % H].set(v)
    rho_hist = state.rho_hist.at[(k + 1) % H].set(rho)

    return RFASTState(k + 1, x, v, z, g_prev, rho, rho_buf, v_hist, rho_hist), None


def rfast_scan(
    topo: Topology | CommPlan,
    grad_fn: Objective,
    gamma: float,
    H: int,
    *,
    donate: bool = False,
):
    """Event-serial engine: a jitted
    ``(state, agent, stamp_v, stamp_rho, keys) -> state``.

    ``donate=True`` donates the state argument (in-place update of the
    history rings) — the caller must not reuse the passed-in state.
    """
    grad_fn = as_grad_fn(grad_fn)
    plan = as_comm_plan(topo)
    pp = _prepare(plan)
    step = partial(_step, pp=pp, grad_fn=grad_fn, gamma=gamma, H=H)

    def run_chunk(state: RFASTState, agent, stamp_v, stamp_rho, keys):
        state, _ = jax.lax.scan(step, state, (agent, stamp_v, stamp_rho, keys))
        return state

    return jax.jit(run_chunk, donate_argnums=(0,) if donate else ())


# --------------------------------------------------------------------- #
# wavefront-batched engine (delta histories, vmapped lanes)
# --------------------------------------------------------------------- #
class PackedState(NamedTuple):
    """Device layout of the wavefront engine: node variables fused into
    one array and ρ/ρ̃ stacked, so a wavefront writes its rows into four
    arrays, in place (:func:`_write_rows`).

    Every array is *lane-dense*: the flat parameter axis is stored as
    ``(R, LANE)`` rows (``p_pad = R·LANE``, zero tail), the TPU's native
    tiling, so the grid kernel reads ``(BLK_R, LANE)`` blocks of any row
    without a relayout copy of the state.

    * ``nodes``  — (n, 4, R, LANE): rows x, v, z, g_prev per node.
    * ``rho2``   — (2·E_A, R, LANE): ρ rows then ρ̃ rows.
    * ``v_hist`` — (H, n, R, LANE) delta rows (writer count mod H, node).
    * ``rho_hist`` — (H, E_A, R, LANE) delta rows (sender count mod H,
      edge).
    """

    nodes: jnp.ndarray
    rho2: jnp.ndarray
    v_hist: jnp.ndarray
    rho_hist: jnp.ndarray


def _pad_width(p: int, shards: int = 1, *, blocks: bool = False) -> int:
    """Padded flat width of the packed state: every shard's slice is
    whole ``(SUBLANES, LANE)`` tiles, or whole ``(BLK_R, LANE)`` blocks
    when the compiled grid kernel reads it (``blocks``).  A ragged tile
    row costs the TPU a copy of every array an update writes in place."""
    if blocks:
        return block_pad_width(p, shards)
    per = SUBLANES * LANE
    loc = -(-int(p) // int(shards))
    return int(shards) * (-(-loc // per) * per)


def _to_lanes(a: jnp.ndarray, p_pad: int) -> jnp.ndarray:
    """``(..., p)`` -> lane-dense ``(..., p_pad // LANE, LANE)``."""
    p = a.shape[-1]
    if p_pad != p:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, p_pad - p)])
    return a.reshape(a.shape[:-1] + (p_pad // LANE, LANE))


def _from_lanes(a: jnp.ndarray, p: int) -> jnp.ndarray:
    """Lane-dense ``(..., R, LANE)`` -> flat ``(..., p)``.  Whole pad rows
    go before the relayout, which then writes the result directly."""
    a = a[..., :-(-p // LANE), :]
    flat = a.reshape(a.shape[:-2] + (-1,))
    return flat if flat.shape[-1] == p else flat[..., :p]


@partial(jax.jit, static_argnames=("index", "p"))
def _take(a: jnp.ndarray, index: tuple, p: int) -> jnp.ndarray:
    """``a[index]`` back in the flat layout, as one program: only the
    indexed rows are read.  ``index`` holds ints and ``(start, stop)``
    pairs (static, so one compile per distinct slice)."""
    idx = tuple(slice(*i) if isinstance(i, tuple) else i for i in index)
    with jax.named_scope("rfast.iterates"):
        return _from_lanes(a[idx], p)


def pack_state(state: RFASTState, *, e_a: int | None = None,
               p_pad: int | None = None) -> PackedState:
    """Device layout for the wavefront/sweep engines.

    ``e_a`` pads the ρ state to a larger flat layout (fleet sweeps
    normalize every lane to the fleet-wide max A-edge count; the extra
    zero rows are never referenced by a real lane and the matching
    WavefrontPlan must be built/padded against the same ``e_a``).

    ``p_pad`` is the padded flat width (a multiple of ``LANE``; default:
    the next one above ``p``; the compiled grid kernel needs whole
    blocks).  The zero tail is inert under the linear protocol — pass
    the real ``p`` back via the engines' ``p_real`` /
    :func:`unpack_state`'s ``p``.
    """
    rho, rho_buf, rho_hist = state.rho, state.rho_buf, state.rho_hist
    if e_a is not None and e_a != rho.shape[0]:
        if e_a < rho.shape[0]:
            raise ValueError(f"e_a={e_a} < state's A-edge count "
                             f"{rho.shape[0]}")
        pad = e_a - rho.shape[0]
        rho = jnp.pad(rho, ((0, pad), (0, 0)))
        rho_buf = jnp.pad(rho_buf, ((0, pad), (0, 0)))
        rho_hist = jnp.pad(rho_hist, ((0, 0), (0, pad), (0, 0)))
    p = state.x.shape[-1]
    if p_pad is None:
        p_pad = _pad_width(p)
    if p_pad < p or p_pad % LANE:
        raise ValueError(f"p_pad={p_pad} must be a multiple of {LANE} "
                         f"and >= the state's p={p}")
    return PackedState(
        nodes=_to_lanes(jnp.stack([state.x, state.v, state.z,
                                   state.g_prev], axis=1), p_pad),
        rho2=_to_lanes(jnp.concatenate([rho, rho_buf], axis=0), p_pad),
        v_hist=_to_lanes(state.v_hist, p_pad),
        rho_hist=_to_lanes(rho_hist, p_pad),
    )


def unpack_state(packed: PackedState, k, *, p: int | None = None
                 ) -> RFASTState:
    """The :class:`RFASTState` of a packed state, on the host, stripped to
    width ``p`` (default: the whole padded width)."""
    return _lane_states(packed, k, S=1, n=packed.nodes.shape[0],
                        e_a=packed.rho_hist.shape[1], p=p)[0]


def _lane_states(packed: PackedState, k, *, S: int, n: int, e_a: int,
                 p: int | None = None, e_a_lane=None, group_size=None,
                 consume: bool = False) -> list[RFASTState]:
    """Per-lane :class:`RFASTState`s of a fleet's packed state (lane
    blocks: nodes ``[s·n, (s+1)·n)``, ρ ``[s·e_a, ·)`` with ρ̃ at offset
    ``S·e_a``; ``e_a_lane`` strips each lane's ρ state back to its real
    A-edge count; ``group_size`` reads the mesh engine's group-stacked
    layout, lane ``s`` in group ``s // group_size``).

    Built on the host: each packed array is copied out whole, so the
    device needs no memory beyond the state itself (a device-side
    relayout would need as much again).  ``consume=True`` is for the
    engines' own final state: each array leaves the device once copied.
    """
    if p is None:
        p = packed.nodes.shape[-2] * LANE
    if e_a_lane is None:
        e_a_lane = [e_a] * S
    host = {}
    for name, arr in zip(PackedState._fields, packed):
        host[name] = np.asarray(arr)
        if consume:
            arr.delete()
    flat = lambda a: a.reshape(a.shape[:-2] + (-1,))[..., :p]
    kk = jnp.asarray(k, jnp.int32)
    states = []
    for s in range(S):
        g, j, s_loc = ((), s, S) if group_size is None else (
            (s // group_size,), s % group_size, group_size)
        nd = flat(host["nodes"][g][j * n:(j + 1) * n])
        rho2 = host["rho2"][g]
        r0, b0, el = j * e_a, (s_loc + j) * e_a, e_a_lane[s]
        states.append(RFASTState(
            k=kk, x=nd[:, 0], v=nd[:, 1], z=nd[:, 2], g_prev=nd[:, 3],
            rho=flat(rho2[r0:r0 + el]), rho_buf=flat(rho2[b0:b0 + el]),
            v_hist=flat(host["v_hist"][g][:, j * n:(j + 1) * n]),
            rho_hist=flat(host["rho_hist"][g][:, r0:r0 + el])))
    return states


def _iterates(packed: PackedState, s: int, *, n: int, p: int,
              group_size=None) -> jnp.ndarray:
    """Lane ``s``'s node iterates x as ``(n, p)`` — what ``eval_fn``
    reads; nothing else of the packed state is touched."""
    if group_size is None:
        index = ((s * n, (s + 1) * n), 0)
    else:
        j = s % group_size
        index = (s // group_size, (j * n, (j + 1) * n), 0)
    return _take(packed.nodes, index, p)


def _init_packed(grad_fn: GradFn, x0: jnp.ndarray, init_keys: jnp.ndarray,
                 **layout) -> PackedState:
    """The paper init of S lanes — z = g_prev = ∇f_i(x_i^0; ζ_i^0) from
    each lane's init key, v = ρ = ρ̃ = histories = 0 — written straight
    into the lane-dense packed layout by the :func:`_init_programs`.

    ``x0`` is ``(p,)``, ``(n, p)`` or ``(S', n, p)`` with ``S' <= S``
    (missing lanes repeat the last one); ``init_keys`` is ``(S, 2)``."""
    x0 = jnp.asarray(x0, jnp.float32)
    grads, assemble = _init_programs(grad_fn, x0.ndim, p=x0.shape[-1],
                                     **layout)
    return assemble(x0, grads(x0, init_keys))


def _init_programs(grad_fn: GradFn, x0_ndim: int, *, p: int, S: int, n: int,
                   H: int, e_a: int, p_pad: int, groups: int | None = None,
                   sharding_of=None):
    """The two jitted init programs: ``grads(x0, init_keys)`` -> the
    lane-dense ``(S, n, R, LANE)`` initial gradients, and
    ``assemble(x0, g0)`` -> the :class:`PackedState`.

    Two programs, not one: XLA:TPU takes minutes to compile the flat ->
    lane-dense relayout when it shares a program with the gradient, and
    seconds when it does not.  x0 is broadcast inside the programs,
    never tiled on the host, and every large array is an argument or
    built in a program (none is captured as a constant).  ``groups``
    selects the mesh engine's group-stacked layout and ``sharding_of``
    (``leaf -> Sharding``) places the state on the mesh as it is
    produced: no device ever holds more than its shard of it."""
    row = (p_pad // LANE, LANE)
    glead = () if groups is None else (groups,)
    s_loc = S if groups is None else S // groups
    shapes = PackedState(nodes=glead + (s_loc * n, 4) + row,
                         rho2=glead + (2 * s_loc * e_a,) + row,
                         v_hist=glead + (H, s_loc * n) + row,
                         rho_hist=glead + (H, s_loc * e_a) + row)

    def lanes_of(x, tail):
        """x0 (or its lane-dense form) -> (S, n, *tail) per-lane rows."""
        x = x.reshape((1,) * (3 - x0_ndim) + x.shape)
        x = jnp.broadcast_to(x, (x.shape[0], n) + tail)
        if x.shape[0] == S:
            return x
        return jnp.concatenate(
            [x, jnp.broadcast_to(x[-1:], (S - x.shape[0], n) + tail)])

    def grads(x0, keys):
        # one node at a time, each gradient written out lane-dense: a
        # batched (S·n, p) gradient is another relayout XLA:TPU compiles
        # for minutes
        node_keys = jax.vmap(lambda k: jax.random.split(k, n))(keys)
        x = lanes_of(x0, (p,)).reshape(S * n, p)
        g = jax.lax.map(
            lambda a: _to_lanes(grad_fn(a[0], a[1], a[2]), p_pad),
            (jnp.tile(jnp.arange(n), S), x, node_keys.reshape(S * n, 2)))
        return g.reshape((S, n) + row)

    def assemble(x0, gl):
        xl = lanes_of(_to_lanes(x0, p_pad), row)
        nodes = jnp.stack([xl, jnp.zeros_like(xl), gl, gl], axis=2)
        return PackedState(
            nodes=nodes.reshape(shapes.nodes),
            rho2=jnp.zeros(shapes.rho2, jnp.float32),
            v_hist=jnp.zeros(shapes.v_hist, jnp.float32),
            rho_hist=jnp.zeros(shapes.rho_hist, jnp.float32))

    shardings = None if sharding_of is None else PackedState(
        *(sharding_of(jax.ShapeDtypeStruct(sh, jnp.float32))
          for sh in shapes))
    return jax.jit(grads), jax.jit(assemble, out_shardings=shardings)


class _WaveInputs(NamedTuple):
    """Per-wavefront lane tables (one scan-step slice of a WavefrontPlan)."""

    agent: jnp.ndarray      # (B,)
    wslot: jnp.ndarray      # (B,)
    w_self: jnp.ndarray     # (B,)
    a_self: jnp.ndarray     # (B,)
    rslot_v: jnp.ndarray    # (B, kw)
    src_v: jnp.ndarray      # (B, kw)
    w_in: jnp.ndarray       # (B, kw)
    rslot_rho: jnp.ndarray  # (B, ka)
    hist_epos: jnp.ndarray  # (B, ka)
    a_val: jnp.ndarray      # (B, ka)
    rho_gidx: jnp.ndarray   # (B, ko+ka)
    out_wt: jnp.ndarray     # (B, ko)
    keys: jnp.ndarray       # (B, 2)


def _wave_step(
    state: PackedState,
    w: _WaveInputs,
    *,
    grad_fn: GradFn,
    gamma: float,
    ko: int,
    impl: str = "jnp",
    mode: str = "emulate",
    p_real: int | None = None,
) -> tuple[PackedState, None]:
    """One wavefront: B independent per-agent updates (distinct agents,
    pre-wavefront reads only — see build_wavefront_plan), whose O(p)
    rows are written in place (:func:`_write_rows`).  Padding lanes carry
    sentinel indices: their gathers clamp and their writes are skipped.
    All plan-derived tables arrive pre-gathered per lane, so the body
    reads only the four state arrays.

    ``impl="pallas"`` routes the S.2b/c + S.4 commit math (the
    bandwidth-bound tail) through ONE fused :func:`commit_grid` launch
    for the whole wave — the lane tables become row gather indices into
    the lane-dense packed state (``nodes.reshape(N·4, R, LANE)``,
    ``rho_hist.reshape(H·E, R, LANE)``, ``rho2``), so no per-lane
    neighbour stacks are materialized and no per-lane kernel is
    dispatched.  ``mode`` is the resolved dispatch mode: ``interpret``
    keeps the original vmapped per-node kernel as the bit-faithful
    oracle; ``compiled``/``emulate`` take the grid.  The consensus pull
    stays in jnp either way: the gradient must be sampled at the mixed
    point x⁺ before the commit runs.

    ``grad_fn`` sees the flat ``(p,)`` iterate; ``p_real`` (< the padded
    width) slices the parameter tail off before the call and zero-pads
    the gradient back — the pad tail stays exactly zero under the linear
    protocol.
    """
    # per-lane scalars broadcast over the trailing (R, LANE) axes
    bc = lambda a: a[..., None, None]
    # named scopes tag every op of a phase in the device trace: rfast.mix,
    # rfast.grad, rfast.commit, rfast.state_write
    with jax.named_scope("rfast.mix"):
        node_rows = state.nodes[w.agent]                   # (B, 4, R, L)
        x_l, z_l, gp_l = node_rows[:, 0], node_rows[:, 2], node_rows[:, 3]

        # (S.1) local descent ---------------------------------------------
        v_new = descent_step(x_l, z_l, gamma)              # (B, R, L)

        # (S.2a) consensus pull, reads resolved to delta-history rows ------
        vals_v = state.v_hist[w.rslot_v, w.src_v]          # (B, kw, R, L)
        x_a = consensus_mix(bc(w.w_self), v_new, bc(w.w_in.swapaxes(0, 1)),
                            vals_v.swapaxes(0, 1))         # sum over kw

    # (S.2b) robust gradient tracking -------------------------------------
    # flat for the gradient and back; both sides of each relayout are
    # materialized (see _init_programs: fused, it compiles for minutes)
    with jax.named_scope("rfast.grad"):
        B = x_a.shape[0]
        x_flat = jax.lax.optimization_barrier(x_a.reshape(B, -1))
        p = x_flat.shape[-1]
        if p_real is not None and p_real != p:
            g_new = jax.vmap(grad_fn)(w.agent, x_flat[:, :p_real], w.keys)
            g_new = jnp.pad(g_new, ((0, 0), (0, p - p_real)))
        else:
            g_new = jax.vmap(grad_fn)(w.agent, x_flat, w.keys)
        g_new = jax.lax.optimization_barrier(
            jax.lax.optimization_barrier(g_new).reshape(x_a.shape))

    with jax.named_scope("rfast.commit"):
        z_a, rho_new, buf_new = _commit(state, w, z_l, gp_l, g_new,
                                        ko=ko, impl=impl, mode=mode)

    with jax.named_scope("rfast.state_write"):
        return _write_rows(state, w, (x_a, v_new, z_a, g_new), rho_new,
                           buf_new, ko=ko), None


def _write_rows(state: PackedState, w: _WaveInputs, node_new, rho_new,
                buf_new, *, ko: int) -> PackedState:
    """Write one wave's rows into the state in place.

    Lane b with agent a writes ``nodes[a]`` (x, v, z, g_prev: the four
    ``(B, R, LANE)`` arrays of ``node_new``) and ``v_hist[wslot, a]``;
    its out-edge e writes ``rho_new`` into ``rho2[e]`` and
    ``rho_hist[wslot, e]``, and its ρ̃ row r writes ``buf_new`` into
    ``rho2[r]``.  Each row is one ``dynamic_update_slice`` under a
    ``lax.cond`` on its index: a sentinel (padding lane or slot) takes
    the branch that returns the buffer as it is, so no old row is read
    to be written back.  Lanes are unrolled up to ``_UNROLL_LANES`` and
    looped above that, which keeps wide fleet waves quick to compile.
    """
    # all writes after every read of the old rows (the commit's gathers
    # and kernel): else XLA copies an array to serve a read it places
    # after a write
    node_new, rho_new, buf_new, state = jax.lax.optimization_barrier(
        (node_new, rho_new, buf_new, state))
    N, E = state.nodes.shape[0], state.rho_hist.shape[1]
    zero = (0,) * (state.nodes.ndim - 2)

    def put(ok, buf, rows, i, start):
        def write(buf, rows):
            row = rows[i]
            row = row.reshape((1,) * (buf.ndim - row.ndim) + row.shape)
            return jax.lax.dynamic_update_slice(buf, row, start)
        return jax.lax.cond(ok, write, lambda buf, rows: buf, buf, rows)

    def lane(b, st):
        nodes, rho2, v_hist, rho_hist = st
        a, slot = w.agent[b], w.wslot[b]
        ok = (a >= 0) & (a < N)
        for j, rows in enumerate(node_new):
            nodes = put(ok, nodes, rows, b, (a, j) + zero)
        v_hist = put(ok, v_hist, node_new[1], b, (slot, a) + zero)
        for j in range(ko):
            e = w.rho_gidx[b, j]
            ok = (e >= 0) & (e < E)
            rho2 = put(ok, rho2, rho_new, (b, j), (e,) + zero)
            rho_hist = put(ok, rho_hist, rho_new, (b, j), (slot, e) + zero)
        for j in range(buf_new.shape[1]):
            r = w.rho_gidx[b, ko + j]
            rho2 = put((r >= 0) & (r < 2 * E), rho2, buf_new, (b, j),
                       (r,) + zero)
        return nodes, rho2, v_hist, rho_hist

    B = w.agent.shape[0]
    return PackedState(*jax.lax.fori_loop(0, B, lane, tuple(state),
                                          unroll=B <= _UNROLL_LANES))


def _commit(state: PackedState, w: _WaveInputs, z_l, gp_l, g_new, *,
            ko: int, impl: str, mode: str):
    """S.2b/c + S.4 of one wave: ``(z_a, rho_new, buf_new)``, the new
    z rows, the out-edge ρ rows ``(B, ko, R, L)`` and the consumed ρ̃
    rows ``(B, ka, R, L)``."""
    bc = lambda a: a[..., None, None]
    if impl == "pallas" and mode != "interpret":
        # one fused launch for the whole wave: gather tables over the
        # state rows.  The kernel's masked ρ̃ blend equals the jnp
        # path's unconditional vals_rho commit: a_val is a 0/1 indicator
        # and zero-mask rows carry the sentinel row index anyway.
        # Sentinel lanes clamp inside commit_grid; their writes are
        # skipped.
        rows = lambda a: a.reshape((-1,) + a.shape[-2:])
        nodes_flat = rows(state.nodes)                     # (N·4, R, L)
        idx_z, idx_g, idx_ri, idx_rb, idx_ro = grid_gather_tables(
            w.agent, w.rslot_rho, w.hist_epos, w.rho_gidx,
            e_a_flat=state.rho_hist.shape[1], ko=ko)
        z_a, rho_new, buf_new = commit_grid(
            idx_z, idx_g, idx_ri, idx_rb, idx_ro,
            w.a_self, w.a_val, w.out_wt,
            nodes_flat, g_new, nodes_flat, rows(state.rho_hist),
            state.rho2, state.rho2, mode=mode)
    elif impl == "pallas":
        # interpret-mode oracle: the original vmapped per-node kernel
        # over flat lanes.
        vals_rho = state.rho_hist[w.rslot_rho, w.hist_epos]  # (B, ka, R, L)
        rho_rows = state.rho2[w.rho_gidx]                    # (B, ko+ka, ..)
        flat = lambda a: a.reshape(a.shape[:-2] + (-1,))

        def one_lane(z_, gn_, go_, ri_, rb_, mk_, ro_, ao_, as_):
            return rfast_commit(z_, gn_, go_, ri_, rb_, mk_, ro_, ao_,
                                a_self=as_, impl="pallas",
                                interpret=True)
        z_a, rho_new, buf_new = jax.vmap(one_lane)(
            flat(z_l), flat(g_new), flat(gp_l), flat(vals_rho),
            flat(rho_rows[:, ko:]), w.a_val, flat(rho_rows[:, :ko]),
            w.out_wt, w.a_self)
        lanes = lambda a: a.reshape(a.shape[:-1] + z_l.shape[-2:])
        z_a, rho_new, buf_new = lanes(z_a), lanes(rho_new), lanes(buf_new)
    else:
        vals_rho = state.rho_hist[w.rslot_rho, w.hist_epos]  # (B, ka, R, L)
        rho_rows = state.rho2[w.rho_gidx]                    # (B, ko+ka, ..)
        recv = jnp.sum(bc(w.a_val) * (vals_rho - rho_rows[:, ko:]), axis=1)
        z_half = tracking_step(z_l, recv, g_new, gp_l)

        # (S.2c) keep own share; push mass onto out-edges ------------------
        z_a = bc(w.a_self) * z_half
        rho_new = rho_rows[:, :ko] \
            + bc(w.out_wt) * z_half[:, None]              # (B, ko, R, L)
        buf_new = vals_rho
    return z_a, rho_new, buf_new


def rfast_wavefront_scan(
    topo: Topology | CommPlan,
    grad_fn: Objective,
    gamma: float,
    *,
    donate: bool = True,
    impl: str = "jnp",
    interpret: bool | None = None,
    p_real: int | None = None,
):
    """Wavefront engine: a jitted ``(packed, wave_inputs) -> packed`` where
    ``wave_inputs`` is a :class:`_WaveInputs` of ``(n_waves, B, ...)``
    lane arrays from a :class:`~repro.core.schedule.WavefrontPlan`.  The
    state is donated by default (the histories update in place; callers
    rebind).

    ``impl="pallas"`` commits every wave through ONE fused grid launch
    (:func:`repro.kernels.rfast_update.grid.commit_grid`); ``interpret``
    is the tri-state dispatch override (None = autodetect: compiled on
    TPU, jnp emulation elsewhere; True = the vmapped per-node kernel in
    the Pallas interpreter, the tests-only oracle).  ``impl="jnp"`` is
    the jnp gather path.  ``p_real`` marks a block-padded flat axis
    (see :func:`_wave_step`).
    """
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"impl must be 'jnp' or 'pallas', got {impl!r}")
    mode = dispatch.resolve_mode(interpret) if impl == "pallas" else "emulate"
    grad_fn = as_grad_fn(grad_fn)
    plan = as_comm_plan(topo)
    step = partial(_wave_step, grad_fn=grad_fn, gamma=gamma, ko=plan.ko,
                   impl=impl, mode=mode, p_real=p_real)

    def run_waves(state: PackedState, waves: _WaveInputs):
        state, _ = jax.lax.scan(step, state, waves)
        return state

    return jax.jit(run_waves, donate_argnums=(0,) if donate else ())


def wave_inputs(wf, step_keys: jnp.ndarray) -> _WaveInputs:
    """Device lane tables for a WavefrontPlan (kidx == K selects the zero
    padding key row)."""
    lane_keys = jnp.concatenate(
        [step_keys, jnp.zeros((1, 2), step_keys.dtype)])[jnp.asarray(wf.kidx)]
    return _WaveInputs(
        agent=jnp.asarray(wf.agent), wslot=jnp.asarray(wf.wslot),
        w_self=jnp.asarray(wf.w_self), a_self=jnp.asarray(wf.a_self),
        rslot_v=jnp.asarray(wf.rslot_v), src_v=jnp.asarray(wf.src_v),
        w_in=jnp.asarray(wf.w_in), rslot_rho=jnp.asarray(wf.rslot_rho),
        hist_epos=jnp.asarray(wf.hist_epos), a_val=jnp.asarray(wf.a_val),
        rho_gidx=jnp.asarray(wf.rho_gidx), out_wt=jnp.asarray(wf.out_wt),
        keys=lane_keys,
    )


def rfast_sweep_scan(
    grad_fn: Objective,
    gamma: float,
    *,
    ko: int,
    n_per_lane: int,
    donate: bool = True,
    impl: str = "jnp",
    interpret: bool | None = None,
    p_real: int | None = None,
):
    """Fleet engine: a jitted ``(packed, wave_inputs) -> packed`` over a
    fleet-FLATTENED plan (:func:`repro.core.schedule.flatten_plans`).

    The fleet program IS the single-experiment wavefront program at
    width S·B over block-concatenated state (nodes ``(S·n, 4, p)``, ρ
    ``(2·S·e_a, p)``): lanes were made disjoint by index offsetting
    host-side, so the scan body is :func:`_wave_step` itself — no fleet
    vmap, and the compile cost matches ONE run, not S.  With
    ``impl="pallas"`` the whole fleet wave therefore commits as ONE
    grid launch spanning (lane × wave-slot) × p-tiles.  ``grad_fn``
    still sees lane-local node ids (the flat agent id is
    ``s·n_per_lane + a``, reduced mod ``n_per_lane`` before the call);
    ``ko`` is the fleet-wide max A out-degree.  ``interpret``/``p_real``
    as in :func:`rfast_wavefront_scan`.
    """
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"impl must be 'jnp' or 'pallas', got {impl!r}")
    mode = dispatch.resolve_mode(interpret) if impl == "pallas" else "emulate"
    grad_fn = as_grad_fn(grad_fn)
    lane_grad = lambda i, x, key: grad_fn(i % n_per_lane, x, key)
    step = partial(_wave_step, grad_fn=lane_grad, gamma=gamma, ko=ko,
                   impl=impl, mode=mode, p_real=p_real)

    def run_waves(state: PackedState, waves: _WaveInputs):
        state, _ = jax.lax.scan(step, state, waves)
        return state

    return jax.jit(run_waves, donate_argnums=(0,) if donate else ())


def _mesh_axis_size(mesh, axis: str | None) -> int:
    if axis is None or axis not in mesh.axis_names:
        return 1
    return int(dict(mesh.shape)[axis])


def _mesh_sweep_scan(
    grad_fn: Objective,
    gamma: float,
    *,
    ko: int,
    n_per_lane: int,
    mesh,
    lane_axis: str = "data",
    param_axis: str | None = "model",
    donate: bool = True,
    impl: str = "jnp",
    interpret: bool | None = None,
    p_real: int | None = None,
):
    """Mesh-mapped fleet engine: :func:`rfast_sweep_scan` distributed over
    a device mesh by ``jax.shard_map``.

    Layout (see :func:`~repro.core.runtime_sharded.packed_sweep_specs`):
    the packed state and wave tables carry a leading *lane-group* axis —
    one block of ``S_loc`` consecutive lanes per ``lane_axis`` device —
    and the lane-dense parameter rows (the ``R`` axis) are split over
    ``param_axis``.  Inside the
    region each device runs the unmodified :func:`_wave_step` scan over
    its own group's flattened program, so lane groups never communicate:
    lane parallelism is embarrassingly parallel by construction.

    When ``param_axis`` has size M > 1 every state array holds only its
    ``p_loc = p_pad // M`` slice of the flat axis (``R // M`` rows).  The protocol math is
    linear and elementwise along p, so it runs unchanged on slices; only
    the gradient needs the full iterate, which is reconstructed per wave
    by ONE tiled ``all_gather`` over ``param_axis`` (O(p) per lane — the
    same traffic a data-parallel all-reduce would pay) and the fresh
    gradient is sliced back to the local shard.  ``p_real`` strips the
    block/shard padding around the ``grad_fn`` call exactly as in the
    unsharded engines.

    The shapes reaching :func:`commit_grid` inside the region are the
    LOCAL shard shapes (``S_loc·B`` lanes, width ``p_loc``), so the
    dispatch cache keys on the shard shape automatically and the whole
    mesh still resolves ONE launch signature per wave.  State in/out
    specs are identical and the outer jit donates the state, so donation
    survives the shard_map boundary (XLA aliases shard buffers).
    """
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"impl must be 'jnp' or 'pallas', got {impl!r}")
    if lane_axis not in mesh.axis_names:
        raise ValueError(f"mesh has no lane axis {lane_axis!r} "
                         f"(axes: {mesh.axis_names})")
    mode = dispatch.resolve_mode(interpret) if impl == "pallas" else "emulate"
    grad_fn = as_grad_fn(grad_fn)
    M = _mesh_axis_size(mesh, param_axis)
    axes = ((lane_axis, param_axis) if M > 1 else (lane_axis,))

    if M > 1:
        def lane_grad(i, x_loc, key):
            # one collective per wave: rebuild the full iterate for the
            # gradient, then keep only this device's shard of g.  The
            # zero pad tail sits at the END of the global flat axis, so
            # the tiled gather reconstructs global order directly.
            x_full = jax.lax.all_gather(x_loc, param_axis, axis=0,
                                        tiled=True)
            p_pad = x_full.shape[0]
            if p_real is not None and p_real != p_pad:
                g = grad_fn(i % n_per_lane, x_full[:p_real], key)
                g = jnp.pad(g, (0, p_pad - p_real))
            else:
                g = grad_fn(i % n_per_lane, x_full, key)
            m = jax.lax.axis_index(param_axis)
            p_loc = x_loc.shape[0]
            return jax.lax.dynamic_slice(g, (m * p_loc,), (p_loc,))
        step = partial(_wave_step, grad_fn=lane_grad, gamma=gamma, ko=ko,
                       impl=impl, mode=mode, p_real=None)
    else:
        lane_grad = lambda i, x, key: grad_fn(i % n_per_lane, x, key)
        step = partial(_wave_step, grad_fn=lane_grad, gamma=gamma, ko=ko,
                       impl=impl, mode=mode, p_real=p_real)

    def local_run(state: PackedState, waves: _WaveInputs):
        # strip this device's singleton group axis, scan, put it back
        st = jax.tree.map(lambda a: a[0], state)
        wv = jax.tree.map(lambda a: a[0], waves)
        st, _ = jax.lax.scan(step, st, wv)
        return jax.tree.map(lambda a: a[None], st)

    st_spec, wv_spec = packed_sweep_specs(
        lane_axis, param_axis if M > 1 else None)

    def run_waves(state: PackedState, waves: _WaveInputs):
        st_specs = jax.tree.map(st_spec, state)
        wv_specs = jax.tree.map(wv_spec, waves)
        fn = jax.shard_map(local_run, mesh=mesh,
                           in_specs=(st_specs, wv_specs),
                           out_specs=st_specs, axis_names=set(axes),
                           check_vma=False)
        return fn(state, waves)

    return jax.jit(run_waves, donate_argnums=(0,) if donate else ())


def sweep_mesh_shardings(mesh, lane_axis: str = "data",
                         param_axis: str | None = "model"):
    """``(state_leaf -> NamedSharding, wave_leaf -> NamedSharding)`` for
    placing the group-stacked fleet state / wave tables on ``mesh``
    before entering :func:`_mesh_sweep_scan` (avoids a first-call
    resharding transfer)."""
    from jax.sharding import NamedSharding
    M = _mesh_axis_size(mesh, param_axis)
    st_spec, wv_spec = packed_sweep_specs(
        lane_axis, param_axis if M > 1 else None)
    return (lambda l: NamedSharding(mesh, st_spec(l)),
            lambda l: NamedSharding(mesh, wv_spec(l)))


def tracked_mass(state: RFASTState) -> jnp.ndarray:
    """LHS of the Lemma-3 invariant: Σ_i z_i + Σ_e (ρ_e − ρ̃_e)."""
    return state.z.sum(axis=0) + (state.rho - state.rho_buf).sum(axis=0)


def run_rfast(
    topo: Topology,
    schedule: Schedule,
    grad_fn: Objective,
    x0: jnp.ndarray,
    gamma: float,
    *,
    seed: int = 0,
    eval_every: int = 0,
    eval_fn: Callable[[jnp.ndarray, float], dict] | None = None,
    mode: str = "wavefront",
    impl: str = "jnp",
    interpret: bool | None = None,
    state0: RFASTState | None = None,
    chunk_cb: Callable[[RFASTState, int], None] | None = None,
    verify_plans: bool = False,
) -> tuple[RFASTState, list[dict]]:
    """Run the full schedule; optionally evaluate every ``eval_every`` events.

    ``grad_fn`` may be the raw traced callable or any
    :class:`~repro.core.paramvec.GradProvider` (``LogisticProblem``,
    ``LMProblem``, ...) — the engines are objective-agnostic over the
    flat-parameter substrate.

    ``mode="wavefront"`` (default) runs the batched engine with delta
    histories; ``mode="event"`` the one-event-per-step snapshot engine.
    Both realize identical Algorithm-2 semantics (tested to fp32
    tolerance); final ``v_hist``/``rho_hist`` *contents* differ by
    representation.  ``impl="pallas"`` (wavefront only) commits lanes
    through the fused ``rfast_commit`` kernel.

    Checkpoint/resume: ``chunk_cb(state, k)`` fires after every eval
    chunk with the (unpacked) state at event ``k`` — persist it with
    ``checkpoint.save_checkpoint`` (which copies to host; the live
    buffers are donated to the next chunk).  ``state0`` resumes from
    such a state: ``state0.k`` must sit on an eval-chunk boundary of
    the SAME schedule/seed AND the SAME ``mode`` it was saved from —
    the two engines' ``v_hist``/``rho_hist`` *representations* differ
    (wavefront: per-writer delta rows; event: full snapshots), the
    shapes do not, so a cross-mode resume is not detectable here and
    would silently realize a wrong trajectory.  The first ``state0.k``
    events are skipped (the RNG key derivation is identical to the
    fresh run, so a resumed run continues the exact trajectory).

    ``interpret`` (pallas only) is the tri-state dispatch override:
    None autodetects (compiled grid launch on TPU, jnp emulation of the
    grid elsewhere); True forces the interpreter oracle.  The wavefront
    engine keeps the flat axis lane-dense and padded (to whole kernel
    blocks in compiled mode) and strips it again before
    ``grad_fn``/``eval_fn``/return.

    ``eval_fn(x, t)`` receives the ``(n, p)`` node iterates at virtual
    time ``t`` — the only rows of the state an evaluation reads, so the
    rest is never copied out of the packed layout.  Both modes donate
    the running state between chunks (in-place updates).  The wavefront
    engine returns its final state on the host (NumPy arrays), moved
    off the device array by array.

    ``verify_plans=True`` runs the :mod:`repro.analysis.planlint` pass
    over the CommPlan and compiled WavefrontPlan before anything is
    traced, raising :class:`~repro.analysis.PlanInvariantError` on any
    diagnostic — the debug belt-and-braces mode; benches leave it off.
    """
    if mode not in ("wavefront", "event"):
        raise ValueError(f"mode must be 'wavefront' or 'event', got {mode!r}")
    if mode == "event" and impl != "jnp":
        raise ValueError("impl='pallas' requires mode='wavefront' "
                         "(the event engine is the jnp oracle)")
    grad_fn = as_grad_fn(grad_fn)
    plan = as_comm_plan(topo)
    H = int(schedule.D) + 2
    key = jax.random.PRNGKey(seed)
    key, init_key = jax.random.split(key)

    K = schedule.K
    step_keys = jax.random.split(key, K)
    metrics: list[dict] = []
    if eval_every <= 0:
        eval_every = K

    k0 = 0
    if state0 is not None:
        if state0.v_hist.shape[0] != H:
            raise ValueError(
                f"state0 has H={state0.v_hist.shape[0]} but this schedule "
                f"needs H={H} — resume only into the same schedule")
        k0 = int(state0.k)
        # k0 == K is a completed run (its K need not be chunk-aligned)
        if k0 < K and k0 % eval_every != 0:
            raise ValueError(f"state0.k={k0} is not an eval-chunk boundary "
                             f"(eval_every={eval_every})")
        if k0 >= K:
            return jax.tree.map(jnp.array, state0), metrics

    if mode == "event":
        # copy state0: the engines donate their state buffers in place
        state = (init_state(plan, x0, grad_fn, init_key, H) if state0 is None
                 else jax.tree.map(jnp.array, state0))
        if verify_plans:
            from ..analysis import planlint
            planlint.check_or_raise(
                planlint.lint_comm_plan(
                    plan, topo if isinstance(topo, Topology) else None),
                "run_rfast(verify_plans)")
        chunk = rfast_scan(plan, grad_fn, gamma, H, donate=True)
        agent = jnp.asarray(schedule.agent)
        stamp_v = jnp.asarray(schedule.stamp_v)
        stamp_rho = jnp.asarray(schedule.stamp_rho)
        for s in range(k0, K, eval_every):
            e = min(K, s + eval_every)
            state = chunk(state, agent[s:e], stamp_v[s:e], stamp_rho[s:e],
                          step_keys[s:e])
            if eval_fn is not None:
                m = eval_fn(state.x, float(schedule.times[e - 1]))
                m["k"] = e
                metrics.append(m)
            if chunk_cb is not None:
                chunk_cb(state, e)       # event engine tracks k == e itself
        return state, metrics

    # lane-dense packed state, block-padded for compiled grid launches
    # (the zero tail is provably inert); stripped again at every read
    p = int(jnp.shape(x0)[-1] if state0 is None else state0.x.shape[-1])
    p_pad = _pad_width(p, blocks=(impl == "pallas" and
                                  dispatch.resolve_mode(interpret)
                                  == "compiled"))

    with tracing.span("rfast.plan"):
        wf = build_wavefront_plan(schedule, plan, H, break_every=eval_every)
        if verify_plans:
            from ..analysis import planlint
            planlint.check_or_raise(
                planlint.lint_comm_plan(
                    plan, topo if isinstance(topo, Topology) else None)
                + planlint.lint_wavefront_plan(wf, comm=plan,
                                               schedule=schedule, H=H),
                "run_rfast(verify_plans)")
        runner = rfast_wavefront_scan(
            plan, grad_fn, gamma, donate=True, impl=impl,
            interpret=interpret, p_real=(p if p_pad != p else None))
        waves = wave_inputs(wf, step_keys)
    with tracing.span("rfast.init_state"):
        if state0 is None:
            packed = _init_packed(grad_fn, x0, init_key[None], S=1,
                                  n=plan.n, H=H, e_a=max(1, plan.n_edges_a),
                                  p_pad=p_pad)
        else:
            packed = pack_state(state0, p_pad=p_pad)
        if tracing.enabled():
            # time the init programs, not their enqueue (traced runs only)
            jax.block_until_ready(packed)

    # chunk boundaries in wave space (waves never cross eval boundaries);
    # pad every chunk to the max wave count so the runner compiles once
    bounds = [int(np.searchsorted(wf.event_start, s))
              for s in range(0, K, eval_every)] + [wf.n_waves]
    cmax = max(b1 - b0 for b0, b1 in zip(bounds, bounds[1:]))
    n_pad = wf.n
    skip = k0 // eval_every          # chunks already realized in state0

    for ci, (w0, w1) in enumerate(zip(bounds[skip:], bounds[skip + 1:]),
                                  start=skip):
        pad = cmax - (w1 - w0)

        def sl(arr, fill):
            if not pad:
                return arr[w0:w1]
            return jnp.concatenate(
                [arr[w0:w1], jnp.full((pad,) + arr.shape[1:], fill,
                                      arr.dtype)])

        with tracing.span("rfast.chunk_inputs", ci):
            chunk_waves = _WaveInputs(
                agent=sl(waves.agent, n_pad), wslot=sl(waves.wslot, 0),
                w_self=sl(waves.w_self, 0.0), a_self=sl(waves.a_self, 0.0),
                rslot_v=sl(waves.rslot_v, 0), src_v=sl(waves.src_v, 0),
                w_in=sl(waves.w_in, 0.0), rslot_rho=sl(waves.rslot_rho, 0),
                hist_epos=sl(waves.hist_epos, 0),
                a_val=sl(waves.a_val, 0.0),
                rho_gidx=sl(waves.rho_gidx, 2 * wf.e_a),
                out_wt=sl(waves.out_wt, 0.0), keys=sl(waves.keys, 0))
        with tracing.span("rfast.run_waves", ci):
            packed = runner(packed, chunk_waves)
        e = min(K, (ci + 1) * eval_every)
        tracing.count("rfast.events", e - ci * eval_every, ci)
        tracing.count("rfast.pad_lanes",
                      int(np.count_nonzero(wf.agent[w0:w1] == wf.n))
                      + pad * wf.width, ci)
        if eval_fn is not None:
            with tracing.span("rfast.read_iterates", ci):
                x = _iterates(packed, 0, n=plan.n, p=p)
            with tracing.span("rfast.eval_fn", ci):
                m = eval_fn(x, float(schedule.times[e - 1]))
            # hold x no longer than eval_fn does: the next chunk's waves
            # and eval read need its device memory
            del x
            m["k"] = e
            metrics.append(m)
        if chunk_cb is not None:
            with tracing.span("rfast.chunk_cb", ci):
                chunk_cb(unpack_state(packed, e, p=p), e)
    return _lane_states(packed, K, S=1, n=plan.n,
                        e_a=packed.rho_hist.shape[1], p=p,
                        consume=True)[0], metrics


# --------------------------------------------------------------------- #
# fleet sweeps: many experiments as one compiled wavefront program
# --------------------------------------------------------------------- #
def run_sweep(
    topos,
    schedules,
    grad_fn: Objective,
    x0: jnp.ndarray,
    gamma: float,
    *,
    seeds=None,
    eval_every: int = 0,
    eval_fn: Callable[[jnp.ndarray, float], dict] | None = None,
    impl: str = "jnp",
    interpret: bool | None = None,
    verify_plans: bool = False,
    mesh=None,
    lane_axis: str = "data",
    param_axis: str | None = "model",
) -> tuple[list[RFASTState], list[list[dict]]]:
    """Run a fleet of S independent experiments as ONE compiled program.

    Each lane is one (topology, schedule, seed) experiment — e.g. a
    :func:`repro.core.scenario.realize_batch` sweep of one scenario over
    many seeds, or a registry sweep across scenarios and topologies.
    Per lane the realized trajectory matches an individual
    :func:`run_rfast` wavefront run of the same (schedule, seed) to fp32
    tolerance; the fleet executes as ONE flattened wavefront program
    (``schedule.flatten_plans``: lanes become index-disjoint blocks of a
    width-S·B wave), so one compile and one ``lax.scan`` serve all S
    experiments and the per-wave math is batched ``(S·B, p)`` instead of
    dispatched S separate times.

    Args:
      topos: one Topology/CommPlan shared by every lane, or a sequence of
        S of them.  All lanes must share the node count ``n`` (the packed
        fleet state is ``(S, n, 4, p)``); topologies may otherwise differ
        — CommPlans are degree-normalized (``plan.pad_comm_plan``) and
        the per-lane WavefrontPlans padded/stacked to fleet maxima, with
        padded waves/lanes provably inert.
      schedules: S realized Schedules sharing ``K`` (each its own trace).
      grad_fn: the shared objective (bare callable or GradProvider);
        gradients are sampled per (lane, event) from the lane's own RNG
        stream, exactly as the individual runs would.
      seeds: per-lane RNG seeds (defaults to 0 for every lane, matching
        ``run_rfast``'s default).
      eval_every / eval_fn: as in :func:`run_rfast`, evaluated per lane —
        the metrics come back as one list per lane, each entry stamped
        with that lane's own virtual time.
      impl: ``"pallas"`` commits every fleet wave — all lanes, all wave
        slots — through ONE fused grid launch.
      interpret: tri-state dispatch override (None = compiled on TPU /
        jnp grid emulation elsewhere; True = interpreter oracle).
      mesh: optional ``jax.sharding.Mesh`` — distribute the fleet via
        :func:`_mesh_sweep_scan`: lanes are split into contiguous groups
        over ``lane_axis`` (the fleet is padded to a multiple of the
        axis size by replicating the last lane; replica results are
        dropped) and the flat parameter axis is sharded over
        ``param_axis`` when that axis has size > 1, so p >= 100M states
        fit in per-device memory.  The initial state is built straight
        into that sharding.  Per lane the results match the unsharded
        engine to fp32 tolerance (tested).
      lane_axis / param_axis: mesh axis names (``"data"`` / ``"model"``,
        the :func:`repro.launch.mesh.make_sweep_mesh` convention).

    Returns:
      ``(states, metrics)`` — the final per-lane :class:`RFASTState` list
      on the host (ρ state stripped back to each lane's real A-edge
      count) and the per-lane metrics lists.
    """
    schedules = list(schedules)
    S = len(schedules)
    if S == 0:
        raise ValueError("run_sweep needs at least one lane")
    if isinstance(topos, (Topology, CommPlan)):
        topos = [topos] * S
    plans = [as_comm_plan(t) for t in topos]
    if len(plans) != S:
        raise ValueError(f"{len(plans)} topologies for {S} schedules")
    n = plans[0].n
    if any(pl.n != n for pl in plans):
        raise ValueError("all lanes must share the node count n "
                         f"(got {[pl.n for pl in plans]})")
    K = schedules[0].K
    if any(s.K != K for s in schedules):
        raise ValueError("all lanes must share the event count K "
                         f"(got {[s.K for s in schedules]})")
    if seeds is None:
        seeds = [0] * S
    seeds = [int(s) for s in seeds]
    if len(seeds) != S:
        raise ValueError(f"{len(seeds)} seeds for {S} lanes")
    grad_fn = as_grad_fn(grad_fn)
    if eval_every <= 0:
        eval_every = K

    # mesh-mapped fleet: pad the lane list to a multiple of the lane-axis
    # size by replicating the last lane (replica outputs are dropped), so
    # every device owns one group of S_loc consecutive lanes
    D = M = 1
    if mesh is not None:
        if lane_axis not in mesh.axis_names:
            raise ValueError(f"mesh has no lane axis {lane_axis!r} "
                             f"(axes: {mesh.axis_names})")
        D = _mesh_axis_size(mesh, lane_axis)
        M = _mesh_axis_size(mesh, param_axis)
    S_pad = -(-S // D) * D
    plans = plans + [plans[-1]] * (S_pad - S)
    schedules = schedules + [schedules[-1]] * (S_pad - S)
    seeds = seeds + [seeds[-1]] * (S_pad - S)
    S_loc = S_pad // D

    # fleet-wide shape maxima: history depth, degrees, ρ layout
    H = max(int(s.D) for s in schedules) + 2
    kw = max(pl.kw for pl in plans)
    ka = max(pl.ka for pl in plans)
    ko = max(pl.ko for pl in plans)
    e_a = max(max(1, pl.n_edges_a) for pl in plans)
    padded_plans = [pad_comm_plan(pl, kw=kw, ka=ka, ko=ko) for pl in plans]

    x0 = jnp.asarray(x0, jnp.float32)
    if x0.ndim == 3 and x0.shape[0] != S:
        raise ValueError(f"per-lane x0 has {x0.shape[0]} lanes, "
                         f"expected {S}")
    p = int(x0.shape[-1])
    # lane-dense flat axis: whole LANE rows per param shard, whole grid
    # blocks for compiled launches (the zero tail is inert)
    p_pad = _pad_width(p, M, blocks=(impl == "pallas" and
                                     dispatch.resolve_mode(interpret)
                                     == "compiled"))
    # per-lane RNG streams, derived exactly as run_rfast does
    lane_keys, init_keys = [], []
    for s in range(S_pad):
        key, init_key = jax.random.split(jax.random.PRNGKey(seeds[s]))
        lane_keys.append(jax.random.split(key, K))
        init_keys.append(init_key)
    step_keys = jnp.stack(lane_keys)                        # (S_pad, K, 2)

    with tracing.span("rfast.plan"):
        # per-lane plans, then chunk-aligned fleet stacking: chunk c of every
        # lane is padded to the fleet-wide max chunk wave count, so chunk c
        # occupies waves [c*cmax, (c+1)*cmax) in EVERY lane and one compiled
        # scan body serves all chunks of all lanes
        wfs = [build_wavefront_plan(schedules[s], padded_plans[s], H,
                                    break_every=eval_every, e_a=e_a)
               for s in range(S_pad)]
        chunk_starts = list(range(0, K, eval_every))
        bounds = [[int(np.searchsorted(wf.event_start, c0))
                   for c0 in chunk_starts] + [wf.n_waves] for wf in wfs]
        cmax = max(b[c + 1] - b[c]
                   for b in bounds for c in range(len(chunk_starts)))
        B = max(wf.width for wf in wfs)
        rechunked = []
        for wf, b in zip(wfs, bounds):
            rechunked.append(concat_plans(
                [pad_plan(slice_plan(wf, b[c], b[c + 1]),
                          width=B, n_waves=cmax, e_a=e_a)
                 for c in range(len(chunk_starts))]))
        if verify_plans:
            from ..analysis import planlint
            diags = []
            for s in range(S_pad):
                diags += planlint.lint_comm_plan(
                    padded_plans[s], subject=f"lane{s}/comm")
                diags += planlint.lint_wavefront_plan(
                    rechunked[s], comm=padded_plans[s],
                    schedule=schedules[s], H=H, subject=f"lane{s}")
        if mesh is None:
            stacked = stack_plans(rechunked)
            fleet = flatten_plans(stacked)
            if verify_plans:
                diags += planlint.lint_flatten(stacked, fleet, subject="fleet")
            pad_lanes = np.count_nonzero(fleet.agent == fleet.n, axis=1)
            waves = wave_inputs(fleet, step_keys.reshape(S_pad * K, 2))
            runner = rfast_sweep_scan(
                grad_fn, gamma, ko=ko, n_per_lane=n, donate=True, impl=impl,
                interpret=interpret, p_real=(p if p_pad != p else None))
        else:
            # one flattened program PER lane group, stacked on the leading
            # device axis: every group shares the (cmax, S_loc·B) wave shape,
            # so the shard_map body compiles once for all groups
            group_waves, pad_lanes = [], 0
            for g in range(D):
                stacked = stack_plans(rechunked[g * S_loc:(g + 1) * S_loc])
                fleet = flatten_plans(stacked)
                if verify_plans:
                    diags += planlint.lint_flatten(stacked, fleet,
                                                   subject=f"fleet/g{g}")
                pad_lanes = pad_lanes + np.count_nonzero(
                    fleet.agent == fleet.n, axis=1)
                group_waves.append(wave_inputs(
                    fleet,
                    step_keys[g * S_loc:(g + 1) * S_loc].reshape(S_loc * K,
                                                                 2)))
            waves = jax.tree.map(lambda *a: jnp.stack(a), *group_waves)
            runner = _mesh_sweep_scan(
                grad_fn, gamma, ko=ko, n_per_lane=n, mesh=mesh,
                lane_axis=lane_axis, param_axis=param_axis, donate=True,
                impl=impl, interpret=interpret,
                p_real=(p if p_pad != p else None))
            st_sh, wv_sh = sweep_mesh_shardings(mesh, lane_axis, param_axis)
            waves = jax.device_put(waves, jax.tree.map(wv_sh, waves))
        if verify_plans:
            planlint.check_or_raise(diags, "run_sweep(verify_plans)")

    # the fleet init straight into the engine's layout (and, on a mesh,
    # its sharding): lane s's g0 comes from the lane's init key exactly
    # as in run_rfast, so the trajectories match the per-lane runs
    group_size = None if mesh is None else S_loc
    with tracing.span("rfast.init_state"):
        packed = _init_packed(grad_fn, x0, jnp.stack(init_keys), S=S_pad,
                              n=n, H=H, e_a=e_a, p_pad=p_pad,
                              groups=None if mesh is None else D,
                              sharding_of=None if mesh is None else st_sh)
        if tracing.enabled():
            jax.block_until_ready(packed)

    metrics: list[list[dict]] = [[] for _ in range(S)]
    for ci in range(len(chunk_starts)):
        sl = (lambda a: a[:, ci * cmax:(ci + 1) * cmax]) if mesh is not \
            None else (lambda a: a[ci * cmax:(ci + 1) * cmax])
        with tracing.span("rfast.chunk_inputs", ci):
            chunk_waves = jax.tree.map(sl, waves)
        with tracing.span("rfast.run_waves", ci):
            packed = runner(packed, chunk_waves)
        e = min(K, (ci + 1) * eval_every)
        tracing.count("rfast.events", S * (e - chunk_starts[ci]), ci)
        tracing.count("rfast.pad_lanes",
                      int(pad_lanes[ci * cmax:(ci + 1) * cmax].sum()), ci)
        if eval_fn is not None:
            for s in range(S):
                with tracing.span("rfast.read_iterates", ci):
                    x = _iterates(packed, s, n=n, p=p, group_size=group_size)
                with tracing.span("rfast.eval_fn", ci):
                    m = eval_fn(x, float(schedules[s].times[e - 1]))
                del x                    # as in run_rfast
                m["k"] = e
                metrics[s].append(m)
    states = _lane_states(
        packed, K, S=S_pad, n=n, e_a=e_a, p=p,
        e_a_lane=[max(1, pl.n_edges_a) for pl in plans],
        group_size=group_size, consume=True)
    return states[:S], metrics


# --------------------------------------------------------------------- #
# epochized runs: dynamic membership / time-varying topologies
# --------------------------------------------------------------------- #
def migrate_state(state: RFASTState, prev_topo, epoch, *,
                  H: int) -> RFASTState:
    """Carry an :class:`RFASTState` across a membership-epoch boundary.

    The migration preserves the Lemma-3 invariant exactly, by
    construction (DESIGN.md §11):

    1. **Settle in-flight mass.**  Every A-edge's undelivered running-sum
       difference ρ_e − ρ̃_e is added to its receiver's z (an instant
       final delivery), then ρ/ρ̃ and both history rings reset to zero —
       the new epoch's edge set need not match the old one, and a reset
       ring read (slot 0) now correctly means "nothing pushed yet".
    2. **Re-absorb departures.**  A departed node's tracked surplus
       ``z_d − g_prev_d`` moves to the new epoch's root and its z/g_prev
       zero out, so the surviving sum Σz − Σg_prev stays 0: tracking
       remains *conservative* — the fleet average still estimates the
       average gradient of the surviving members.
    3. **Adopt joiners.**  A joining node copies the donor's current
       iterate into x and v (the donor is the new root, or the first
       carried-over member when the root itself is the one joining) with
       ``z = g_prev = 0`` — a zero net contribution until its first own
       activation samples a real gradient.
    4. **v continuity.**  The new epoch's ``v_hist[0]`` is seeded with
       the carried v: slot 0 is the engines' "no write yet" read, so
       neighbours pulling a node that has not yet re-activated read its
       last published value instead of zero (no re-init transient).

    ``prev_topo`` identifies the A-edge layout the state's ρ rows belong
    to (fleet-padded tails are inert zeros).  The returned state has the
    NEW epoch's ρ layout and ``H``-deep rings, ``k = 0`` (epoch-local;
    callers track the global event count).
    """
    state = jax.tree.map(jnp.asarray, state)     # host states welcome
    prev_plan = as_comm_plan(prev_topo)
    new_plan = as_comm_plan(epoch.topology)
    n, p = state.x.shape
    e_prev = max(1, prev_plan.n_edges_a)

    # (1) settle ρ − ρ̃ at each receiver
    z = state.z
    if prev_plan.n_edges_a:
        inflight = state.rho[:e_prev] - state.rho_buf[:e_prev]
        z = z.at[jnp.asarray(prev_plan.dst_a[:e_prev])].add(inflight)

    # (2) departures: move the tracked surplus to the new root
    dep = jnp.asarray(epoch.departed)
    root = int(epoch.root)
    d_mass = jnp.sum(jnp.where(dep[:, None], z - state.g_prev, 0.0),
                     axis=0)
    z = jnp.where(dep[:, None], 0.0, z).at[root].add(d_mass)
    g_prev = jnp.where(dep[:, None], 0.0, state.g_prev)

    # (3) joiners adopt a surviving donor's iterate, zero tracking
    joined_np = np.asarray(epoch.joined)
    if joined_np.any():
        carried = epoch.topology.active_mask() & ~joined_np
        if not carried.any():
            raise ValueError("epoch has no carried-over member to "
                             "donate an iterate to its joiners")
        donor = root if not joined_np[root] else int(
            np.nonzero(carried)[0][0])
        joined = jnp.asarray(joined_np)
        x = jnp.where(joined[:, None], state.x[donor], state.x)
        v = jnp.where(joined[:, None], state.x[donor], state.v)
        z = jnp.where(joined[:, None], 0.0, z)
        g_prev = jnp.where(joined[:, None], 0.0, g_prev)
    else:
        x, v = state.x, state.v

    # (4) fresh rings in the new epoch's layout; slot 0 carries v
    e_a = max(1, new_plan.n_edges_a)
    zf = lambda *s: jnp.zeros(s, jnp.float32)
    return RFASTState(
        k=jnp.zeros((), jnp.int32), x=x, v=v, z=z, g_prev=g_prev,
        rho=zf(e_a, p), rho_buf=zf(e_a, p),
        v_hist=zf(H, n, p).at[0].set(v), rho_hist=zf(H, e_a, p))


def _epoch_lane_plans(epochs, eval_every: int, *, H: int, kw: int,
                      ka: int, ko: int, e_a: int):
    """Per-epoch padded CommPlans, WavefrontPlans (built against the
    shared shape maxima) and chunk wave bounds for one epochized lane."""
    plans = [as_comm_plan(ep.topology) for ep in epochs]
    padded = [pad_comm_plan(pl, kw=kw, ka=ka, ko=ko) for pl in plans]
    wfs = [build_wavefront_plan(ep.trace.schedule, padded[i], H,
                                break_every=eval_every, e_a=e_a)
           for i, ep in enumerate(epochs)]
    bounds = []
    for ep, wf in zip(epochs, wfs):
        starts = list(range(0, ep.K, eval_every))
        bounds.append([int(np.searchsorted(wf.event_start, s))
                       for s in starts] + [wf.n_waves])
    return plans, padded, wfs, bounds


def _scan_epochs(epochs, plans, wfs, bounds, runner, step_keys, state0,
                 *, B: int, cmax: int, e_a: int, H: int, p: int,
                 p_pad: int, eval_every: int, eval_fn, chunk_cb):
    """Drive one epochized lane through the shared jitted runner: scan
    each epoch's chunks (padded to the shared ``(cmax, B)`` wave shape),
    migrating the packed state at every epoch boundary."""
    metrics: list[dict] = []
    packed = pack_state(state0, e_a=e_a, p_pad=p_pad)
    for i, (ep, wf, b) in enumerate(zip(epochs, wfs, bounds)):
        if i > 0:
            state = unpack_state(packed, ep.k0, p=p)
            state = migrate_state(state, epochs[i - 1].topology, ep, H=H)
            packed = pack_state(state, e_a=e_a, p_pad=p_pad)
        rc = concat_plans(
            [pad_plan(slice_plan(wf, b[c], b[c + 1]),
                      width=B, n_waves=cmax, e_a=e_a)
             for c in range(len(b) - 1)])
        waves = wave_inputs(rc, step_keys[ep.k0:ep.k0 + ep.K])
        sched = ep.trace.schedule
        for ci in range(len(b) - 1):
            w = jax.tree.map(lambda a: a[ci * cmax:(ci + 1) * cmax],
                             waves)
            packed = runner(packed, w)
            e_loc = min(ep.K, (ci + 1) * eval_every)
            tracing.count("rfast.events", e_loc - ci * eval_every)
            tracing.count("rfast.pad_lanes", int(np.count_nonzero(
                rc.agent[ci * cmax:(ci + 1) * cmax] == rc.n)))
            kg = ep.k0 + e_loc
            if eval_fn is not None:
                m = eval_fn(_iterates(packed, 0, n=packed.nodes.shape[0],
                                      p=p),
                            ep.t0 + float(sched.times[e_loc - 1]))
                m["k"] = kg
                metrics.append(m)
            if chunk_cb is not None:
                chunk_cb(unpack_state(packed, kg, p=p), kg)
    K = epochs[-1].k0 + epochs[-1].K
    final = unpack_state(packed, K, p=p)
    # strip the fleet ρ padding back to the final epoch's real layout
    e_fin = max(1, plans[-1].n_edges_a)
    if e_fin != e_a:
        final = final._replace(rho=final.rho[:e_fin],
                               rho_buf=final.rho_buf[:e_fin],
                               rho_hist=final.rho_hist[:, :e_fin])
    return final, metrics


def run_epochs(
    epoch_trace,
    grad_fn: Objective,
    x0: jnp.ndarray,
    gamma: float,
    *,
    seed: int = 0,
    eval_every: int = 0,
    eval_fn: Callable[[jnp.ndarray, float], dict] | None = None,
    impl: str = "jnp",
    interpret: bool | None = None,
    chunk_cb: Callable[[RFASTState, int], None] | None = None,
    verify_plans: bool = False,
) -> tuple[RFASTState, list[dict]]:
    """Run an epochized trace (:meth:`NetworkScenario.realize_epochs`)
    through the wavefront engine: one compiled scan body for ALL epochs.

    Every epoch's CommPlan is degree-normalized (``pad_comm_plan``) and
    its WavefrontPlan padded (``pad_plan``) to the trace-wide maxima —
    history depth H, in/out degrees, ρ layout ``e_a``, wave width B and
    chunk wave count — so epoch transitions change *data*, never
    compiled shapes: the jitted runner compiles once and (under
    ``impl="pallas"``) the ``commit_grid`` dispatch cache stays at one
    entry per shape across the whole run.  At each boundary the packed
    state is migrated by :func:`migrate_state` (mass settled, departures
    re-absorbed at the new root, joiners adopted, v carried through ring
    slot 0).

    RNG: one global per-event key stream derived exactly as
    :func:`run_rfast` does (``PRNGKey(seed)``), sliced per epoch at
    ``k0`` — a single-epoch (static) trace therefore reproduces
    :func:`run_rfast` on the same realized schedule.  ``eval_every``
    counts *global* events; evaluation additionally lands on every epoch
    boundary (partial final chunks), each metrics entry stamped with the
    global event count ``k`` and global virtual time ``t0 + t_local``.
    """
    epochs = list(epoch_trace.epochs)
    if not epochs:
        raise ValueError("epoch trace has no epochs")
    grad_fn = as_grad_fn(grad_fn)
    K = int(epoch_trace.K)
    if eval_every <= 0:
        eval_every = K

    H = max(int(ep.trace.schedule.D) for ep in epochs) + 2
    raw_plans = [as_comm_plan(ep.topology) for ep in epochs]
    kw = max(pl.kw for pl in raw_plans)
    ka = max(pl.ka for pl in raw_plans)
    ko = max(pl.ko for pl in raw_plans)
    e_a = max(max(1, pl.n_edges_a) for pl in raw_plans)
    plans, padded, wfs, bounds = _epoch_lane_plans(
        epochs, eval_every, H=H, kw=kw, ka=ka, ko=ko, e_a=e_a)
    if verify_plans:
        from ..analysis import planlint
        diags = planlint.lint_epoch_trace(epoch_trace)
        for i, ep in enumerate(epochs):
            diags += planlint.lint_comm_plan(padded[i],
                                             subject=f"ep{i}/comm")
            diags += planlint.lint_wavefront_plan(
                wfs[i], comm=padded[i], schedule=ep.trace.schedule,
                H=H, subject=f"ep{i}")
        planlint.check_or_raise(diags, "run_epochs(verify_plans)")
    B = max(wf.width for wf in wfs)
    cmax = max(b[c + 1] - b[c] for b in bounds for c in range(len(b) - 1))

    key, init_key = jax.random.split(jax.random.PRNGKey(seed))
    step_keys = jax.random.split(key, K)
    state0 = init_state(plans[0], x0, grad_fn, init_key, H)
    p = int(state0.x.shape[-1])
    p_pad = _pad_width(p, blocks=(impl == "pallas" and
                                  dispatch.resolve_mode(interpret)
                                  == "compiled"))
    runner = rfast_wavefront_scan(
        padded[0], grad_fn, gamma, donate=True, impl=impl,
        interpret=interpret, p_real=(p if p_pad != p else None))
    return _scan_epochs(epochs, plans, wfs, bounds, runner, step_keys,
                        state0, B=B, cmax=cmax, e_a=e_a, H=H, p=p,
                        p_pad=p_pad, eval_every=eval_every,
                        eval_fn=eval_fn, chunk_cb=chunk_cb)


def run_sweep_epochs(
    epoch_traces,
    grad_fn: Objective,
    x0: jnp.ndarray,
    gamma: float,
    *,
    seeds=None,
    eval_every: int = 0,
    eval_fn: Callable[[jnp.ndarray, float], dict] | None = None,
    impl: str = "jnp",
    interpret: bool | None = None,
    verify_plans: bool = False,
    mesh=None,
    lane_axis: str = "data",
    param_axis: str | None = "model",
) -> tuple[list[RFASTState], list[list[dict]]]:
    """Fleet of epochized lanes (e.g. one scenario × many seeds from
    :func:`repro.core.scenario.realize_epochs_batch`) through ONE shared
    compiled scan body.

    Unlike :func:`run_sweep`, lanes are not flattened into a single wave
    program: membership timelines are lane-local (regional-failure draws
    and epoch cuts differ per seed), so lanes execute sequentially — but
    every epoch of every lane is padded to the fleet-wide shape maxima,
    so one jitted runner serves all lanes and all epochs (one compile,
    one ``commit_grid`` dispatch-cache entry per shape).  Per lane the
    result equals :func:`run_epochs` of that (trace, seed) — same key
    streams, same migrations.

    ``mesh`` shards the flat PARAMETER axis over ``param_axis`` via
    :func:`_mesh_sweep_scan` (large-p epochized runs); the lane axis of
    the mesh must have size 1 — lanes stay sequential here because their
    membership timelines (epoch cuts, migrations) are host-driven and
    lane-local.  Use :func:`run_sweep` for lane-parallel meshes.
    """
    traces = list(epoch_traces)
    S = len(traces)
    if S == 0:
        raise ValueError("run_sweep_epochs needs at least one lane")
    if seeds is None:
        seeds = [0] * S
    seeds = [int(s) for s in seeds]
    if len(seeds) != S:
        raise ValueError(f"{len(seeds)} seeds for {S} lanes")
    n = traces[0].n
    if any(t.n != n for t in traces):
        raise ValueError("all lanes must share the node count n")
    grad_fn = as_grad_fn(grad_fn)
    K = max(int(t.K) for t in traces)
    if eval_every <= 0:
        eval_every = K

    all_eps = [ep for t in traces for ep in t.epochs]
    H = max(int(ep.trace.schedule.D) for ep in all_eps) + 2
    raw = [as_comm_plan(ep.topology) for ep in all_eps]
    kw = max(pl.kw for pl in raw)
    ka = max(pl.ka for pl in raw)
    ko = max(pl.ko for pl in raw)
    e_a = max(max(1, pl.n_edges_a) for pl in raw)

    lanes = [_epoch_lane_plans(list(t.epochs), eval_every, H=H, kw=kw,
                               ka=ka, ko=ko, e_a=e_a) for t in traces]
    if verify_plans:
        from ..analysis import planlint
        diags = []
        for s, (trace, (_pl, padded_s, wfs_s, _b)) in enumerate(
                zip(traces, lanes)):
            diags += planlint.lint_epoch_trace(trace,
                                               subject=f"lane{s}")
            for i, ep in enumerate(trace.epochs):
                diags += planlint.lint_wavefront_plan(
                    wfs_s[i], comm=padded_s[i],
                    schedule=ep.trace.schedule, H=H,
                    subject=f"lane{s}/ep{i}")
        planlint.check_or_raise(diags, "run_sweep_epochs(verify_plans)")
    B = max(wf.width for (_pl, _pd, wfs, _b) in lanes for wf in wfs)
    cmax = max(b[c + 1] - b[c] for (_pl, _pd, _w, bs) in lanes
               for b in bs for c in range(len(b) - 1))

    x0 = jnp.asarray(x0, jnp.float32)
    x0_lanes = (x0 if x0.ndim == 3
                else jnp.broadcast_to(
                    x0[None] if x0.ndim == 2
                    else jnp.tile(x0[None, None, :], (1, n, 1)),
                    (S, n, x0.shape[-1])))
    p = int(x0_lanes.shape[-1])
    M = 1
    if mesh is not None:
        if _mesh_axis_size(mesh, lane_axis) != 1:
            raise ValueError(
                "run_sweep_epochs shards the parameter axis only; the "
                f"mesh's {lane_axis!r} axis must have size 1 "
                "(lane-parallel meshes go through run_sweep)")
        M = _mesh_axis_size(mesh, param_axis)
    p_pad = _pad_width(p, M, blocks=(impl == "pallas" and
                                     dispatch.resolve_mode(interpret)
                                     == "compiled"))
    if mesh is None:
        runner = rfast_wavefront_scan(
            lanes[0][1][0], grad_fn, gamma, donate=True, impl=impl,
            interpret=interpret, p_real=(p if p_pad != p else None))
    else:
        ko_fleet = lanes[0][1][0].ko
        base = _mesh_sweep_scan(
            grad_fn, gamma, ko=ko_fleet, n_per_lane=n, mesh=mesh,
            lane_axis=lane_axis, param_axis=param_axis, donate=True,
            impl=impl, interpret=interpret,
            p_real=(p if p_pad != p else None))
        st_sh, wv_sh = sweep_mesh_shardings(mesh, lane_axis, param_axis)

        def runner(packed, w):
            # _scan_epochs drives the unsharded packed layout; bridge it
            # through the mesh engine's singleton group axis (one extra
            # device_put/copy per chunk, amortized by the wave scan)
            pk = jax.tree.map(lambda a: a[None], packed)
            wv = jax.tree.map(lambda a: a[None], w)
            pk = jax.device_put(pk, jax.tree.map(st_sh, pk))
            wv = jax.device_put(wv, jax.tree.map(wv_sh, wv))
            pk = base(pk, wv)
            return jax.tree.map(lambda a: a[0], pk)

    states: list[RFASTState] = []
    metrics: list[list[dict]] = []
    for s, (trace, (plans, _padded, wfs, bounds)) in enumerate(
            zip(traces, lanes)):
        key, init_key = jax.random.split(jax.random.PRNGKey(seeds[s]))
        step_keys = jax.random.split(key, int(trace.K))
        state0 = init_state(plans[0], x0_lanes[s], grad_fn, init_key, H)
        lane_eval = (None if eval_fn is None
                     else lambda x, t: dict(eval_fn(x, t)))
        st, ms = _scan_epochs(list(trace.epochs), plans, wfs, bounds,
                              runner, step_keys, state0, B=B, cmax=cmax,
                              e_a=e_a, H=H, p=p, p_pad=p_pad,
                              eval_every=eval_every, eval_fn=lane_eval,
                              chunk_cb=None)
        states.append(st)
        metrics.append(ms)
    return states, metrics
