"""shard_map R-FAST runtime: spanning-tree gossip as ``lax.ppermute``.

The dense-mixing runtime (runtime.py) is protocol-faithful but lowers the
node-axis mixing to gather/scatter that GSPMD can only realize by
all-gathering full per-node replicas — O(N · |params|) temp memory.  Here
the gossip is explicit: the edge sets of G(W)/G(A) are decomposed into
*matchings* (unique sources AND destinations; see
:func:`repro.core.plan.matchings`) and each matching becomes one
``ppermute`` along the node mesh axes — O(deg · |params|) traffic and
O(1) extra memory, exactly one inter-node hop per edge.

The node axes are MANUAL (shard_map); the 'model' axis stays AUTO, so the
per-node gradient runs the same GSPMD-sharded model code as everywhere
else.  The protocol *math* is :mod:`repro.core.protocol`'s scalar steps
over a :class:`repro.core.plan.CommPlan`'s slot tables — bit-identical to
runtime.py (tested); only the data movement differs.

State layout (node-major, padded to S slots = max degree):
  x, z, g_prev, m : (N, ...)          sharded over node axes
  rho_out         : (N, S_a, ...)     sender's running sums, slot-indexed
  rho_buf         : (N, S_a, ...)     receiver's buffers, slot-indexed
  mail_v          : (N, S_w, ...)     consensus mailboxes (robust mode)
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .plan import CommPlan, as_comm_plan, matchings  # noqa: F401  (re-export)
from .protocol import descent_step, mailbox_merge, momentum_mix, tracking_step
from .topology import Topology

__all__ = ["ShardedState", "matchings", "make_sharded_round",
           "init_sharded_state", "sharded_state_specs",
           "packed_sweep_specs"]

GradFn = Callable[[Any, Any, jax.Array], tuple[jnp.ndarray, Any]]


class ShardedState(NamedTuple):
    step: jnp.ndarray
    x: Any
    z: Any
    g_prev: Any
    rho_out: Any
    rho_buf: Any
    mail_v: Any
    m: Any


def _slot_tables(topo: Topology | CommPlan):
    """Per-slot weight tables indexed by node id (from the CommPlan).

    Compat accessor kept for external consumers (tests/helpers); the
    round builder reads the CommPlan fields directly."""
    plan = as_comm_plan(topo)
    slots_w = [list(s) for s in plan.slots_w]
    slots_a = [list(s) for s in plan.slots_a]
    return (slots_w, slots_a, plan.w_in_table, plan.a_out_table,
            plan.has_in_a)


def _node_index(node_axes: Sequence[str], mesh) -> jnp.ndarray:
    idx = jnp.zeros((), jnp.int32)
    for a in node_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def init_sharded_state(topo: Topology | CommPlan, params: Any, grad_fn: GradFn,
                       batches: Any, keys: Any, *, momentum: float = 0.0,
                       robust: bool = False) -> ShardedState:
    """Host-side init (unsharded semantics; shard via device_put)."""
    plan = as_comm_plan(topo)
    n = plan.n
    x = jax.tree.map(lambda l: jnp.broadcast_to(l[None], (n,) + l.shape),
                     params)
    g0 = jax.vmap(lambda p, b, k: grad_fn(p, b, k)[1])(x, batches, keys)
    sa, sw = plan.s_a, plan.s_w
    zer = lambda S: jax.tree.map(
        lambda l: jnp.zeros((n, S) + l.shape, l.dtype), params)
    return ShardedState(
        # g_prev gets its own buffer: donating rounds forbid aliased leaves
        step=jnp.zeros((), jnp.int32), x=x, z=g0,
        g_prev=jax.tree.map(jnp.copy, g0),
        rho_out=zer(sa), rho_buf=zer(sa),
        mail_v=zer(sw) if robust else None,
        m=jax.tree.map(jnp.zeros_like, x) if momentum else None)


def sharded_state_specs(state: ShardedState, node_axes) -> ShardedState:
    """shard_map in/out specs: node dim manual, everything else auto."""
    na = tuple(node_axes)

    def spec(l):
        return P(na, *([None] * (l.ndim - 1)))

    f = lambda tree: (None if tree is None
                      else jax.tree.map(spec, tree))
    return ShardedState(
        step=P(), x=f(state.x), z=f(state.z), g_prev=f(state.g_prev),
        rho_out=f(state.rho_out), rho_buf=f(state.rho_buf),
        mail_v=f(state.mail_v), m=f(state.m))


def packed_sweep_specs(lane_axis: str = "data",
                       param_axis: str | None = None):
    """Per-leaf spec builders for the mesh-mapped fleet sweep.

    The sweep engine stacks its packed state and wave tables on a leading
    *lane-group* axis (one group of ``S_loc`` lanes per ``lane_axis``
    device) and keeps the lane-dense parameter axes ``(R, LANE)`` last.
    Returns two ``leaf -> PartitionSpec`` callables for ``jax.tree.map``:

    * ``state_spec``: ``P(lane_axis, None, ..., param_axis, None)`` —
      group axis sharded over the lanes, the ``R`` rows of the parameter
      axis sharded over ``param_axis`` (replicated when ``param_axis`` is
      None).
    * ``wave_spec``:  ``P(lane_axis, None, ...)`` — plan tables and step
      keys are lane-group data; their trailing axes are table axes, not
      parameters, so only the leading axis is sharded.
    """

    def state_spec(leaf):
        return P(lane_axis, *([None] * (leaf.ndim - 3)), param_axis, None)

    def wave_spec(leaf):
        return P(lane_axis, *([None] * (leaf.ndim - 1)))

    return state_spec, wave_spec


def make_sharded_round(
    topo: Topology | CommPlan,
    grad_fn: GradFn,
    mesh,
    *,
    gamma,
    node_axes: Sequence[str],
    momentum: float = 0.0,
    robust: bool = False,
    donate: bool = False,
):
    """Build ``round_fn(state, batches, keys, masks) -> (state, metrics)``.

    ``masks``: (n, S_w + S_a) float deliveries in robust mode, else None.
    ``donate=True`` jits the round with the state donated (in-place
    protocol-state commits; callers must rebind and not reuse the old
    state).
    """
    plan = as_comm_plan(topo)
    slots_w, slots_a = plan.slots_w, plan.slots_a
    w_diag = jnp.asarray(plan.w_diag)
    a_diag = jnp.asarray(plan.a_diag)
    w_in_t = jnp.asarray(plan.w_in_table)
    a_out_t = jnp.asarray(plan.a_out_table)
    has_in_t = jnp.asarray(plan.has_in_a)
    na = tuple(node_axes)
    ax = na if len(na) > 1 else na[0]
    S_w, S_a = plan.s_w, plan.s_a

    # The collectives are chained through an optimization_barrier token so
    # every device issues them in the same order — independent ppermutes
    # may otherwise be scheduled in different orders by the concurrent
    # thunk executor and deadlock the rendezvous (observed on XLA:CPU; on
    # TPU the fixed order also makes the ICI schedule deterministic).
    def tperm(tree, perm, token):
        if not perm:
            return jax.tree.map(jnp.zeros_like, tree), token
        def one(l):
            l, _ = jax.lax.optimization_barrier((l, token))
            return jax.lax.ppermute(l, ax, perm=list(perm))
        out = jax.tree.map(one, tree)
        new_token = jax.tree.leaves(out)[0].ravel()[:1]
        return out, new_token

    def block_step(state: ShardedState, batch, key, masks):
        idx = _node_index(na, mesh)
        lr = gamma(state.step) if callable(gamma) else gamma
        token = jnp.zeros((1,), jnp.float32)
        sq = lambda tree: jax.tree.map(lambda l: l[0], tree)
        unsq = lambda tree: jax.tree.map(lambda l: l[None], tree)

        # (S1) local descent direction
        if momentum:
            m = jax.tree.map(lambda mm, zz: momentum_mix(mm, zz, momentum),
                             state.m, state.z)
            v = jax.tree.map(lambda xx, mm: descent_step(xx, mm, lr),
                             state.x, m)
        else:
            m = None
            v = jax.tree.map(lambda xx, zz: descent_step(xx, zz, lr),
                             state.x, state.z)

        # (S2a) consensus pull: one ppermute per W-matching
        x_new = jax.tree.map(lambda vv: w_diag[idx] * vv, v)
        mail_new = [] if robust else None
        for s in range(S_w):
            rv, token = tperm(v, slots_w[s] if s < len(slots_w) else [],
                              token)
            if robust:
                mk = masks[0, s] if masks is not None else 1.0
                old = jax.tree.map(lambda l: l[:, s], state.mail_v)
                rv = jax.tree.map(
                    lambda r, o: mailbox_merge(r, o, mk), rv, old)
                mail_new.append(rv)
            x_new = jax.tree.map(
                lambda xn, r: xn + (w_in_t[s, idx] * r).astype(xn.dtype),
                x_new, rv)

        # (S2b) fresh gradient at the mixed point
        loss, g_new = grad_fn(sq(x_new), sq(batch), key[0])
        g_new = unsq(g_new)

        # robust tracking: one ppermute per A-matching
        recv = jax.tree.map(jnp.zeros_like, state.z)
        buf_new = []
        for s in range(S_a):
            rr, token = tperm(jax.tree.map(lambda l: l[:, s],
                                           state.rho_out),
                              slots_a[s] if s < len(slots_a) else [],
                              token)
            mk = (masks[0, S_w + s] if (robust and masks is not None)
                  else 1.0)
            old = jax.tree.map(lambda l: l[:, s], state.rho_buf)
            gate = mk * has_in_t[s, idx]
            recv = jax.tree.map(
                lambda rc, r, o: rc + (gate * (r - o)).astype(rc.dtype),
                recv, rr, old)
            buf_new.append(jax.tree.map(
                lambda r, o: mailbox_merge(r, o, gate), rr, old))

        z_half = jax.tree.map(
            lambda zz, rc, gn, go: tracking_step(zz, rc, gn, go),
            state.z, recv, g_new, state.g_prev)
        z_new = jax.tree.map(lambda zh: (a_diag[idx] * zh).astype(zh.dtype),
                             z_half)
        rho_out_new = jax.tree.map(
            lambda ro, zh: ro + jnp.stack(
                [(a_out_t[s, idx] * zh[0]).astype(ro.dtype)
                 for s in range(S_a)])[None],
            state.rho_out, z_half)
        rho_buf_new = jax.tree.map(
            lambda *cols: jnp.stack([c[0] for c in cols])[None], *buf_new)
        mail_v_new = None
        if robust:
            mail_v_new = jax.tree.map(
                lambda *cols: jnp.stack([c[0] for c in cols])[None],
                *mail_new)

        new_state = ShardedState(
            step=state.step + 1, x=x_new, z=z_new, g_prev=g_new,
            rho_out=rho_out_new, rho_buf=rho_buf_new,
            mail_v=mail_v_new, m=m)
        return new_state, loss[None]

    def round_fn(state: ShardedState, batches, keys, masks=None):
        specs = sharded_state_specs(state, na)
        bspec = jax.tree.map(
            lambda l: P(na, *([None] * (l.ndim - 1))), batches)
        kspec = P(na)
        mspec = P(na) if masks is not None else None
        in_specs = (specs, bspec, kspec)
        args = (state, batches, keys)
        if masks is not None:
            in_specs = in_specs + (mspec,)
            args = args + (masks,)
            fn = block_step
        else:
            fn = lambda s, b, k: block_step(s, b, k, None)
        out_specs = (specs, P(na))
        new_state, losses = jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=set(na), check_vma=False)(*args)
        return new_state, {"loss": losses.mean(), "losses": losses}

    if donate:
        return jax.jit(round_fn, donate_argnums=(0,))
    return round_fn
