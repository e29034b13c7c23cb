"""Plain reference of asynchronous R-FAST training: Algorithm 2 of the
paper (Zhu et al., arXiv:2307.11617), one event after another, in
``jax.numpy`` and float32.  It imports nothing of the program under
test.

Event ``k`` wakes ``agent[k] = a``, which reads stale payloads: the ``v``
of each in-neighbour ``j`` on the pull graph W as it stood after event
``stamp_v[k, e] - 1`` (stamp 0 is the initial state), and likewise the
running sum ``rho`` of each A-edge into ``a``.  Then

    v_a     = x_a - gamma * z_a                          (S.1)
    x_a     = W[a,a] v_a + sum_j W[a,j] v_j^stale        (S.2a)
    g       = grad f_a(x_a; batch of event k)
    z_half  = z_a + sum_e (rho_e^stale - rho~_e) + g - g_prev_a   (S.2b)
    z_a     = A[a,a] z_half ; rho_e += A[i,a] z_half for e = (a -> i)   (S.2c)
    rho~_e  = rho_e^stale for e into a ; g_prev_a = g    (S.4)

from x_i = x0, z_i = g_prev_i = grad f_i(x0; init batch i), v = rho =
rho~ = 0.  Only the payload versions a later read can still reach are
kept.  Rows live on the devices given (a node's rows on one, its
payload versions on another), so a state larger than one chip fits.

The batches are the program's, drawn from the seed the way its engines
draw them: ``key, init_key = split(PRNGKey(seed))``, event ``k`` takes
``split(key, K)[k]``, node ``i``'s initial gradient ``split(init_key,
n)[i]``; a gradient's key is split in two, the first half folded with the
node id, and ``B x (S+1)`` uniforms mapped through the Zipf CDF of the
token ids give tokens and next-token labels.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def zipf_cdf(vocab: int, s: float) -> np.ndarray:
    """float32 CDF of the Zipf marginal p(t) ~ (t + 1) ** -s."""
    w = np.arange(1, vocab + 1, dtype=np.float64) ** (-s)
    return np.cumsum(w / w.sum()).astype(np.float32)


def program_keys(seed32: int, K: int, n: int):
    """(event keys (K, 2), initial-gradient keys (n, 2))."""
    key, init_key = jax.random.split(jax.random.PRNGKey(seed32))
    return jax.random.split(key, K), jax.random.split(init_key, n)


@partial(jax.jit, static_argnames=("B", "S"))
def sample_batch(cdf, key, node, *, B: int, S: int):
    bkey, _ = jax.random.split(key)
    u = jax.random.uniform(jax.random.fold_in(bkey, node), (B, S + 1))
    toks = jnp.clip(jnp.searchsorted(cdf, u), 0, cdf.shape[0] - 1)
    toks = toks.astype(jnp.int32)
    return toks[:, :-1], toks[:, 1:]


@partial(jax.jit, static_argnames=("gamma",))
def _descend(x, z, *, gamma):
    return x - gamma * z


@jax.jit
def _axpy(a, x, y):
    return a * x + y


@jax.jit
def _track(z, recv, g, g_prev):
    return z + recv + g - g_prev


class _Versions:
    """Payload versions of one row: (stamp written, array or None = 0)."""

    def __init__(self):
        self.items = [(0, None)]

    def read(self, stamp: int):
        return [a for s, a in self.items if s <= stamp][-1]

    def add(self, stamp: int, arr):
        self.items.append((stamp, arr))

    def prune(self, min_future_stamp: int):
        keep = [i for i, (s, _) in enumerate(self.items)
                if s <= min_future_stamp]
        self.items = self.items[keep[-1]:] if keep else self.items


def train(*, loss_fn, W, A, agent, stamp_v, stamp_rho, x0, gamma: float,
          event_keys, init_keys, batch, devices):
    """Run ``len(agent)`` events; returns the final per-node iterates.

    ``loss_fn(flat, tokens, labels)``; ``batch(key, node) -> (tokens,
    labels)``; ``x0`` a flat float32 vector."""
    n = W.shape[0]
    nd = len(devices)
    home = lambda i: devices[i % nd]
    spare = lambda i: devices[(n + i) % nd]
    put = jax.device_put
    grad = jax.jit(jax.grad(loss_fn))
    edges_w = [(j, i) for i in range(n) for j in range(n)
               if i != j and W[i, j] > 0]
    edges_a = [(j, i) for i in range(n) for j in range(n)
               if i != j and A[i, j] > 0]

    def gradient(i, x, key):
        toks, labels = batch(put(key, home(i)), i)
        return grad(x, toks, labels)

    x, z, g_prev, v = [], [], [], [None] * n
    for i in range(n):
        xi = put(x0, home(i))
        x.append(xi)
        g = gradient(i, xi, init_keys[i])
        z.append(g)
        g_prev.append(g)
    rho = [None] * len(edges_a)           # at the sender
    rho_buf = [None] * len(edges_a)       # at the receiver
    v_ver = [_Versions() for _ in range(n)]
    rho_ver = [_Versions() for _ in edges_a]

    K = len(agent)
    for k in range(K):
        a = int(agent[k])
        dev = home(a)
        v_new = _descend(x[a], z[a], gamma=float(gamma))
        xa = float(W[a, a]) * v_new
        for e, (j, i) in enumerate(edges_w):
            if i == a:
                vj = v_ver[j].read(int(stamp_v[k, e]))
                if vj is not None:
                    xa = _axpy(float(W[a, j]), put(vj, dev), xa)
        g = gradient(a, xa, event_keys[k])
        recv = jnp.zeros_like(xa)
        stale = {}
        for e, (j, i) in enumerate(edges_a):
            if i == a:
                r = rho_ver[e].read(int(stamp_rho[k, e]))
                stale[e] = None if r is None else put(r, dev)
                if stale[e] is not None:
                    recv = recv + stale[e]
                if rho_buf[e] is not None:
                    recv = recv - rho_buf[e]
        z_half = _track(z[a], recv, g, g_prev[a])
        z[a] = float(A[a, a]) * z_half
        for e, (j, i) in enumerate(edges_a):
            if j == a:
                out = float(A[i, a]) * z_half
                rho[e] = out if rho[e] is None else rho[e] + out
                rho_ver[e].add(k + 1, put(rho[e], spare(a)))
        for e in stale:
            rho_buf[e] = stale[e]
        x[a], v[a], g_prev[a] = xa, v_new, g
        v_ver[a].add(k + 1, put(v_new, spare(a)))
        # keep only versions a later read can reach (stamps only grow)
        for j in range(n):
            later = [int(stamp_v[k + 1:, e].min())
                     for e, (s, _) in enumerate(edges_w) if s == j and k + 1 < K]
            v_ver[j].prune(min(later, default=k + 1))
        for e in range(len(edges_a)):
            rho_ver[e].prune(int(stamp_rho[k + 1:, e].min()) if k + 1 < K
                             else k + 1)
    return x
