"""The benchmark's traffic: communication graphs and asynchronous
event schedules, made from a traffic file and the seed.

A copy of the frozen-graph path of the program's scenario clock
(``repro.core.scenario.NetworkScenario.realize``) and of its tree
weights (``repro.core.topology``), kept here so that the traffic a
cell measures cannot change with the program; a test holds it equal to
the program's ``straggler`` schedule, event for event, for the cells'
mixes.  A traffic file holds
the parameters:

* ``compute_time`` / ``last_node_compute_time`` — mean compute interval
  of every node, and of the last one (the straggler);
* ``jitter`` — multiplicative uniform jitter of each interval;
* ``latency`` — mean (exponential) packet latency;
* ``loss`` — Bernoulli packet loss probability;
* ``D_max`` — the hard staleness bound (the paper's Assumption 3(ii)):
  a read that would be staler is forced to the bound.

Packets carry the sender's post-update stamp; a receiver consumes the
largest stamp delivered so far.  Event ``k`` of the schedule is the
wake-up of ``agent[k]``; ``stamp_v[k, e]`` / ``stamp_rho[k, e]`` is the
stamp of the payload on W-edge / A-edge ``e`` that it reads.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def tree_parents(n: int) -> list[int | None]:
    """Parent of each node in the binary tree rooted at node 0."""
    return [None] + [(i - 1) // 2 for i in range(1, n)]


def tree_weights(n: int) -> tuple[np.ndarray, np.ndarray]:
    """(W, A) of a binary tree: each node pulls v from its parent with
    uniform row-stochastic W, and pushes z to its parent with uniform
    column-stochastic A (both with their self weight)."""
    W = np.zeros((n, n))
    A = np.zeros((n, n))
    in_w = {i: [] for i in range(n)}
    out_a = {i: [] for i in range(n)}
    for i, par in enumerate(tree_parents(n)):
        if par is not None:
            in_w[i].append(par)
            out_a[i].append(par)
    for i in range(n):
        W[i, i] = 1.0 / (len(in_w[i]) + 1)
        for j in in_w[i]:
            W[i, j] = W[i, i]
        A[i, i] = 1.0 / (len(out_a[i]) + 1)
        for j in out_a[i]:
            A[j, i] = A[i, i]
    return W, A


TOPOLOGIES = {"binary_tree": tree_weights}


def edges(M: np.ndarray) -> list[tuple[int, int]]:
    """Edges ``(j, i)`` (j sends to i) of a weight matrix, in the order
    the program's topologies list them."""
    n = M.shape[0]
    return [(j, i) for i in range(n) for j in range(n)
            if i != j and M[i, j] > 0]


@dataclasses.dataclass(frozen=True)
class Schedule:
    agent: np.ndarray       # (K,) int32
    stamp_v: np.ndarray     # (K, max(1, E_W)) int32
    stamp_rho: np.ndarray   # (K, max(1, E_A)) int32
    times: np.ndarray       # (K,) float64 virtual completion times
    D: int                  # realized largest staleness
    T: int                  # realized largest activation gap


def realize(traffic: dict, W: np.ndarray, A: np.ndarray, K: int,
            seed: int) -> Schedule:
    """K events of the traffic's clocks and channels over (W, A)."""
    rng = np.random.default_rng(seed)
    n = W.shape[0]
    base = np.full(n, float(traffic["compute_time"]))
    base[-1] = float(traffic.get("last_node_compute_time",
                                 traffic["compute_time"]))
    jitter, latency = float(traffic["jitter"]), float(traffic["latency"])
    loss, D_max = float(traffic["loss"]), int(traffic["D_max"])
    edges_w, edges_a = edges(W), edges(A)
    in_w = {i: [e for e, (_, d) in enumerate(edges_w) if d == i]
            for i in range(n)}
    in_a = {i: [e for e, (_, d) in enumerate(edges_a) if d == i]
            for i in range(n)}
    out_w = {i: [e for e, (s, _) in enumerate(edges_w) if s == i]
             for i in range(n)}
    out_a = {i: [e for e, (s, _) in enumerate(edges_a) if s == i]
             for i in range(n)}
    arr_w = [[] for _ in edges_w]
    arr_a = [[] for _ in edges_a]
    best_w = np.zeros(len(edges_w), np.int64)
    best_a = np.zeros(len(edges_a), np.int64)
    clocks = rng.uniform(0.0, 1.0, n) * base

    agent = np.zeros(K, np.int32)
    stamp_v = np.zeros((K, max(1, len(edges_w))), np.int32)
    stamp_rho = np.zeros((K, max(1, len(edges_a))), np.int32)
    times = np.zeros(K)
    max_delay = 0

    def consume(queue, best, e, now, k):
        keep = []
        for t_arr, s in queue[e]:
            if t_arr <= now:
                best[e] = max(best[e], s)
            else:
                keep.append((t_arr, s))
        queue[e] = keep
        best[e] = max(best[e], k - D_max)

    def send(queue, e, now, k):
        if rng.uniform() >= loss:          # one draw per packet, always
            queue[e].append((now + rng.exponential(latency), k + 1))

    for k in range(K):
        a = int(np.argmin(clocks))
        now = float(clocks[a])
        agent[k], times[k] = a, now
        for e in in_w[a]:
            consume(arr_w, best_w, e, now, k)
        for e in in_a[a]:
            consume(arr_a, best_a, e, now, k)
        if edges_w:
            stamp_v[k] = best_w
        if edges_a:
            stamp_rho[k] = best_a
        for e in in_w[a]:
            max_delay = max(max_delay, k - int(best_w[e]))
        for e in in_a[a]:
            max_delay = max(max_delay, k - int(best_a[e]))
        for e in out_w[a]:
            send(arr_w, e, now, k)
        for e in out_a[a]:
            send(arr_a, e, now, k)
        clocks[a] = now + base[a] * (1.0 + rng.uniform(-jitter, jitter))
    return Schedule(agent, stamp_v, stamp_rho, times, max(1, max_delay),
                    _activation_gap(agent, n))


def _activation_gap(agent: np.ndarray, n: int) -> int:
    """Smallest T such that every window of T events wakes every node."""
    last = -np.ones(n, np.int64)
    gap = 0
    for k, a in enumerate(agent):
        last[a] = k
        if (last >= 0).all():
            gap = max(gap, k - int(last.min()))
    return gap + 1
