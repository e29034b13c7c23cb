"""Work and bytes of a cell, worked out from its shapes alone.

* :func:`flops_per_token` — model FLOPs of one training token, forward
  and backward: ``6 * N_matmul`` plus attention's ``12 * L * S * (H *
  head_dim)`` (scores and weighted values over the whole ``S x S``
  square, as the model computes them).  ``N_matmul`` counts every
  matrix of the layers and the output projection (the tied embedding's
  transpose, where tied); the embedding's gather is not a matrix
  product and is not counted.  Each event's gradient counts once, how
  many chips compute it notwithstanding.
* :func:`state_rows` — rows of the engine's packed state: ``4n`` node
  rows (x, v, z, g_prev), ``2 E_A`` running sums and buffers, and ``H``
  history slots of ``n + E_A`` rows, ``H = D + 2``.
* :func:`commit_bytes` — HBM bytes one commit-kernel lane moves: per
  tile of the flat axis it reads ``3 + 2 ka + ko`` tiles and writes
  ``1 + ka + ko`` (DESIGN.md section 10.3), so ``(4 + 3 ka + 2 ko)``
  float32 rows of the width one device holds.
"""
from __future__ import annotations

import numpy as np


def matmul_params(cfg: dict) -> int:
    d, L, ff, V = cfg["d_model"], cfg["n_layers"], cfg["d_ff"], cfg["vocab"]
    hd = d // cfg["n_heads"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return L * per_layer + d * V


def flops_per_token(cfg: dict, seq: int) -> int:
    attn = 12 * cfg["n_layers"] * seq * cfg["d_model"]
    return 6 * matmul_params(cfg) + attn


def degrees(A: np.ndarray) -> tuple[int, int]:
    """(ka, ko): the largest number of A-edges into / out of a node."""
    off = (A > 0) & ~np.eye(A.shape[0], dtype=bool)
    return int(off.sum(1).max()), int(off.sum(0).max())


def n_edges(M: np.ndarray) -> int:
    return int(((M > 0) & ~np.eye(M.shape[0], dtype=bool)).sum())


def state_rows(n: int, e_a: int, H: int) -> int:
    return 4 * n + 2 * e_a + H * (n + e_a)


def commit_bytes(width: int, ka: int, ko: int) -> int:
    return (4 + 3 * ka + 2 * ko) * width * 4
