"""Plain reference of the dense decoder LM that the configurations
``rfast-100m`` describes, in ``jax.numpy`` and float32, every matrix
product at the precision the configuration states
(``matmul_precision``: on a TPU, ``"default"`` is one bfloat16 pass with
float32 accumulation, ``"highest"`` full float32).  It imports nothing of
the program under test.

The model: token embedding; per layer a pre-norm causal self-attention
(grouped-query when ``n_kv_heads < n_heads``, rotary positions on the
two halves of each head, scale ``head_dim ** -0.5``) and a pre-norm SwiGLU
MLP ``(silu(x W_g) * (x W_i)) W_o``, each added to the residual stream;
a final norm; logits by ``lm_head`` or, with tied embeddings, by the
embedding's transpose; the loss is the mean next-token cross entropy.
Norms are RMSNorm with a learned scale (``norm == "rmsnorm"``) or
LayerNorm without parameters (``"nonparam_ln"``, as OLMo has it), with
``norm_eps`` inside the square root.  No biases.

Parameters travel as one flat float32 vector, leaf after leaf in the
order of :func:`layout` (the sorted-key order of the nested parameter
dict), zero-padded to a multiple of 128.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 128


def layout(cfg: dict) -> list[tuple[str, tuple[int, ...]]]:
    """``(name, shape)`` of every parameter leaf, in flat order."""
    d, L, V, ff = cfg["d_model"], cfg["n_layers"], cfg["vocab"], cfg["d_ff"]
    hd = d // cfg["n_heads"]
    q, kv = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd
    rms = cfg["norm"] == "rmsnorm"
    leaves = [("embed", (V, d))]
    if rms:
        leaves.append(("final_norm.scale", (d,)))
    leaves += [("layers.attn.wk", (L, d, kv)), ("layers.attn.wo", (L, q, d)),
               ("layers.attn.wq", (L, d, q)), ("layers.attn.wv", (L, d, kv))]
    if rms:
        leaves += [("layers.ln1.scale", (L, d)), ("layers.ln2.scale", (L, d))]
    leaves += [("layers.mlp.wg", (L, d, ff)), ("layers.mlp.wi", (L, d, ff)),
               ("layers.mlp.wo", (L, ff, d))]
    if not cfg["tie_embeddings"]:
        leaves.append(("lm_head", (d, V)))
    return leaves


def segments(cfg: dict) -> list[tuple[str, int, int]]:
    """``(name, offset, size)`` of every leaf in the flat vector."""
    out, off = [], 0
    for name, shape in layout(cfg):
        size = math.prod(shape)
        out.append((name, off, size))
        off += size
    return out


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) for _, s in layout(cfg))


def flat_width(cfg: dict) -> int:
    return -(-n_params(cfg) // PAD_TO) * PAD_TO


def _init_leaf(name: str, shape, key):
    if name.endswith(".scale"):
        return jnp.ones(shape, jnp.float32)
    if name == "embed":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    return jax.random.normal(key, shape, jnp.float32) * shape[-2] ** -0.5


def init_flat(cfg: dict, key) -> jnp.ndarray:
    """Random weights from ``key`` as the flat vector (jit it: one call
    makes every leaf on the device)."""
    parts = [_init_leaf(name, shape, jax.random.fold_in(key, i)).reshape(-1)
             for i, (name, shape) in enumerate(layout(cfg))]
    pad = flat_width(cfg) - n_params(cfg)
    return jnp.pad(jnp.concatenate(parts), (0, pad))


def unflatten(cfg: dict, flat: jnp.ndarray) -> dict:
    return {name: flat[off:off + size].reshape(shape)
            for (name, off, size), (_, shape)
            in zip(segments(cfg), layout(cfg))}


def _precision(cfg: dict):
    return jax.lax.Precision[cfg["matmul_precision"].upper()]


def _norm(cfg, x, scale=None):
    eps = cfg["norm_eps"]
    if cfg["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """Rotary positions on the two halves of each head: x (B, S, H, hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(ang)[None, :, None].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None].astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, lp, h):
    """One decoder layer; ``lp`` maps leaf names to this layer's slice."""
    B, S, d = h.shape
    H, KV = cfg["n_heads"], cfg["n_kv_heads"]
    hd = d // H
    prec = _precision(cfg)
    mm = partial(jnp.matmul, precision=prec)
    a = _norm(cfg, h, lp.get("layers.ln1.scale"))
    q = _rope(mm(a, lp["layers.attn.wq"]).reshape(B, S, H, hd),
              cfg["rope_theta"])
    k = _rope(mm(a, lp["layers.attn.wk"]).reshape(B, S, KV, hd),
              cfg["rope_theta"])
    v = mm(a, lp["layers.attn.wv"]).reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)       # head h reads kv head h // R
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) * hd ** -0.5
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                   precision=prec).reshape(B, S, H * hd)
    h = h + mm(o, lp["layers.attn.wo"])
    m = _norm(cfg, h, lp.get("layers.ln2.scale"))
    f = jax.nn.silu(mm(m, lp["layers.mlp.wg"])) * mm(m, lp["layers.mlp.wi"])
    return h + mm(f, lp["layers.mlp.wo"])


def logits(cfg: dict, flat: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    p = unflatten(cfg, flat)
    h = p["embed"][tokens]
    per_layer = {k: v for k, v in p.items() if k.startswith("layers.")}
    h, _ = jax.lax.scan(lambda h, lp: (_layer(cfg, lp, h), None), h,
                        per_layer)
    h = _norm(cfg, h, p.get("final_norm.scale"))
    head = p["embed"].T if cfg["tie_embeddings"] else p["lm_head"]
    return jnp.matmul(h, head, precision=_precision(cfg))


def loss(cfg: dict, flat, tokens, labels) -> jnp.ndarray:
    lg = logits(cfg, flat, tokens)
    lse = jax.scipy.special.logsumexp(lg, -1)
    tgt = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - tgt)
