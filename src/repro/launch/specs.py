"""Input specs (ShapeDtypeStruct stand-ins) and step functions for every
(architecture × input shape) combination — the dry-run's subject matter.

Shapes (assigned):
  train_4k     seq 4096    global_batch 256   train_step (R-FAST round)
  prefill_32k  seq 32768   global_batch 32    prefill (forward logits)
  decode_32k   seq 32768   global_batch 128   serve_step (1 token + cache)
  long_500k    seq 524288  global_batch 1     serve_step, sub-quadratic only
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.plan import build_comm_plan
from repro.core.runtime import init_node_state, make_rfast_round
from repro.core.runtime_sharded import (init_sharded_state,
                                        make_sharded_round,
                                        packed_sweep_specs)
from repro.core.topology import binary_tree
from repro.models import sharding as msh
from repro.models.config import ModelConfig
from repro.models.transformer import (decode_step, forward, init_cache,
                                      init_params, loss_fn)
from . import shardings as sh

__all__ = ["SHAPES", "LONG_WINDOW", "shape_supported", "build_train",
           "build_prefill", "build_decode", "build_case",
           "packed_sweep_specs"]
# packed_sweep_specs is re-exported for launch-level consumers: the
# mesh-mapped fleet sweep's packed state has no logical axis names (a
# flat (group, lanes·n, 4, p) substrate), so it bypasses the name-table
# resolution below and uses the fixed per-rank specs from
# core/runtime_sharded — lane-group axis -> lane_axis ('data'), flat
# parameter axis -> param_axis ('model').  See DESIGN.md §13.

SHAPES = {
    "train_4k": dict(seq=4096, batch=256, kind="train"),
    "prefill_32k": dict(seq=32768, batch=32, kind="prefill"),
    "decode_32k": dict(seq=32768, batch=128, kind="decode"),
    "long_500k": dict(seq=524288, batch=1, kind="decode", long=True),
}
LONG_WINDOW = 8192          # sliding window used by dense archs at 500k

# measured per-arch tuning (reports/roofline_*.json): sequence-parallel
# residual sharding regresses MHA-32 (deepseek-7b, resharding between
# head- and seq-layouts each layer) and deepseek-v2's MoE dispatch.
SEQ_PARALLEL_OPT_OUT = {"deepseek-7b", "deepseek-v2-236b"}


def shape_supported(cfg: ModelConfig, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and cfg.enc_dec:
        return False, ("enc-dec audio model: quadratic encoder context, no "
                       "sliding-window decoder analogue (DESIGN.md §4)")
    return True, ""


def _long_variant(cfg: ModelConfig) -> ModelConfig:
    """Sub-quadratic serving variant for the 500k shape."""
    if cfg.mixer == "ssm":
        return cfg
    if cfg.attn_window and cfg.attn_window <= LONG_WINDOW:
        return cfg
    return dataclasses.replace(cfg, attn_window=LONG_WINDOW)


# activation rules (models/sharding.py logical axes -> mesh axes)
def act_rules(batch_axes, seq_parallel: bool = False) -> dict:
    """seq_parallel: shard the residual stream's sequence dim over
    'model' (sequence parallelism) — per-layer activation all-reduces
    become all-gather/reduce-scatter pairs and the attention-score
    working set shrinks by the model-axis factor (§Perf 1.It5: memory
    −44%, collective −60%, temp −66% on llama3-8b train_4k)."""
    return dict(
        batch=tuple(batch_axes) if batch_axes else None,
        seq="model" if seq_parallel else None,
        embed=None, mlp="model", heads="model",
        kv_heads="model", head_dim=None, vocab="model", expert="model",
        cap=None, ssm_inner="model", ssm_state=None, kv_seq=None,
        frontend=None, node=None,
    )


def _sds(struct_tree, shardings_tree):
    return jax.tree.map(
        lambda s, shd: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shd),
        struct_tree, shardings_tree)


def _frontend_struct(cfg, n_lead, b, dtype):
    if not cfg.frontend:
        return None
    shape = (cfg.frontend_seq, cfg.frontend_dim or cfg.d_model)
    lead = ((n_lead, b) if n_lead else (b,))
    return jax.ShapeDtypeStruct(lead + shape, dtype)


# ------------------------------------------------------------------ #
# train_4k: one R-FAST production round
# ------------------------------------------------------------------ #
def build_train(cfg: ModelConfig, mesh, *, seq: int, global_batch: int,
                rules=None, node_axes=None, gamma=1e-2, topo=None,
                dtype=jnp.bfloat16, unroll=False, comm: str = "ppermute",
                ce: str = "lse", seq_parallel: bool | None = None):
    """comm="ppermute": shard_map spanning-tree gossip (production).
    comm="dense": GSPMD dense-mixing baseline (paper-naive port).
    ce: cross-entropy mode (see models.transformer.loss_fn)."""
    rules = rules or sh.RULES_BASE
    if seq_parallel is None:
        seq_parallel = cfg.name not in SEQ_PARALLEL_OPT_OUT
    if node_axes is None:
        node_axes = tuple(a for a in mesh.axis_names if a != "model")
    n_nodes = sh.mesh_axis_size(mesh, tuple(node_axes))
    b_node = global_batch // n_nodes
    assert b_node >= 1, (global_batch, n_nodes)
    topo = topo or binary_tree(n_nodes)
    spec = build_comm_plan(topo)

    s_text = seq - (cfg.frontend_seq if (cfg.frontend and not cfg.enc_dec)
                    else 0)

    def grad_fn(params, batch, key):
        del key

        def loss(p):
            return loss_fn(cfg, p, batch["tokens"], batch["labels"],
                           batch.get("frontend"), remat=True, unroll=unroll,
                           ce=ce)
        return jax.value_and_grad(loss)(params)

    if comm == "ppermute":
        round_fn = make_sharded_round(topo, grad_fn, mesh, gamma=gamma,
                                      node_axes=node_axes)
    else:
        round_fn = make_rfast_round(spec, grad_fn, gamma=gamma,
                                    node_axes=node_axes)

    # mesh axes not used by the node dim carry the *within-node* batch
    # (data parallelism inside a node group — paper Remark 9)
    inner_batch = tuple(a for a in mesh.axis_names
                        if a != "model" and a not in node_axes)
    arules = act_rules(inner_batch, seq_parallel=seq_parallel)

    def train_step(state, batches, keys):
        with msh.mesh_rules(mesh, arules):
            return round_fn(state, batches, keys, None)

    # ---- structs ----------------------------------------------------- #
    params_s = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.random.PRNGKey(0))
    batch_s = {
        "tokens": jax.ShapeDtypeStruct((n_nodes, b_node, s_text), jnp.int32),
        "labels": jax.ShapeDtypeStruct((n_nodes, b_node, s_text), jnp.int32),
    }
    fs = _frontend_struct(cfg, n_nodes, b_node, dtype)
    if fs is not None:
        batch_s["frontend"] = fs
    keys_s = jax.ShapeDtypeStruct((n_nodes, 2), jnp.uint32)

    # ---- shardings (computed on the STACKED structs: the node/edge dim
    # is part of the leaf shape, so base-axis alignment stays correct) --- #
    node_lead = (tuple(node_axes),)

    if comm == "ppermute":
        state_s = jax.eval_shape(
            lambda p, b, k: init_sharded_state(topo, p, grad_fn, b, k),
            params_s, batch_s, keys_s)
        x_sh = sh.tree_shardings(state_s.x, mesh, rules, lead_axes=node_lead)
        slot_lead = (tuple(node_axes), None)
        rho_sh = sh.tree_shardings(state_s.rho_out, mesh, rules,
                                   lead_axes=slot_lead)
        state_sh = type(state_s)(
            step=NamedSharding(mesh, P()),
            x=x_sh, z=x_sh, g_prev=x_sh,
            rho_out=rho_sh, rho_buf=rho_sh,
            mail_v=None, m=None,
        )
    else:
        state_s = jax.eval_shape(
            lambda p, b, k: init_node_state(spec, p, grad_fn, b, k),
            params_s, batch_s, jax.random.PRNGKey(0))
        x_sh = sh.tree_shardings(state_s.x, mesh, rules, lead_axes=node_lead)
        rho_sh = sh.tree_shardings(state_s.rho, mesh, rules,
                                   lead_axes=node_lead)
        state_sh = type(state_s)(
            step=NamedSharding(mesh, P()),
            x=x_sh, z=x_sh, g_prev=x_sh,
            rho=rho_sh, rho_buf=rho_sh,
            mail_v=None, m=None,
        )
    ib = tuple(inner_batch) if inner_batch else None
    batch_sh = jax.tree.map(
        lambda s: NamedSharding(
            mesh, P(*((tuple(node_axes), ib)
                      + (None,) * (len(s.shape) - 2)))),
        batch_s)
    keys_sh = NamedSharding(mesh, P(tuple(node_axes)))

    args = (_sds(state_s, state_sh), _sds(batch_s, batch_sh),
            jax.ShapeDtypeStruct(keys_s.shape, keys_s.dtype,
                                 sharding=keys_sh))
    return train_step, args


# ------------------------------------------------------------------ #
# prefill_32k: full forward producing logits
# ------------------------------------------------------------------ #
def build_prefill(cfg: ModelConfig, mesh, *, seq: int, global_batch: int,
                  rules=None, dtype=jnp.bfloat16, unroll=False,
                  seq_parallel: bool | None = None):
    rules = rules or sh.RULES_BASE
    if seq_parallel is None:
        seq_parallel = cfg.name not in SEQ_PARALLEL_OPT_OUT
    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    arules = act_rules(batch_axes, seq_parallel=seq_parallel)
    s_text = seq - (cfg.frontend_seq if (cfg.frontend and not cfg.enc_dec)
                    else 0)

    def prefill_step(params, tokens, frontend=None):
        with msh.mesh_rules(mesh, arules):
            logits, _ = forward(cfg, params, tokens, frontend, remat=True,
                                last_only=True, unroll=unroll)
        return logits

    params_s = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.random.PRNGKey(0))
    params_sh = sh.tree_shardings(params_s, mesh, rules)
    toks = jax.ShapeDtypeStruct(
        (global_batch, s_text), jnp.int32,
        sharding=NamedSharding(mesh, sh.batch_pspec(
            2, mesh, batch_axes, (global_batch, s_text))))
    args = [_sds(params_s, params_sh), toks]
    fs = _frontend_struct(cfg, 0, global_batch, dtype)
    if fs is not None:
        args.append(jax.ShapeDtypeStruct(
            fs.shape, fs.dtype,
            sharding=NamedSharding(mesh, sh.batch_pspec(
                fs.ndim if hasattr(fs, "ndim") else len(fs.shape),
                mesh, batch_axes, fs.shape))))
    return prefill_step, tuple(args)


# ------------------------------------------------------------------ #
# decode_32k / long_500k: serve_step (one token, filled cache)
# ------------------------------------------------------------------ #
def build_decode(cfg: ModelConfig, mesh, *, seq: int, global_batch: int,
                 long: bool = False, rules=None, dtype=jnp.bfloat16,
                 unroll=False, cache_seq_shard: bool = True):
    rules = rules or sh.RULES_BASE
    if long:
        cfg = _long_variant(cfg)
    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    arules = act_rules(batch_axes)

    def serve_step(params, cache, token):
        with msh.mesh_rules(mesh, arules):
            return decode_step(cfg, params, cache, token, unroll=unroll)

    params_s = jax.eval_shape(
        lambda k: init_params(cfg, k, dtype), jax.random.PRNGKey(0))
    params_sh = sh.tree_shardings(params_s, mesh, rules)
    fs = _frontend_struct(cfg, 0, global_batch, dtype)
    cache_s = jax.eval_shape(
        lambda p, f: init_cache(cfg, p, global_batch, seq, dtype, f),
        params_s, fs)
    cache_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        sh.cache_pspecs(cache_s, mesh, batch_axes,
                        seq_shard=cache_seq_shard))
    token = jax.ShapeDtypeStruct(
        (global_batch, 1), jnp.int32,
        sharding=NamedSharding(mesh, sh.batch_pspec(
            2, mesh, batch_axes, (global_batch, 1))))
    return serve_step, (_sds(params_s, params_sh),
                        _sds(cache_s, cache_sh), token)


# ------------------------------------------------------------------ #
def build_case(cfg: ModelConfig, mesh, shape_name: str, **kw):
    info = SHAPES[shape_name]
    if info["kind"] == "train":
        return build_train(cfg, mesh, seq=info["seq"],
                           global_batch=info["batch"], **kw)
    if info["kind"] == "prefill":
        return build_prefill(cfg, mesh, seq=info["seq"],
                             global_batch=info["batch"], **kw)
    return build_decode(cfg, mesh, seq=info["seq"],
                        global_batch=info["batch"],
                        long=info.get("long", False), **kw)


def input_specs(arch: str, shape_name: str, mesh=None, **kw):
    """Public API: ShapeDtypeStruct stand-ins (weak-type-correct, shardable,
    no device allocation) for every model input of (arch × shape), plus the
    step function they feed.  Returns (step_fn, args)."""
    from repro.configs import get_config
    from .mesh import make_production_mesh

    if mesh is None:
        mesh = make_production_mesh()
    return build_case(get_config(arch), mesh, shape_name, **kw)
