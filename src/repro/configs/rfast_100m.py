"""rfast-100m — the ~100M-param LM used by the end-to-end R-FAST training
driver (examples/train_rfast.py).  Llama-style dense decoder.

Its flat parameter vector is p = 124,668,672 fp32 (0.46 GiB), and the
wavefront engine keeps four node slots plus the ρ and history rings of it
per node.  With 2 nodes (binary tree) that is 8.8 GiB, which fits one
16 GiB v5e chip: ``launch.train --scenario <name> --nodes 2``.  With 4 or
more nodes it does not: shard the flat axis over ``model`` with
``launch.train --scenario <name> --nodes 4 --param-shards 4`` (or
``run_sweep(mesh=make_sweep_mesh(lanes=1, param_shards=M), ...)``); the
``lm100m/*`` rows in benchmarks/bench_scaling.py pin this path.
"""
from repro.models.config import ModelConfig


def get_config() -> ModelConfig:
    return ModelConfig(
        name="rfast-100m",
        n_layers=12,
        d_model=768,
        n_heads=12,
        n_kv_heads=4,
        d_ff=2048,
        vocab=32000,
        mixer="attn",
        mlp="swiglu",
        norm="rmsnorm",
    )
