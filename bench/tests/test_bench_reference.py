"""The plain reference model and batch sampler agree with the program's
LM objective at a test size on the CPU (the chip check then compares
them at the cells' sizes)."""
import json

import jax
import numpy as np
import pytest

from bench import harness, rfast_ref, spec
from bench.configs import dense_decoder

DATA = spec.BENCH_DIR / "tests" / "data"


@pytest.mark.parametrize("name", ["tiny-gqa", "tiny-mha-tied"])
def test_reference_gradient_matches_the_program(name):
    from repro.core.paramvec import make_ravel_spec
    from repro.data.objectives import LMProblem
    from repro.data.pipeline import LMShardConfig
    from repro.models.transformer import init_params

    with open(DATA / "configs" / f"{name}.json") as f:
        cfg = json.load(f)
    cfg["name"] = name
    mcfg = harness._model_config(cfg)
    shapes = jax.eval_shape(lambda k: init_params(mcfg, k),
                            jax.random.PRNGKey(0))
    rspec = make_ravel_spec(shapes, pad_to=dense_decoder.PAD_TO)
    assert list(rspec.shapes) == [s for _, s in dense_decoder.layout(cfg)]
    B, S, zipf = 3, 16, 1.2
    prob = LMProblem(cfg=mcfg, spec=rspec, params0=None,
                     shard=LMShardConfig(vocab=cfg["vocab"], batch_per_node=B,
                                         seq_len=S, n_nodes=2, zipf=zipf),
                     eval_tokens=None, eval_labels=None)
    x = dense_decoder.init_flat(cfg, jax.random.PRNGKey(7))
    key = jax.random.PRNGKey(11)
    g_prog = jax.jit(prob.grad_fn())(1, x, key)
    cdf = rfast_ref.zipf_cdf(cfg["vocab"], zipf)
    toks, labels = rfast_ref.sample_batch(cdf, key, 1, B=B, S=S)
    g_ref = jax.grad(lambda f: dense_decoder.loss(cfg, f, toks, labels))(x)
    np.testing.assert_allclose(np.asarray(g_prog), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-6)
