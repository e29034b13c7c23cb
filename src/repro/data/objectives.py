"""Objectives with the simulator's ``grad_fn(node, x, key)`` interface.

Every objective here is a :class:`~repro.core.paramvec.GradProvider`:
``n`` nodes, flat dimension ``p``, and ``grad_fn()`` returning the
traced ``(i, x_flat, key) -> g_flat`` the engines consume.

* :class:`LogisticProblem` — the paper's §VI-A regularized logistic
  regression (smooth and strongly convex thanks to the L2 term).
* :class:`LMProblem` — a real (reduced) transformer LM on the flat
  substrate: parameters travel through the engines as one padded
  ``(p,)`` lane (``paramvec.ravel``/``unravel`` rebuild the pytree
  inside the traced gradient), batches are sampled device-side from
  the shard's Zipfian token marginal, so the same asynchronous engines
  that run the hand-written objectives train the model.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.paramvec import (ModelGradProvider, RavelSpec, make_ravel_spec,
                             ravel, unravel)
from .pipeline import LMShardConfig, zipf_probs

__all__ = ["LogisticProblem", "make_logistic_problem",
           "LMProblem", "make_lm_problem"]


@dataclasses.dataclass(frozen=True)
class LogisticProblem:
    """Regularized logistic regression over n node-local shards.

    Parameter layout: x = [w (d,), b ()] -> p = d + 1.
    Local objective:  f_i(x) = Σ_{s∈shard_i} log(1+exp(-ŷ s)) + (λ/2)|x|²
    (sum, not mean — matches problem (1)'s Σ_i f_i structure; the λ term is
    split evenly so F keeps a single global λ).
    """

    X: jnp.ndarray          # (n, m_i, d)
    y: jnp.ndarray          # (n, m_i) in {0,1}
    lam: float
    batch: int              # minibatch size per gradient sample (0 = full)

    @property
    def n(self) -> int:
        return self.X.shape[0]

    @property
    def p(self) -> int:
        return self.X.shape[2] + 1

    # -- losses --------------------------------------------------------- #
    def _margins(self, Xb, yb, x):
        w, b = x[:-1], x[-1]
        logits = Xb @ w + b
        s = 2.0 * yb.astype(jnp.float32) - 1.0
        return logits * s

    def local_loss(self, i: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
        m = self._margins(self.X[i], self.y[i], x)
        return jnp.sum(jax.nn.softplus(-m)) + 0.5 * self.lam * jnp.sum(x * x)

    def global_loss(self, x: jnp.ndarray) -> jnp.ndarray:
        """F(x) = Σ_i f_i(x), evaluated on the full data."""
        losses = jax.vmap(lambda i: self.local_loss(i, x))(jnp.arange(self.n))
        return jnp.sum(losses)

    def mean_loss(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.global_loss(x) / (self.X.shape[0] * self.X.shape[1])

    def accuracy(self, x: jnp.ndarray) -> jnp.ndarray:
        w, b = x[:-1], x[-1]
        logits = self.X.reshape(-1, self.X.shape[-1]) @ w + b
        pred = (logits > 0).astype(jnp.int32)
        return jnp.mean((pred == self.y.reshape(-1)).astype(jnp.float32))

    # -- gradients ------------------------------------------------------ #
    def grad_fn(self) -> Callable:
        """Stochastic grad_fn(node, x, key): minibatch ∇f_i, unbiased."""
        m_i = self.X.shape[1]
        full = self.batch <= 0 or self.batch >= m_i

        if full:
            def gfn(i, x, key):
                del key
                return jax.grad(lambda xx: self.local_loss(i, xx))(x)
            return gfn

        scale = m_i / self.batch  # rescale minibatch sum to unbiased f_i grad

        def gfn(i, x, key):
            idx = jax.random.randint(key, (self.batch,), 0, m_i)
            Xb, yb = self.X[i][idx], self.y[i][idx]

            def loss(xx):
                mg = self._margins(Xb, yb, xx)
                data = jnp.sum(jax.nn.softplus(-mg)) * scale
                return data + 0.5 * self.lam * jnp.sum(xx * xx)

            return jax.grad(loss)(x)
        return gfn

    def optimum(self, iters: int = 2000, lr: float = 0.5) -> jnp.ndarray:
        """Reference x* by full-batch gradient descent on F (for gap plots)."""
        x = jnp.zeros(self.p, jnp.float32)
        g = jax.jit(jax.grad(lambda xx: self.mean_loss(xx)))

        def body(x, _):
            return x - lr * g(x), None
        x, _ = jax.lax.scan(body, x, None, length=iters)
        return x


# --------------------------------------------------------------------- #
# the reduced-LM objective on the flat substrate
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class LMProblem:
    """A transformer LM as a flat-substrate GradProvider.

    Each node owns a Zipfian synthetic shard (problem (1)'s D_i);
    ``grad_fn`` unravels the flat iterate to the parameter pytree,
    samples the node's batch device-side from the per-event key, runs
    ``models.transformer.loss_fn``, and ravels the gradient back to the
    ``(p,)`` lane (zero tail padding — invisible to the protocol,
    which is linear in the lane).  ``mean_loss``/``accuracy`` evaluate
    a fixed held-out batch, so the benchmark harness's
    ``eval_fn_for``/``time_to_loss`` work unchanged.
    """

    cfg: Any                    # models.config.ModelConfig
    shard: LMShardConfig
    spec: RavelSpec
    params0: Any                # host init pytree (the x0 everyone starts at)
    eval_tokens: jnp.ndarray    # (Be, S) held-out eval batch
    eval_labels: jnp.ndarray    # (Be, S)

    @property
    def n(self) -> int:
        return self.shard.n_nodes

    @property
    def p(self) -> int:
        return self.spec.p

    @property
    def x0_flat(self) -> jnp.ndarray:
        return ravel(self.spec, self.params0)

    def _token_cdf(self) -> jnp.ndarray | None:
        if self.shard.zipf <= 0:
            return None
        return jnp.asarray(
            np.cumsum(zipf_probs(self.shard.vocab, self.shard.zipf)),
            jnp.float32)

    def grad_fn(self):
        from ..models.transformer import loss_fn
        cfg, shard = self.cfg, self.shard
        B, S, V = shard.batch_per_node, shard.seq_len, shard.vocab
        cdf = self._token_cdf()
        vg = jax.value_and_grad(
            lambda prms, t, lbl: loss_fn(cfg, prms, t, lbl))

        def sample(_i, key):
            if cdf is None:
                return jax.random.randint(key, (B, S + 1), 0, V,
                                          dtype=jnp.int32)
            u = jax.random.uniform(key, (B, S + 1))
            return jnp.clip(jnp.searchsorted(cdf, u), 0, V - 1) \
                .astype(jnp.int32)

        # the generic adapter owns the flat recipe (unravel / key split /
        # node-folded batch key / ravel); the model has no per-step
        # stochasticity, so the gkey the adapter passes is unused
        return ModelGradProvider(
            spec=self.spec, n_nodes=self.n,
            value_and_grad=lambda prms, toks, _k: vg(prms, toks[:, :-1],
                                                     toks[:, 1:]),
            batch_fn=sample,
        ).grad_fn()

    # -- evaluation (host-callable, cached jit) ------------------------- #
    @functools.cached_property
    def _eval(self):
        from ..models.transformer import forward
        cfg, spec = self.cfg, self.spec

        @jax.jit
        @jax.named_scope("rfast.eval")
        def ev(x_flat, toks, labels):
            params = unravel(spec, x_flat)
            logits, aux = forward(cfg, params, toks)
            lse = jax.scipy.special.logsumexp(
                logits.astype(jnp.float32), axis=-1)
            tgt = jnp.take_along_axis(logits, labels[..., None],
                                      axis=-1)[..., 0].astype(jnp.float32)
            loss = (lse - tgt).mean() + aux
            acc = jnp.mean((logits.argmax(-1) == labels)
                           .astype(jnp.float32))
            return loss, acc

        return ev

    def mean_loss(self, x_flat: jnp.ndarray) -> jnp.ndarray:
        return self._eval(jnp.asarray(x_flat, jnp.float32),
                          self.eval_tokens, self.eval_labels)[0]

    def accuracy(self, x_flat: jnp.ndarray) -> jnp.ndarray:
        return self._eval(jnp.asarray(x_flat, jnp.float32),
                          self.eval_tokens, self.eval_labels)[1]


def make_lm_problem(
    cfg: Any, n_nodes: int, *, batch_per_node: int = 4, seq_len: int = 32,
    eval_batch: int = 16, zipf: float = 1.2, seed: int = 0,
    pad_to: int = 128,
) -> LMProblem:
    """Build an :class:`LMProblem` from a ``ModelConfig`` (pass a
    ``cfg.reduced(...)`` variant for CPU/CI scale).  ``pad_to=128``
    aligns the flat lane with the fused commit kernel's block layout."""
    from ..models.transformer import init_params
    shard = LMShardConfig(vocab=cfg.vocab, batch_per_node=batch_per_node,
                          seq_len=seq_len, n_nodes=n_nodes, seed=seed,
                          zipf=zipf)
    # kept on the host: the engines take x0 where they need it, and no
    # device has to carry an idle copy of the initial weights
    params0 = jax.device_get(init_params(cfg, jax.random.PRNGKey(seed)))
    spec = make_ravel_spec(params0, pad_to=pad_to)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x0E7A1]))
    shape = (eval_batch, seq_len + 1)
    if zipf > 0:
        toks = rng.choice(cfg.vocab, size=shape,
                          p=zipf_probs(cfg.vocab, zipf))
    else:
        toks = rng.integers(0, cfg.vocab, shape)
    return LMProblem(
        cfg=cfg, shard=shard, spec=spec, params0=params0,
        eval_tokens=jnp.asarray(toks[:, :-1], jnp.int32),
        eval_labels=jnp.asarray(toks[:, 1:], jnp.int32),
    )


def make_logistic_problem(
    n: int, *, m: int = 12_000, d: int = 784, lam: float = 1e-3,
    batch: int = 32, heterogeneous: bool = False, seed: int = 0,
) -> LogisticProblem:
    from .synthetic import logistic_dataset, partition

    X, y = logistic_dataset(m, d, seed=seed)
    Xs, ys = partition(X, y, n, heterogeneous=heterogeneous, seed=seed)
    # λ split evenly across nodes so Σ_i f_i carries a single global λ
    return LogisticProblem(
        X=jnp.asarray(Xs), y=jnp.asarray(ys), lam=lam / n, batch=batch,
    )
