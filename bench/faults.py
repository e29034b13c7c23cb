"""Faults planted under the timed path, to show that the check fails
them.  Each is a context manager that patches the program while it is
active; ``calibrate.py`` reads them on the chip and ``tests/`` sees
``correct`` come out false for each.

* ``frozen_state`` — every wave runner returns its state unchanged;
* ``half_batch`` — the loss sees the first half of each batch only, its
  mean taken over those rows;
* ``no_exchange`` — on a mesh, the all-gather that rebuilds the full
  iterate for the gradient is left out: each device tiles its own shard.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def frozen_state():
    from repro.core import simulator

    def frozen(make):
        def build(*a, **k):
            make(*a, **k)
            return lambda state, waves: state
        return build

    with contextlib.ExitStack() as stack:
        for name in ("rfast_wavefront_scan", "rfast_sweep_scan",
                     "_mesh_sweep_scan"):
            stack.enter_context(_patched(simulator, name,
                                         frozen(getattr(simulator, name))))
        yield


@contextlib.contextmanager
def half_batch():
    from repro.models import transformer
    loss_fn = transformer.loss_fn

    def half(cfg, params, tokens, labels, *a, **k):
        b = tokens.shape[0] // 2
        return loss_fn(cfg, params, tokens[:b], labels[:b], *a, **k)

    with _patched(transformer, "loss_fn", half):
        yield


@contextlib.contextmanager
def no_exchange():
    import jax
    import jax.numpy as jnp

    def own_shard_only(x, axis_name, *, axis=0, tiled=False, **_):
        m = jax.lax.axis_size(axis_name)
        return jnp.concatenate([x] * m, axis=axis) if tiled else \
            jnp.stack([x] * m, axis=axis)

    with _patched(jax.lax, "all_gather", own_shard_only):
        yield


FAULTS = {"frozen_state": frozen_state, "half_batch": half_batch,
          "no_exchange": no_exchange}
