"""Shape-specialized dispatch cache for the fleet-grid commit kernel.

Every caller of the grid launch (``ops.rfast_commit``, the wavefront and
sweep engines, ``core/protocol.py``'s pallas backend) resolves through
this module: the launch callable is constructed ONCE per static shape
signature — (execution mode, lane count B, p-tile count T, gather
degrees ka/ko, source row counts, source dtypes) — and reused for every
subsequent wave, chunk, seed, and hot-swapped plan that shares the
signature.  Plans padded to common fleet maxima (``schedule.pad_plan`` /
``plan.pad_comm_plan``) deliberately share signatures, so a whole sweep
resolves to one cached launch.

The cache is instrumented: :func:`stats` exposes hit/miss counters
(incremented at trace time, when a caller actually resolves a launch)
and :func:`clear` resets both the cache and the counters, so recompile
bugs surface as a counter assertion in tests instead of a silent
wall-time cliff.

Execution modes (:func:`resolve_mode` maps the engines' tri-state
``interpret`` flag onto them):

* ``"compiled"``  — the real Mosaic TPU launch (``interpret=False``).
* ``"interpret"`` — the Pallas interpreter; orders of magnitude slower
  than XLA on CPU, retained purely as the bit-faithful kernel oracle
  for tests (``interpret=True``).
* ``"emulate"``   — a jnp program with gather/commit semantics identical
  to the grid kernel (same index tables, same blend math).  The CPU
  default: off-TPU benchmarks then measure the grid *architecture*
  (one fused dispatch per wave over flat sources) rather than the
  interpreter's per-operand overhead.

``interpret=None`` (the default everywhere) resolves to ``compiled`` on
TPU and ``emulate`` elsewhere — visibly: ``launch/train.py`` prints the
resolved mode, and ``chip_smoke.py`` fails unless every cached key is
``compiled``.

Under the mesh-mapped sweep engine the commit runs *inside* a shard_map
region, so the shapes that reach :func:`lookup` are the **local shard
shapes** — lane count ``S_loc·B`` and flat width ``p_pad // M``.  The
key therefore shard-localizes automatically: every device of a wave
resolves the same signature, and a whole mesh-mapped fleet still
compiles to ONE launch per shard shape (pinned by the sweep tests).
"""
from __future__ import annotations

from typing import Callable

import jax

__all__ = ["MODES", "resolve_mode", "lookup", "stats", "keys", "clear"]

MODES = ("compiled", "interpret", "emulate")

_cache: dict[tuple, Callable] = {}
_hits = 0
_misses = 0


def resolve_mode(interpret: bool | None) -> str:
    """Map the engines' ``interpret`` tri-state to an execution mode.

    ``True`` → ``"interpret"`` (the oracle), ``False`` → ``"compiled"``
    (force the real launch), ``None`` → autodetect from
    ``jax.default_backend()``: ``compiled`` on TPU, ``emulate`` off it.
    """
    if interpret is True:
        return "interpret"
    if interpret is False:
        return "compiled"
    return "compiled" if jax.default_backend() == "tpu" else "emulate"


def lookup(key: tuple, build: Callable[[], Callable]) -> Callable:
    """Return the cached callable for ``key``, constructing it with
    ``build()`` on the first request.  Counts a hit or a miss."""
    global _hits, _misses
    fn = _cache.get(key)
    if fn is None:
        _misses += 1
        fn = build()
        _cache[key] = fn
    else:
        _hits += 1
    return fn


def stats() -> dict:
    """Current counters: ``{"hits", "misses", "entries"}``.  Misses count
    distinct launch signatures constructed since the last :func:`clear`;
    a steady-state engine loop must not grow them."""
    return {"hits": _hits, "misses": _misses, "entries": len(_cache)}


def keys() -> list[tuple]:
    """The cached launch signatures; each key's second entry is its
    execution mode."""
    return list(_cache)


def clear() -> None:
    """Drop every cached launch and zero the counters (test isolation)."""
    global _hits, _misses
    _cache.clear()
    _hits = 0
    _misses = 0
