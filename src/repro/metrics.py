"""JSONL metrics logging, step timing, and program spans and counters —
the observability substrate.

Every logged record carries the step, a monotonic timestamp, and
arbitrary scalar fields; readers get pandas-free helpers for quick
analysis.

Program tracing (:func:`span`, :func:`count`) is off until
:func:`enable` turns it on.  Off, a span site costs one call that
returns a shared no-op context: no allocation, no clock read.  On, each
span is kept in memory as ``(name, id, parent, t0, t1)`` on
``time.perf_counter`` (``parent`` is the innermost span open when it
started) and also enters ``jax.profiler.TraceAnnotation(name)``, so a
profiler trace taken meanwhile shows it on the device trace's clock;
each count is kept as ``(name, n, id, t)``.  :func:`record` hands both
back.
"""
from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Iterator

import jax


class MetricsLogger:
    """Append-only JSONL logger with buffered writes."""

    def __init__(self, path: str, flush_every: int = 10):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._f = open(path, "a", buffering=1)
        self._buf: list[str] = []
        self._flush_every = flush_every
        self._t0 = time.monotonic()

    def log(self, step: int, **fields: Any) -> None:
        rec = {"step": int(step), "t": round(time.monotonic() - self._t0, 4)}
        for k, v in fields.items():
            try:
                rec[k] = float(v)
            except (TypeError, ValueError):
                rec[k] = str(v)
        self._buf.append(json.dumps(rec))
        if len(self._buf) >= self._flush_every:
            self.flush()

    def flush(self) -> None:
        if self._buf:
            self._f.write("\n".join(self._buf) + "\n")
            self._buf.clear()

    def close(self) -> None:
        self.flush()
        self._f.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def read_metrics(path: str) -> Iterator[dict]:
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                yield json.loads(line)


class StepTimer:
    """Rolling steps/sec + ETA."""

    def __init__(self, window: int = 20):
        self._times: list[float] = []
        self._window = window

    def tick(self) -> None:
        self._times.append(time.monotonic())
        if len(self._times) > self._window:
            self._times.pop(0)

    @property
    def steps_per_sec(self) -> float:
        if len(self._times) < 2:
            return 0.0
        dt = self._times[-1] - self._times[0]
        return (len(self._times) - 1) / dt if dt > 0 else 0.0

    def eta_s(self, remaining_steps: int) -> float:
        sps = self.steps_per_sec
        return remaining_steps / sps if sps > 0 else float("inf")


# --------------------------------------------------------------------- #
# program spans and counters
# --------------------------------------------------------------------- #
_NO_SPAN = contextlib.nullcontext()


class _Trace:
    """What :func:`enable` turns on: the open-span stack and the kept
    spans and counts of this process."""

    def __init__(self):
        self.on = False
        self.open: list[str] = []
        self.spans: list[tuple] = []
        self.counts: list[tuple] = []


_TRACE = _Trace()


class _Span:
    __slots__ = ("name", "id", "parent", "t0", "ann")

    def __init__(self, name: str, id):
        self.name, self.id = name, id

    def __enter__(self):
        self.parent = _TRACE.open[-1] if _TRACE.open else None
        _TRACE.open.append(self.name)
        self.ann = jax.profiler.TraceAnnotation(self.name)
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ann.__exit__(*exc)
        _TRACE.open.pop()
        _TRACE.spans.append((self.name, self.id, self.parent, self.t0, t1))
        return False


def span(name: str, id=None):
    """Context manager timing one program step; a shared no-op when
    tracing is off."""
    if not _TRACE.on:
        return _NO_SPAN
    return _Span(name, id)


def count(name: str, n: int = 1, id=None) -> None:
    """Record ``n`` more of ``name`` (with the time) when tracing is on."""
    if _TRACE.on:
        _TRACE.counts.append((name, n, id, time.perf_counter()))


def enable(on: bool = True) -> None:
    _TRACE.on = bool(on)


def enabled() -> bool:
    return _TRACE.on


def reset() -> None:
    """Drop every kept span and count (tracing stays as it is)."""
    _TRACE.open.clear()
    _TRACE.spans.clear()
    _TRACE.counts.clear()


def total(name: str) -> int:
    """Sum of the counts of ``name`` kept so far."""
    return sum(n for c, n, _, _ in _TRACE.counts if c == name)


def record() -> dict:
    """The kept spans and counts, oldest first, as plain dicts."""
    return {
        "spans": [dict(name=n, id=i, parent=p, t0=t0, t1=t1)
                  for n, i, p, t0, t1 in _TRACE.spans],
        "counts": [dict(name=c, n=n, id=i, t=t)
                   for c, n, i, t in _TRACE.counts],
    }
