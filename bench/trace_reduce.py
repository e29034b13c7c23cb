"""Reduce a profiler trace of the measured window to what the per-layer
metrics read.

:func:`start` / :func:`load` take a trace with JAX's profiler (device
activity and the harness's ``TraceAnnotation`` spans; no Python function
tracing) and read it back with ``jax.profiler.ProfileData``.  The reading
keeps plain tuples, so the reduction can be checked on a small recorded
trace (``tests/data``) without a chip:

* device planes are those named ``/device:<KIND>:<i>``; their ``XLA Ops``
  line holds one event per operation run on the device, named by its HLO
  instruction text (a loop's event encloses its body's);
* host spans are the events named ``bench.*`` on host planes;
* the traced window runs from the first ``bench.chunk`` span's start to
  the last ``bench.eval`` span's end, both on the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import re
import shutil

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    """Seconds on the trace's clock.  ``ops[d]`` lists ``(name, start,
    end, self_s, leaf)`` of device ``d``'s operations inside the window:
    an operation that encloses others (a ``while`` loop, a call) has them
    on the same line, so ``self_s`` is its time less its children's, and
    ``leaf`` says it has none.  ``spans`` are the harness spans ``(name,
    start, end)``."""

    ops: list
    spans: list
    window: tuple

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    # -- per device --------------------------------------------------- #
    def busy_s(self) -> list:
        """Union of operation intervals, per device."""
        return [_length(_union([(s, e) for _, s, e, _, _ in ops]))
                for ops in self.ops]

    def idle_frac(self) -> float:
        """1 - busy / window, averaged over the devices."""
        busy = self.busy_s()
        return 1.0 - sum(busy) / len(busy) / self.window_s

    def op_seconds(self, pattern: str) -> list:
        """Summed duration of the leaf operations whose name matches
        ``pattern`` (``re.search``), per device."""
        rx = re.compile(pattern)
        return [sum(e - s for n, s, e, _, leaf in ops
                    if leaf and rx.search(n)) for ops in self.ops]

    def exposed_seconds(self, pattern: str) -> list:
        """Time of the leaf operations matching ``pattern`` during which
        no other leaf operation runs on that device, per device."""
        rx = re.compile(pattern)
        out = []
        for ops in self.ops:
            leaves = [(n, s, e) for n, s, e, _, leaf in ops if leaf]
            mine = _union([(s, e) for n, s, e in leaves if rx.search(n)])
            other = _union([(s, e) for n, s, e in leaves
                            if not rx.search(n)])
            out.append(_length(mine) - _length(_intersect(mine, other)))
        return out

    # -- breakdown ---------------------------------------------------- #
    def top_ops(self, k: int = 10) -> list:
        """``[name, seconds]`` of the operations with the most self time,
        averaged over the devices (names shortened to the instruction and
        its kind)."""
        tot: dict = {}
        for ops in self.ops:
            for name, _, _, self_s, _ in ops:
                key = short_name(name)
                tot[key] = tot.get(key, 0.0) + self_s / len(self.ops)
        return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> list:
        """``[span, seconds]`` of device 0's longest idle gaps in the
        window, each named by the harness span that overlaps it most."""
        busy = _union([(s, e) for _, s, e, _, _ in self.ops[0]])
        edges = [self.window[0]] + [x for iv in busy for x in iv] \
            + [self.window[1]]
        gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            best, label = 0.0, "none"
            for name, s, e in self.spans:
                ov = min(b, e) - max(a, s)
                if ov > best:
                    best, label = ov, name
            out.append([label, b - a])
        return out


def short_name(name: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``%fusion.12 fusion``."""
    m = _INSTR.match(name)
    return f"{m[1]} {m[2]}" if m else name[:80]


_INSTR = re.compile(r"(%[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def _nesting(events):
    """``(name, start, end, self_s, leaf)`` of events on one line, where
    an event that contains others is their parent."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    child = [0.0] * len(events)
    leaf = [True] * len(events)
    stack = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] < e:     # not contained
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return [(n, s, e, (e - s) - child[i], leaf[i])
            for i, (n, s, e) in enumerate(events)]


def _union(ivs):
    out = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def _length(ivs) -> float:
    return sum(e - s for s, e in ivs)


def _intersect(a, b):
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out.append((max(s, b[k][0]), min(e, b[k][1])))
            k += 1
    return out


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def raw_events(log_dir: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns)]}, "spans":
    [(name, start_ns, dur_ns)]}`` read from the trace under ``log_dir``."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def reduce(raw: dict) -> Trace | None:
    """The :class:`Trace` of raw events; None when no device op ran."""
    spans = [(n, s * 1e-9, (s + d) * 1e-9) for n, s, d in raw["spans"]]
    chunks = [s for n, s, _ in spans if n == "bench.chunk"]
    evals = [e for n, _, e in spans if n == "bench.eval"]
    if not chunks or not evals:
        return None
    window = (min(chunks), max(evals))
    ops = []
    for plane in sorted(raw["devices"], key=_device_index):
        ops.append(_nesting(
            [(n, max(s * 1e-9, window[0]), min((s + d) * 1e-9, window[1]))
             for n, s, d in raw["devices"][plane]
             if (s + d) * 1e-9 > window[0] and s * 1e-9 < window[1]]))
    if not ops or not any(ops):
        return None
    return Trace(ops=ops, spans=spans, window=window)


def _device_index(plane: str) -> int:
    return int(plane.rsplit(":", 1)[1])


def load(log_dir: str) -> Trace | None:
    return reduce(raw_events(log_dir))


def remove(log_dir: str) -> None:
    shutil.rmtree(log_dir, ignore_errors=True)


def load_raw(path) -> dict:
    """Raw events saved as gzipped JSON (the recorded test trace)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)
