"""The program's own names in a reduced trace: device scopes and host
spans.

The engine tags every operation of a wave with a ``jax.named_scope``
(``rfast.mix``, ``rfast.grad``, ``rfast.commit``, ``rfast.state_write``;
``rfast.iterates`` on the eval read, ``rfast.eval`` on the loss), which
XLA keeps in each instruction's ``op_name`` metadata; operations XLA
inserts itself (copies, relayouts) carry none.  The engine's host loop
opens ``rfast.*`` spans (``repro.metrics.span``), which a profiler trace
shows beside the harness's ``bench.*`` spans.

The functions here take the scope from an operation's name, which must
carry its ``op_name`` (``..., metadata={op_name="jit(run_waves)/...
/rfast.grad/..."}``).  The names ``trace_reduce.raw_events`` reads from
the ``XLA Ops`` line are the HLO text without it: the trace holds the
op names only in its ``/host:metadata`` plane (each module's HLO proto),
which ``jax.profiler.ProfileData`` does not expose.  The recorded scoped
trace (``tests/data``) has them joined from the compiled programs' HLO.
Program spans are given as ``(name, start, end)`` on the trace's clock.
A trace without scopes or spans gives what ``trace_reduce`` gives.
"""
from __future__ import annotations

import re

from . import trace_reduce

SCOPE = re.compile(r"(?<![\w.])rfast\.[a-z_]+")


def scope_of(name: str) -> str | None:
    """The innermost ``rfast.*`` scope in an operation's name."""
    found = SCOPE.findall(name)
    return found[-1] if found else None


def scope_seconds(trace, scope: str) -> list:
    """Summed duration of the leaf operations under ``scope``, per
    device."""
    return [sum(e - s for n, s, e, _, leaf in ops
                if leaf and scope_of(n) == scope) for ops in trace.ops]


def unscoped_seconds(trace) -> list:
    """Summed duration of the leaf operations under no ``rfast.*``
    scope, per device."""
    return scope_seconds(trace, None)


def top_ops(trace, k: int = 10) -> list:
    """``trace.top_ops`` with each name followed by ``@<scope>`` where
    the operation has one."""
    tot: dict = {}
    for ops in trace.ops:
        for name, _, _, self_s, _ in ops:
            scope = scope_of(name)
            key = trace_reduce.short_name(name) + (f"@{scope}" if scope
                                                   else "")
            tot[key] = tot.get(key, 0.0) + self_s / len(trace.ops)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(trace, program_spans, k: int = 10) -> list:
    """``trace.idle_gaps`` with each harness-span label followed by
    ``>`` and the program span that overlaps the gap most (of spans
    that cover it alike, the innermost: the one that started last),
    where one does."""
    rows = trace.idle_gaps(k)
    for row, (a, b) in zip(rows, _gaps(trace)):
        over = [(min(b, e) - max(a, s), s, n) for n, s, e in program_spans
                if s < b and e > a]
        if over:
            row[0] = f"{row[0]}>{max(over)[2]}"
    return rows


def _gaps(trace) -> list:
    """Device 0's idle intervals in the window, longest first: the
    intervals ``trace.idle_gaps`` reports, in its order."""
    busy = trace_reduce._union([(s, e) for _, s, e, _, _ in trace.ops[0]])
    edges = [trace.window[0]] + [x for iv in busy for x in iv] \
        + [trace.window[1]]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    return gaps
