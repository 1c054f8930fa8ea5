"""The device's idle time put down to the serving program's own spans.

The program scopes the host work at its layer boundaries with profiler
annotations (``repro.telemetry.trace.Tracer.scope``): ``server.*`` in
``Server``, ``sched.*`` in the decode session's scheduling and
``step.*`` around its jitted calls, from their operand uploads to
their results on the host.  A profiler session records them on the
host plane, beside the benchmark's own ``bench.*`` annotations and on
the clock of the device planes, so an instant in which the chip ran
nothing can be named by the innermost program span the host was in.
``harness.trace.load`` keeps them, under any prefix, in
``Trace.spans``.

Interval arithmetic only, on :class:`harness.trace.Event`; the
program's spans nest (one serving thread, context managers), so the
innermost span covering an instant is the latest-started one still
open.
"""
from __future__ import annotations

from collections import defaultdict

from harness import trace as tm
from harness.trace import Event


def innermost(spans: list[Event]) -> list[tuple[int, int, str]]:
    """Nested spans flattened to disjoint ``(start, end, name)``
    segments, each named by the innermost span covering it."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []          # (end, name), open spans
    t = 0

    def emit(a: int, b: int, name: str) -> None:
        if b > a:
            out.append((a, b, name))

    for e in sorted(spans, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0] <= e.start:
            end, name = stack.pop()
            emit(t, end, name)
            t = max(t, end)
        if stack:
            emit(t, e.start, stack[-1][1])
        t = e.start
        stack.append((e.end, e.name))
    while stack:
        end, name = stack.pop()
        emit(t, end, name)
        t = max(t, end)
    return out


def idle_by_span(tr: tm.Trace, spans: list[Event]) -> dict:
    """Nanoseconds of ``trace.idle_gaps(tr)`` by the innermost program
    span covering each instant; instants under none are left out."""
    segs = innermost(spans)
    out: dict = defaultdict(int)
    i = 0
    for a, b in tm.idle_gaps(tr):
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        k = i
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(segs[k][0], a), min(segs[k][1], b)
            if hi > lo:
                out[segs[k][2]] += hi - lo
            k += 1
    return dict(out)


def host_idle_pct(tr: tm.Trace, spans: list[Event], prefix: str):
    """The share of the traced span, in percent, in which the first
    chip was idle and the innermost program span was one of layer
    ``prefix``; ``None`` where the trace holds no program span or no
    chip."""
    if not spans or not tr.devices or tr.window_s <= 0:
        return None
    ns = sum(v for k, v in idle_by_span(tr, spans).items()
             if k.startswith(prefix))
    return 100.0 * ns / 1e9 / tr.window_s
