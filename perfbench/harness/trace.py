"""Reduce a profiler trace (``.xplane.pb``) to device time.

Read with ``jax.profiler.ProfileData`` alone.  A TPU trace has one
plane per chip (``/device:TPU:<n>``) whose ``XLA Ops`` line holds one
event per operation run on the chip and whose ``XLA Modules`` line
holds one event per program execution (``jit_<name>(<id>)``); the
host plane (``/host:CPU``) holds, on the same clock, the benchmark's
own annotations (``bench.*``) and the program's spans: the
``Tracer.scope`` annotations at its layer boundaries, each named
``<layer>.<what>`` in lower case (``server.push``, ``sched.advance``,
``step.window``, and whatever prefix a new mechanism brings).  The
runtime's own host events are named otherwise (``PjitFunction(step_k)``,
``tpu::System::Execute``, ``np.asarray(jax.Array)``, ``shard_args``,
and on a CPU the operations themselves, ``fusion.12``).

Everything here is arithmetic on intervals: busy time is the union of
the operations' intervals, a program's or a kernel's time is the sum
of its events' durations, an idle gap is a stretch of the window in
which no operation ran, named by the host annotation that covers most
of it.  Which events belong to a metric is decided by name patterns
that the metric's own file holds.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
# a program span's name: ``<layer>.<what>``, lower case, each part
# starting with a letter (an operation's name ends in ``.<number>``)
SPAN_NAME = re.compile(r"[a-z][a-z0-9_]*(?:\.[a-z_][a-z0-9_]*)+")


@dataclass
class Event:
    name: str                  # an operation's short HLO name
    start: int                 # ns, on the trace's clock
    end: int


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)    # plane -> [Event] ops
    modules: dict = field(default_factory=dict)    # plane -> [Event]
    host: list = field(default_factory=list)       # bench.* annotations
    spans: list = field(default_factory=list)      # the program's spans
    t0: int = 0                                    # traced window (ns)
    t1: int = 0

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``: TPU
    traces name an operation by its whole HLO instruction."""
    return name.split(" = ", 1)[0].lstrip("%")


def load(path: str, window: tuple[int, int] | None = None) -> Trace:
    """Parse ``path``.  The window is the ``bench.window`` annotation
    when the host plane has one, else ``window``, else the span of the
    device events.  Device events are kept inside the window; the
    program's spans whole, sorted by start (the outer of two that start
    together first)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.devices[plane.name] = [
                        Event(short_name(e.name), int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                        for e in line.events]
                elif line.name == MODULES_LINE:
                    tr.modules[plane.name] = [
                        Event(e.name, int(e.start_ns),
                              int(e.start_ns + e.duration_ns))
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        kept = tr.host
                    elif SPAN_NAME.fullmatch(e.name):
                        kept = tr.spans
                    else:
                        continue
                    kept.append(Event(e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns)))
    marks = [e for e in tr.host if e.name == "bench.window"]
    if marks:
        tr.t0, tr.t1 = marks[0].start, marks[0].end
    elif window is not None:
        tr.t0, tr.t1 = window
    else:
        evs = [e for v in tr.devices.values() for e in v]
        tr.t0 = min((e.start for e in evs), default=0)
        tr.t1 = max((e.end for e in evs), default=0)
    for d in (tr.devices, tr.modules):
        for k in d:
            d[k] = sorted((e for e in d[k]
                           if e.end > tr.t0 and e.start < tr.t1),
                          key=lambda e: e.start)
    tr.host.sort(key=lambda e: e.start)
    tr.spans.sort(key=lambda e: (e.start, -e.end))
    return tr


def _clip(e: Event, t0: int, t1: int) -> tuple[int, int]:
    return max(e.start, t0), min(e.end, t1)


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not tr.devices:
        return 0.0
    tot = 0
    for evs in tr.devices.values():
        tot += sum(b - a for a, b in union(_clip(e, tr.t0, tr.t1)
                                           for e in evs))
    return tot / len(tr.devices) / 1e9


def matches(name: str, patterns) -> bool:
    return any(p in name for p in patterns)


def op_time_s(tr: Trace, patterns) -> float:
    """Summed device seconds of the operations whose own name contains
    one of ``patterns`` (a kernel is named after its function; its
    consumers only list it among their operands), averaged over the
    chips."""
    if not tr.devices:
        return 0.0
    tot = 0
    for evs in tr.devices.values():
        for e in evs:
            if matches(e.name, patterns):
                a, b = _clip(e, tr.t0, tr.t1)
                tot += max(b - a, 0)
    return tot / len(tr.devices) / 1e9


def program_runs(tr: Trace, patterns) -> list[Event]:
    """Executions of the programs whose name contains a pattern (on the
    first chip)."""
    if not tr.modules:
        return []
    first = sorted(tr.modules)[0]
    return [e for e in tr.modules[first] if matches(e.name, patterns)]


def program_time_s(tr: Trace, patterns) -> float:
    """Device seconds in which the matching programs' operations ran:
    the union of the operations inside those programs' executions,
    averaged over the chips."""
    if not tr.devices:
        return 0.0
    tot = 0
    for plane, evs in tr.devices.items():
        runs = [e for e in tr.modules.get(plane, [])
                if matches(e.name, patterns)]
        spans = union(_clip(e, tr.t0, tr.t1) for e in runs)
        ops = union(_clip(e, tr.t0, tr.t1) for e in evs)
        tot += _overlap(ops, spans)
    return tot / len(tr.devices) / 1e9


def _overlap(a: list, b: list) -> int:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def idle_gaps(tr: Trace) -> list[tuple[int, int]]:
    """Stretches of the window with no operation on the first chip."""
    if not tr.devices:
        return [(tr.t0, tr.t1)]
    first = sorted(tr.devices)[0]
    busy = union(_clip(e, tr.t0, tr.t1) for e in tr.devices[first])
    gaps, t = [], tr.t0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < tr.t1:
        gaps.append((t, tr.t1))
    return gaps


def self_times(evs: list, t0: int, t1: int) -> dict:
    """Self time by name: an operation's clipped duration less
    that of the operations nested inside it (a ``while`` loop holds its
    body's operations on the same line)."""
    out = defaultdict(int)
    stack: list[tuple[int, str]] = []         # (end, name) of open ops
    for e in sorted(evs, key=lambda e: (e.start, -e.end)):
        a, b = _clip(e, t0, t1)
        if b <= a:
            continue
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack and b <= stack[-1][0]:
            out[stack[-1][1]] -= b - a
        out[e.name] += b - a
        stack.append((b, e.name))
    return out


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device operations that took most self time and the idle
    time by what the host was doing (the annotation that covers most of
    each gap; ``untraced`` where none does), each in seconds."""
    ops = defaultdict(int)
    for evs in tr.devices.values():
        for k, v in self_times(evs, tr.t0, tr.t1).items():
            ops[k] += v
    n = max(len(tr.devices), 1)
    dev = sorted(((k, v / n / 1e9) for k, v in ops.items()),
                 key=lambda kv: -kv[1])[:top]
    host = [e for e in tr.host if e.name != "bench.window"]
    idle = defaultdict(int)
    j = 0
    for a, b in idle_gaps(tr):
        while j < len(host) and host[j].end <= a:
            j += 1
        cover = defaultdict(int)
        k = j
        while k < len(host) and host[k].start < b:
            lo, hi = max(host[k].start, a), min(host[k].end, b)
            if hi > lo:
                cover[host[k].name] += hi - lo
            k += 1
        name = max(cover, key=cover.get) if cover else "untraced"
        idle[name] += b - a
    gaps = sorted(((k, v / 1e9) for k, v in idle.items()),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in dev],
            "idle_gaps": [[k, v] for k, v in gaps]}
