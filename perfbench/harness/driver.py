"""Drive the serving system under test on the wall clock.

The path is the program's normal one: ``Server.push`` / ``Server.poke``
with ``AdmissionMiddleware`` under the ``open`` controller (every
request admitted), a ``ContinuousEngineAdapter`` over a paged
``ContinuousBatchingEngine`` (greedy, no speculation).  The benchmark
stamps each request from its own clock:

    due       when the traffic plan says it is sent (open loop), or
              when its caller's previous reply completed (closed
              loop); a request due inside the window is sent and
              stamped even where the driver only gets to it after the
              close
    first     when its first token became visible: the end of the
              ``push``/``poke`` whose host sync delivered it
    last      when it completed (its last token became visible)

Each ``push`` or ``poke`` of a non-idle server runs one refill (a
bucketed prefill wave) and one fused decode window; that is the
program's own schedule and the benchmark does not change it.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, fields

import numpy as np

from harness.traffic import Planned

# the prefill batch buckets the program pads a wave to (``_bucket`` in
# ``repro.serving.continuous``) for up to 8 slots
PREFILL_BUCKETS = (1, 2, 4, 8)


@dataclass
class Stamp:
    plan: Planned
    due: float
    first: float | None = None
    last: float | None = None
    tokens: list | None = None        # served tokens, once completed
    seen: int = 0                     # tokens visible so far


@dataclass
class Mark:
    """The run's state at one instant of the window."""
    t: float
    seen: dict                        # rid -> tokens visible
    counters: dict                    # the decode session's integer
                                      # counters
    queue_area: float                 # integral of n_queued from 0 to t
    tokens: int                       # tokens visible in all


@dataclass
class WindowResult:
    stamps: list                      # Stamp of every request due
    marks: dict                       # "open", "close"; with a trace
                                      # also "trace_on", "trace_off"
    drain_s: float                    # the last reply (or give-up) time
    calls: int = 0
    compiles: int = 0                 # compiles inside the window
    late_s: float = 0.0               # how late the generator ran

    @property
    def end_s(self) -> float:
        """When the window's last call ended."""
        return self.marks["close"].t

    @property
    def tokens_in_window(self) -> int:
        return self.marks["close"].tokens

    def span(self) -> tuple[Mark, Mark]:
        """The traced part of the window, or the whole window."""
        if "trace_on" in self.marks:
            return self.marks["trace_on"], self.marks["trace_off"]
        return self.marks["open"], self.marks["close"]


class _CompileCounter:
    """Counts backend compiles and persistent-cache loads in a span of
    the run (jax.monitoring listeners cannot be removed: one per
    process, switched by ``active``)."""
    _instance = None
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event in self.EVENTS:
            self.n += 1

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


def model_config(cell):
    """The program's ``ModelConfig`` as the cell runs it: every key of
    the configuration file that names one of its fields (``source``
    aside; a list as a tuple), the registry entry of the file's
    ``program_arch`` for what the file leaves out, on a paged pool."""
    from repro.configs import get_config
    base = get_config(cell.config["program_arch"])
    named = {f.name for f in fields(base)} - {"source"}
    sizes = {k: tuple(v) if isinstance(v, list) else v
             for k, v in cell.config.items() if k in named}
    return base.replace(**{**sizes,
                           "kv_block_size": int(cell.setup["kv_block_size"]),
                           "draft_layers": 0, "temperature": 0.0,
                           "remat": False})


class System:
    """The server under test, built once per run from the cell."""

    def __init__(self, cell, params):
        from repro.core.controller import AdmissionController
        from repro.serving.adapters import ContinuousEngineAdapter
        from repro.serving.api import (AdmissionMiddleware, Server,
                                       ServerConfig)
        from repro.serving.continuous import ContinuousBatchingEngine

        s = cell.setup
        self.cell = cell
        self.cfg = model_config(cell)
        self.plen = int(cell.traffic["prompt_len"])
        self.engine = ContinuousBatchingEngine(
            self.cfg, params, n_slots=int(s["slots"]),
            max_seq=int(s["max_seq"]), draft_depth=0,
            sync_every=int(s.get("sync_every", 8)))
        self.port = ContinuousEngineAdapter(self.engine,
                                            prompt_len=self.plen)
        self.server = Server(self.port,
                             ServerConfig(path="continuous-decode"),
                             middleware=[AdmissionMiddleware(
                                 AdmissionController(enabled=False))])

    def request(self, p: Planned, due: float):
        from repro.serving.api import InferRequest
        return InferRequest(rid=p.rid, arrival_s=due, payload=p.prompt,
                            kind="generate", max_new=p.max_new,
                            entropy_hint=0.5)

    def warm_up(self) -> None:
        """Compile (or load from the cache) every program the cell's
        traffic drives: one prefill wave per batch bucket the slots can
        fill (``_bucket``: 1, 2, 4, 8) at the cell's prompt length, and
        the decode window.  Each wave goes through a fresh decode
        session of the engine, whose jit caches persist."""
        from repro.serving.continuous import GenRequest
        rng = np.random.default_rng(0)
        slots = self.engine.n_slots
        for nb in PREFILL_BUCKETS:
            if nb > slots:
                continue
            sess = self.engine.start_session(self.plen)
            for i in range(nb):
                sess.push(GenRequest(
                    rid=10**9 + i,
                    prompt=rng.integers(0, self.cfg.vocab, self.plen,
                                        dtype=np.int64).astype(np.int32),
                    max_new=2))
            while not sess.idle:
                sess.advance()
            del sess
        gc.collect()

    def close(self) -> None:
        """Free the device memory the system holds (weights, KV pool),
        so the reference that follows has the chip to itself: jit
        caches may keep the engine object alive after the last
        reference here is dropped."""
        import jax
        sess = self.port.session
        held = [self.engine.params]
        if sess is not None:
            held.append(sess._pool)
        for leaf in jax.tree_util.tree_leaves(held):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
        self.engine.params = None
        gc.collect()

    def counters(self) -> dict:
        """Every integer counter of the decode session (none before the
        first request opens one)."""
        sess = self.port.session
        if sess is None:
            return {}
        return {k: v for k, v in sess.stats().items()
                if isinstance(v, int) and not isinstance(v, bool)}

    def n_queued(self) -> int:
        sess = self.port.session
        return 0 if sess is None else sess.n_queued

    def live_requests(self):
        """GenRequests seated in a slot right now."""
        sess = self.port.session
        return [] if sess is None else [r for r in sess.slots
                                        if r is not None]


def run_window(system: System, planned: list[Planned], seconds: float,
               *, drain_limit_s: float = 60.0,
               annotate=None, trace=None) -> WindowResult:
    """Serve the plan for ``seconds`` on the wall clock, then wait for
    every request sent (at most ``drain_limit_s`` past the close).

    ``trace = (t_on, t_off, start, stop)`` calls ``start()`` at the
    first call boundary at or after ``t_on`` and ``stop()`` at the
    first at or after ``t_off`` (or the close); ``annotate(name)``
    returns a context manager that marks host work on the profiler's
    clock, and the traced span is marked ``bench.window``."""
    import contextlib
    ann = annotate or (lambda name: contextlib.nullcontext())
    server = system.server
    server.start()
    stamps: dict[int, Stamp] = {}
    scheduled = [p for p in planned if p.due_s is not None]
    oi = 0                            # next of ``scheduled`` to send
    callers: dict[int, list[Planned]] = {}
    for p in planned:
        if p.due_s is None:
            callers.setdefault(p.client, []).append(p)
    # closed loop: every caller's first request is due as the window
    # opens, each later one when the caller's previous reply completes
    ready = [(0.0, q.pop(0)) for _, q in sorted(callers.items())]
    counter = _CompileCounter.get()
    counter.n = 0
    counter.active = True
    late, calls, tokens_seen = 0.0, 0, 0
    queue_area, last_q = 0.0, (0.0, 0)
    marks: dict[str, Mark] = {}
    span_mark = None
    stopped_at = 0.0                  # when writing the trace ended
    t0 = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - t0

    def mark(name: str, t: float) -> None:
        marks[name] = Mark(
            t=t, seen={rid: st.seen for rid, st in stamps.items()},
            counters=system.counters(),
            queue_area=queue_area + last_q[1] * (t - last_q[0]),
            tokens=tokens_seen)

    def observe(t: float, responses) -> None:
        nonlocal tokens_seen, queue_area, last_q
        for r in system.live_requests():
            st = stamps.get(r.rid)
            if st is None:
                continue
            n = len(r.generated)
            if n and st.first is None:
                st.first = t
            tokens_seen += n - st.seen
            st.seen = n
        for resp in responses:
            st = stamps[resp.rid]
            out = list(resp.output) if resp.output is not None else []
            if st.first is None and out:
                st.first = t
            st.last = t
            st.tokens = out
            tokens_seen += len(out) - st.seen
            st.seen = len(out)
            q = callers.get(st.plan.client)
            if q and t < seconds:
                ready.append((t, q.pop(0)))
        queue_area += last_q[1] * (t - last_q[0])
        last_q = (t, system.n_queued())

    mark("open", 0.0)
    while True:
        t = now()
        in_window = t < seconds
        if trace is not None:
            t_on, t_off, start, stop = trace
            if "trace_on" not in marks and in_window and t >= t_on:
                start()
                span_mark = ann("bench.window")
                span_mark.__enter__()
                mark("trace_on", now())
                continue
            if (span_mark is not None and "trace_off" not in marks
                    and (t >= t_off or not in_window)):
                mark("trace_off", t)
                span_mark.__exit__(None, None, None)
                stop()
                stopped_at = now()
        if not in_window and "close" not in marks:
            mark("close", t)
            counter.active = False
        # every request due inside the window is sent, the overdue
        # ones after the close too: each is stamped from its due time
        item = None
        if ready:
            item = ready.pop(0)
        elif oi < len(scheduled) and scheduled[oi].due_s <= t:
            item = (scheduled[oi].due_s, scheduled[oi])
            oi += 1
        if item is not None:
            due, p = item
            late = max(late, t - due)
            stamps[p.rid] = Stamp(plan=p, due=due)
            with ann("bench.push"):
                out = server.push(system.request(p, due))
            calls += 1
            observe(now(), out)
            continue
        pending = any(st.last is None for st in stamps.values())
        # the wait for the last replies starts at the close, or after
        # the trace was written if that ran past it
        drain_until = max(seconds, stopped_at) + drain_limit_s
        if not in_window and oi == len(scheduled) and (
                not pending or t > drain_until):
            break
        sess = system.port.session
        if sess is not None and not sess.idle:
            with ann("bench.poke"):
                out = server.poke(t)
            calls += 1
            observe(now(), out)
            continue
        # nothing to run: sleep until the next arrival or the close
        nxt = seconds
        if oi < len(scheduled):
            nxt = min(nxt, scheduled[oi].due_s)
        with ann("bench.idle"):
            time.sleep(max(min(nxt - t, 0.002), 0.0))
        observe(now(), [])
    return WindowResult(stamps=list(stamps.values()), marks=marks,
                        drain_s=now(), calls=calls, compiles=counter.n,
                        late_s=late)
