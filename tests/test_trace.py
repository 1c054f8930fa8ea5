"""Observability layer: spans + clocks, the metrics registry, the
Chrome/Prometheus exports, trace validation, the energy-drift audit,
and the Server-level root-span contract."""
import json
import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.core import LatencyModel
from repro.serving import (DirectPath, DynamicBatcher, InferRequest,
                           Oracle, OracleEngine, Server, ServerConfig)
from repro.telemetry import (NULL_METRICS, NULL_TRACER, EnergyDriftAudit,
                             MetricsRegistry, ProcessTimeSource, Tracer,
                             VirtualClock, WallClock, to_chrome,
                             validate_chrome, validate_trace)
from repro.telemetry.trace import Span
from repro.telemetry.validate import main as validate_main


# ---------------------------------------------------------------------------
# spans and clocks


def _nested(tracer):
    root = tracer.begin("request", 0.0, rid=1)
    child = tracer.span("prefill", 0.1, 0.4, parent=root,
                        resource="prefill-0")
    grand = tracer.span("transfer", 0.4, 0.5, parent=child,
                        resource="link")
    tracer.end(root, 1.0)
    return root, child, grand


@pytest.mark.parametrize("clock", [WallClock, lambda: VirtualClock(0.0)])
def test_span_nesting_under_both_clocks(clock):
    tr = Tracer(clock=clock())
    root, child, grand = _nested(tr)
    assert child.parent_id == root.span_id
    assert grand.parent_id == child.span_id
    assert tr.children_of(root) == [child]
    assert tr.children_of(child) == [grand]
    assert root.duration == pytest.approx(1.0)
    assert not tr.open_spans()
    assert validate_trace(tr.spans) == []


def test_virtual_clock_fallback_times():
    clk = VirtualClock(5.0)
    tr = Tracer(clock=clk)
    s = tr.begin("work")           # no explicit t -> clock now
    clk.advance(2.5)
    tr.end(s)
    assert s.t_start == pytest.approx(5.0)
    assert s.duration == pytest.approx(2.5)


def test_wall_clock_starts_near_zero():
    t = WallClock().now()
    assert 0.0 <= t < 1.0


def test_event_is_instant_and_null_tracer_noops():
    tr = Tracer(clock=VirtualClock())
    e = tr.event("route", 3.0, chosen="direct-0")
    assert e.duration == 0.0 and e.attrs["chosen"] == "direct-0"
    assert NULL_TRACER.enabled is False
    s = NULL_TRACER.begin("x", 0.0)
    NULL_TRACER.end(s, 1.0)
    NULL_TRACER.event("y")
    assert NULL_TRACER.spans == []


def test_scope_nests_on_the_tracers_clock():
    clk = VirtualClock(1.0)
    tr = Tracer(clock=clk)
    with tr.scope("server.push") as push:
        clk.advance(0.5)
        with tr.scope("sched.advance") as adv:
            with tr.scope("step.window", active=3) as win:
                clk.advance(2.0)
        with tr.scope("server.absorb") as absorb:
            clk.advance(0.25)
    assert push.parent_id is None
    assert adv.parent_id == push.span_id
    assert win.parent_id == adv.span_id and win.attrs == {"active": 3}
    assert absorb.parent_id == push.span_id
    assert (push.t_start, push.t_end) == (1.0, 3.75)
    assert (win.t_start, win.t_end) == (1.5, 3.5)
    assert (absorb.t_start, absorb.t_end) == (3.5, 3.75)
    assert not tr.open_spans() and validate_trace(tr.spans) == []
    # a scope left by an exception is closed, and the next one is a
    # root again
    with pytest.raises(RuntimeError):
        with tr.scope("sched.refill"):
            raise RuntimeError("wave refused")
    with tr.scope("server.poke") as poke:
        pass
    assert tr.find("sched.refill")[0].closed and poke.parent_id is None


def test_null_tracer_scope_records_nothing():
    with NULL_TRACER.scope("server.push"):
        with NULL_TRACER.scope("step.window", active=3):
            pass
    assert NULL_TRACER.spans == []


# ---------------------------------------------------------------------------
# validation


def test_validate_catches_every_defect():
    tr = Tracer(clock=VirtualClock())
    tr.begin("open", 0.0)                                 # never ended
    tr.span("neg", 1.0, 0.5)                              # negative dur
    tr.span("orphan", 0.0, 0.1, parent=999)               # bad parent
    tr.span("a", 0.0, 1.0, resource="line")
    tr.span("b", 0.5, 1.5, resource="line")               # overlap
    problems = "\n".join(validate_trace(tr.spans))
    for marker in ("open span", "negative duration", "orphan span",
                   "overlap on resource"):
        assert marker in problems


def test_validate_chrome_round_trip():
    tr = Tracer(clock=VirtualClock())
    _nested(tr)
    doc = tr.to_chrome()
    assert validate_chrome(doc) == []
    # corrupt it: drop one async end -> unbalanced pair
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if e["ph"] != "e"]
    assert any("unbalanced" in p for p in validate_chrome(doc))


def test_chrome_export_shapes():
    tr = Tracer(clock=VirtualClock())
    _nested(tr)
    tr.event("autoscale", 0.9, resource="autoscaler", action="drain")
    ev = to_chrome(tr.spans)["traceEvents"]
    phases = {e["ph"] for e in ev}
    assert {"X", "b", "e", "i", "M"} <= phases
    # resource spans land on named tracks
    names = {e["args"]["name"] for e in ev if e["ph"] == "M"}
    assert {"prefill-0", "link", "autoscaler"} <= names
    # async request events share their root ancestor's id
    reqs = [e for e in ev if e["ph"] in ("b", "e")]
    assert len(reqs) == 2 and len({e["id"] for e in reqs}) == 1


def test_validate_cli(tmp_path):
    tr = Tracer(clock=VirtualClock())
    _nested(tr)
    m = MetricsRegistry()
    m.gauge("fleet_pressure").set(0.5, replica="direct-0")
    trace, snap = tmp_path / "t.json", tmp_path / "m.json"
    tr.write_chrome(str(trace))
    m.write_json(str(snap))
    assert validate_main([str(trace), str(snap),
                          "--require-gauge", "fleet_pressure"]) == 0
    assert validate_main([str(trace), str(snap),
                          "--require-gauge", "missing_gauge"]) == 1


# ---------------------------------------------------------------------------
# metrics registry


def test_metrics_label_aggregation():
    m = MetricsRegistry()
    c = m.counter("requests_total", "served")
    c.inc(path="direct", admitted="True")
    c.inc(2, admitted="True", path="direct")   # kwarg order irrelevant
    c.inc(path="batched", admitted="False")
    assert c.value(path="direct", admitted="True") == 3
    assert c.value(path="batched", admitted="False") == 1
    g = m.gauge("pressure")
    g.set(1.5, replica="a")
    g.set(0.5, replica="a")                    # last write wins
    g.add(0.25, replica="a")
    assert g.value(replica="a") == pytest.approx(0.75)
    h = m.histogram("latency_s", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v, path="direct")
    snap = h.snapshot()[0]
    assert snap["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}
    assert snap["sum"] == pytest.approx(5.55)


def test_metrics_kind_collision_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")


def test_prometheus_golden():
    m = MetricsRegistry()
    m.counter("req_total", "requests").inc(3, path="direct")
    m.gauge("tau").set(float("inf"), replica="r0")
    h = m.histogram("lat_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(2.0)
    assert m.to_prometheus() == (
        "# TYPE lat_s histogram\n"
        'lat_s_bucket{le="0.1"} 1\n'
        'lat_s_bucket{le="1.0"} 1\n'
        'lat_s_bucket{le="+Inf"} 2\n'
        "lat_s_sum 2.05\n"
        "lat_s_count 2\n"
        "# HELP req_total requests\n"
        "# TYPE req_total counter\n"
        'req_total{path="direct"} 3.0\n'
        "# TYPE tau gauge\n"
        'tau{replica="r0"} +Inf\n')


def test_null_metrics_noops():
    assert NULL_METRICS.enabled is False
    NULL_METRICS.counter("x").inc(5, path="p")
    NULL_METRICS.gauge("y").set(1.0)
    NULL_METRICS.histogram("z").observe(0.5)
    assert NULL_METRICS.counter("x").value() == 0.0
    assert NULL_METRICS.snapshot() == {"counters": {}, "gauges": {},
                                       "histograms": {}}


# ---------------------------------------------------------------------------
# energy drift audit


def test_energy_drift_audit_reports_ratio():
    class Fake:
        name = "fake"
        j = 0.0

        def read_j(self):
            return self.j

    src = Fake()
    audit = EnergyDriftAudit(source=src).start()
    src.j = 50.0                               # measured 50 J
    audit.record(100.0, n_requests=10)         # modelled 100 J
    rep = audit.stop()
    assert rep["drift_ratio"] == pytest.approx(2.0)
    assert rep["modelled_j_per_request"] == pytest.approx(10.0)
    m = MetricsRegistry()
    audit.export(m)
    assert m.gauge("energy_drift_ratio").value(
        source="fake") == pytest.approx(2.0)


def test_process_time_source_monotone():
    src = ProcessTimeSource(p_active_w=100.0)
    a = src.read_j()
    sum(i * i for i in range(20000))           # burn a little CPU
    assert src.read_j() >= a


# ---------------------------------------------------------------------------
# Server-level contract: one root span per request, triage inside it,
# no orphans, root covers arrival..finish


def _oracle(n, seed=0):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 2, n)
    return Oracle(full_pred=labels.copy(), proxy_pred=labels.copy(),
                  entropy=rng.uniform(0, 0.6, n), labels=labels,
                  proxy_latency=LatencyModel(0.0002, 0.0))


def test_server_roots_cover_every_response():
    n = 12
    lat = LatencyModel(0.005, 0.001)
    engine = OracleEngine(_oracle(n), DirectPath(lat),
                          DynamicBatcher(lat, max_batch_size=4,
                                         queue_window_s=0.01))
    tracer = Tracer(clock=VirtualClock())
    metrics = MetricsRegistry()
    server = Server(engine, ServerConfig(path="auto"),
                    tracer=tracer, metrics=metrics, name="s0")
    reqs = [InferRequest(rid=i, arrival_s=0.01 * i) for i in range(n)]
    resps = server.serve(reqs)

    assert validate_trace(tracer.spans) == []
    roots = {s.attrs["rid"]: s for s in tracer.find("request")}
    assert len(roots) == n
    for r in resps:
        root = roots[r.rid]
        kids = tracer.children_of(root)
        assert any(k.name == "triage" for k in kids)
        assert root.t_start == pytest.approx(r.arrival_s)
        assert root.t_end == pytest.approx(r.t_finish)
        assert "unfinished" not in root.attrs.values()
    # every non-root span hangs off some recorded span
    ids = {s.span_id for s in tracer.spans}
    assert all(s.parent_id in ids for s in tracer.spans
               if s.parent_id is not None)
    # execute spans carry the flush reason and land on the named track
    execs = tracer.find("execute")
    assert execs and all(s.resource.startswith("s0:") for s in execs)
    assert all(s.attrs.get("flush") in ("size", "window", "drain",
                                        "direct") for s in execs)
    # metrics saw every response
    c = metrics.counter("serving_requests_total")
    assert sum(v for v in c.series.values()) == n
    h = metrics.histogram("serving_latency_s")
    assert sum(s.total for s in h.series.values()) == n


def test_server_disabled_tracing_records_nothing():
    n = 6
    lat = LatencyModel(0.005, 0.001)
    engine = OracleEngine(_oracle(n), DirectPath(lat),
                          DynamicBatcher(lat, max_batch_size=4,
                                         queue_window_s=0.01))
    server = Server(engine, ServerConfig(path="auto"))
    resps = server.serve([InferRequest(rid=i, arrival_s=0.01 * i)
                          for i in range(n)])
    assert len(resps) == n
    assert server.tracer is None or server.tracer is NULL_TRACER
    assert NULL_TRACER.spans == []


# ---------------------------------------------------------------------------
# run exporter


def test_export_observability_lands_artifacts(tmp_path):
    from repro.telemetry import Tracker, export_observability

    tr = Tracer(clock=VirtualClock())
    _nested(tr)
    m = MetricsRegistry()
    m.gauge("fleet_pressure").set(0.1, replica="r0")
    audit = EnergyDriftAudit(source=ProcessTimeSource()).start()
    audit.record(1.0, 1)
    audit.stop()
    run = Tracker(root=str(tmp_path)).start_run("obs")
    paths = export_observability(run, tracer=tr, metrics=m, audit=audit)
    run.finish()
    assert set(paths) == {"trace", "metrics", "prometheus", "drift"}
    with open(paths["trace"]) as f:
        assert validate_chrome(json.load(f)) == []
    with open(paths["drift"]) as f:
        rep = json.load(f)
    assert rep["source"] == "process-time"
    assert math.isfinite(rep["modelled_j"])


# ---------------------------------------------------------------------------
# compile watcher (xla.compile spans + compile_seconds gauge)
# ---------------------------------------------------------------------------

def test_compile_watcher_exports_spans_and_gauges():
    """A real jit compile inside the watch window must land as a
    serialized xla.compile span and the compile_seconds gauge; the
    gauge is ALWAYS set (0.0 on a warm start) so CI can require it."""
    import jax
    import jax.numpy as jnp

    from repro.telemetry import CompileWatcher

    w = CompileWatcher().install()
    # a fresh shape forces one backend compile under this watcher
    jax.jit(lambda x: (x * 2 + 1).sum())(jnp.ones((17, 23))).block_until_ready()
    tracer, metrics = Tracer(), MetricsRegistry()
    report = w.export(tracer, metrics)
    assert report["compile_count"] >= 1
    spans = tracer.find("xla.compile")
    assert len(spans) == report["compile_count"]
    assert validate_trace(tracer.spans) == []        # serialized, no overlap
    snap = metrics.snapshot()
    assert snap["gauges"]["compile_seconds"][0]["value"] > 0.0

    # warm start: nothing compiles, gauge still present at 0.0
    w2 = CompileWatcher().install()
    m2 = MetricsRegistry()
    w2.export(None, m2)
    snap2 = m2.snapshot()
    assert snap2["gauges"]["compile_seconds"][0]["value"] == 0.0


def test_compile_watcher_events_dropped_when_inactive():
    """Compile events with no active watcher are dropped — the
    untraced fast path records nothing."""
    import jax
    import jax.numpy as jnp

    from repro.telemetry import CompileWatcher

    w = CompileWatcher()                    # NOT installed
    jax.jit(lambda x: x - 3.5)(jnp.ones((5, 31))).block_until_ready()
    assert w.compile_count == 0 and w.compile_seconds == 0.0
