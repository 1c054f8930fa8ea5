"""The serving program's own spans in a profiler trace: the idle time
of the chip put down to the innermost program span on the host, on
hand-made intervals; the spans of a served window recorded by the
profiler on the CPU; and the shared clock of a trace recorded on a TPU
v5e, with the three host-idle readers on it."""
import gzip
import os
import shutil
from types import SimpleNamespace

import pytest
from perfbench_testlib import REPO

from harness import driver, spec, traffic, weights
from harness import spans as sm
from harness import trace as tm
from harness.trace import Event, Trace

# the serving program's layers, by the prefix of their span names
LAYERS = ("server.", "sched.", "step.")


def _ev(name, a, b):
    return Event(name, a, b)


@pytest.fixture
def spanned():
    """Window [0, 100) ns on one chip, with a window program's ops on
    [10, 50) and a prefill's on [60, 80), so idle on [0, 10), [50, 60)
    and [80, 100); the benchmark's annotations, and the program's
    spans nested inside them."""
    plane = "/device:TPU:0"
    ops = [_ev("fusion.1", 10, 20),
           _ev("paged_decode_attention.3", 20, 35),
           _ev("fusion.2", 30, 50),
           _ev("flash_attention.1", 60, 80)]
    mods = [_ev("jit_step_k(1)", 10, 50), _ev("jit_prefill_p(2)", 60, 80)]
    host = [_ev("bench.poke", 0, 50), _ev("bench.idle", 50, 58),
            _ev("bench.push", 58, 100)]
    program = [_ev("server.poke", 0, 50),
               _ev("sched.advance", 1, 50),
               _ev("step.window", 4, 50),
               _ev("server.push", 58, 100),
               _ev("server.admit", 58, 59),
               _ev("sched.advance", 59, 95),
               _ev("sched.refill", 59, 60),
               _ev("step.prefill", 60, 82),
               _ev("sched.seat", 82, 84),
               _ev("step.window", 84, 95),
               _ev("server.absorb", 95, 99)]
    tr = Trace(devices={plane: ops}, modules={plane: mods}, host=host,
               t0=0, t1=100)
    return tr, program


def test_innermost_span_names_each_instant(spanned):
    _, program = spanned
    segs = sm.innermost(program)
    assert segs[:4] == [(0, 1, "server.poke"), (1, 4, "sched.advance"),
                        (4, 50, "step.window"), (58, 59, "server.admit")]
    assert segs[-3:] == [(84, 95, "step.window"),
                         (95, 99, "server.absorb"),
                         (99, 100, "server.push")]
    # disjoint, in order, and covering exactly the union of the spans
    assert all(a < b <= c for (a, b, _), (c, _, _) in zip(segs, segs[1:]))
    assert sum(b - a for a, b, _ in segs) == 50 + 42


def test_idle_is_put_down_to_the_innermost_span(spanned):
    tr, program = spanned
    assert sm.idle_by_span(tr, program) == {
        "server.poke": 1, "sched.advance": 3, "step.window": 6 + 11,
        "server.admit": 1, "sched.refill": 1, "step.prefill": 2,
        "sched.seat": 2, "server.absorb": 4, "server.push": 1}
    shares = {p: sm.host_idle_pct(tr, program, p) for p in LAYERS}
    assert shares == pytest.approx({"server.": 7.0, "sched.": 6.0,
                                    "step.": 19.0})
    # the rest of the idle share is the benchmark's own: bench.idle
    idle_pct = 100.0 * (1 - tm.busy_s(tr) / tr.window_s)
    assert idle_pct - sum(shares.values()) == pytest.approx(8.0)
    # the benchmark's own reduction is untouched by the program spans:
    # each gap goes whole to the annotation that covers most of it
    assert dict(tm.breakdown(tr)["idle_gaps"]) == pytest.approx(
        {"bench.poke": 10e-9, "bench.idle": 10e-9, "bench.push": 20e-9})


def test_shares_read_zero_or_nothing(spanned):
    tr, program = spanned
    only_sched = [e for e in program if e.name.startswith("sched.")]
    assert sm.host_idle_pct(tr, only_sched, "server.") == 0.0
    # with the step spans gone, their idle falls to sched.advance
    assert sm.host_idle_pct(tr, only_sched, "sched.") == pytest.approx(
        9.0 + 1.0 + 2.0 + 2.0 + 11.0)
    assert sm.host_idle_pct(tr, [], "sched.") is None
    no_chip = Trace(host=tr.host, t0=0, t1=100)
    assert sm.host_idle_pct(no_chip, program, "sched.") is None


def test_profiled_window_records_the_program_spans(tiny_bench, tmp_path):
    """The benchmark's own serving loop under a profiler session on the
    CPU: the program's spans reach the host plane with no tracer passed
    in; the harness keeps its own annotations in ``Trace.host`` and
    the program's spans, and nothing of the runtime's, in
    ``Trace.spans``."""
    import jax
    cell = tiny_bench.cell("tiny-mha.chat")
    system = driver.System(cell, weights.make_params(cell.config, 5,
                                                      cell.bench_dir))
    system.warm_up()
    plan = traffic.plan(cell.traffic, 1.0, 5, int(cell.config["vocab"]),
                        rate_qps=12.0)
    win = driver.run_window(
        system, plan, 1.0, annotate=jax.profiler.TraceAnnotation,
        trace=(0.0, 1.0, lambda: jax.profiler.start_trace(str(tmp_path)),
               jax.profiler.stop_trace))
    path = tm.find_xplane(str(tmp_path))
    tr = tm.load(path)
    assert {e.name for e in tr.host} <= {"bench.window", "bench.push",
                                         "bench.poke", "bench.idle"}
    program = tr.spans
    assert program == sorted(program, key=lambda e: (e.start, -e.end))
    names = {e.name for e in program}
    assert {"server.push", "server.admit", "sched.advance",
            "sched.refill", "step.prefill", "sched.seat", "step.window",
            "sched.harvest", "server.absorb"} <= names
    assert all(n.startswith(LAYERS) for n in names)
    on, off = win.marks["trace_on"], win.marks["trace_off"]
    windows = [e for e in program if e.name == "step.window"]
    # no session, so no counter, before the first request
    assert len(windows) == (off.counters["host_syncs"]
                            - on.counters.get("host_syncs", 0)) > 0
    # every program span lies inside the benchmark's call that made it
    calls = [e for e in tr.host if e.name in ("bench.push", "bench.poke")]
    for e in program:
        assert any(c.start <= e.start and e.end <= c.end for c in calls), e
    # no chip plane on the CPU: the shares are not read
    assert sm.host_idle_pct(tr, program, "step.") is None


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "stablelm-2layer-spans-v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_spans_path(tmp_path_factory):
    """1.27 s of the chat cell's serving loop recorded on one TPU v5e
    with the program's spans: stablelm-3b's published widths cut to 2
    layers, 8 slots x 512, paged pool, 22 decode windows and 3 prefill
    waves."""
    path = tmp_path_factory.mktemp("trace") / "fixture.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def chip_spans(chip_spans_path):
    tr = tm.load(chip_spans_path)
    return tr, tr.spans


def test_trace_keeps_the_spans_the_prefix_reading_kept(chip_spans_path,
                                                       chip_spans):
    """``Trace.spans``, found by the name pattern of the program's
    scopes, holds exactly what the earlier reading by the three layers'
    prefixes held: every such host event, whole, in the same order."""
    from jax.profiler import ProfileData
    by_prefix = []
    for plane in ProfileData.from_file(chip_spans_path).planes:
        if plane.name != tm.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(LAYERS):
                    by_prefix.append(Event(e.name, int(e.start_ns),
                                           int(e.start_ns
                                               + e.duration_ns)))
    by_prefix.sort(key=lambda e: (e.start, -e.end))
    assert len(by_prefix) == 107
    assert chip_spans[1] == by_prefix


def test_host_idle_readers_on_the_recorded_trace(chip_spans):
    """The three per-layer readers give the layers' shares of the
    recorded span, and with the benchmark's own idle they make up no
    more than the device's idle share."""
    tr, _ = chip_spans
    ctx = SimpleNamespace(trace=tr)
    bench_dir = os.path.join(REPO, "perfbench")

    def read(name):
        return spec.metric_reader(bench_dir, name).read(ctx)
    shares = [read(p + "host_idle_pct") for p in LAYERS]
    assert shares == pytest.approx([0.16196023, 3.93963852, 5.19221026])
    assert sum(shares) <= read("device.idle_pct")
    # an untraced run has nothing to read: the metrics are left out
    ctx = SimpleNamespace(trace=None)
    assert [read(p + "host_idle_pct") for p in LAYERS] == [None] * 3


def _inside(run, spans):
    return any(s.start <= run.start and run.end <= s.end for s in spans)


def test_device_programs_run_inside_the_host_span_that_waited(chip_spans):
    tr, program = chip_spans
    windows = [e for e in program if e.name == "step.window"]
    prefills = [e for e in program if e.name == "step.prefill"]
    steps = tm.program_runs(tr, ("jit_step_k",))
    waves = tm.program_runs(tr, ("jit_prefill_p",))
    assert steps and waves
    # a program that started in the traced span ran inside the host
    # span that launched it and waited on it, on the same clock
    assert all(_inside(r, windows) for r in steps if r.start >= tr.t0)
    assert all(_inside(r, prefills) for r in waves if r.start >= tr.t0)


def test_recorded_idle_is_put_down_to_the_layers(chip_spans):
    tr, program = chip_spans
    assert tr.window_s == pytest.approx(1.266143525)
    assert len(tm.program_runs(tr, ("jit_step_k",))) == 22
    assert len(tm.program_runs(tr, ("jit_prefill_p",))) == 3
    shares = [sm.host_idle_pct(tr, program, p) for p in LAYERS]
    assert shares == pytest.approx([0.16196023, 3.93963852, 5.19221026])
    idle_pct = 100.0 * (1 - tm.busy_s(tr) / tr.window_s)
    assert idle_pct == pytest.approx(70.76018084)
    assert sum(shares) <= idle_pct
    # the kernels keep the names the roofline readers match
    ops = {e.name.rsplit(".", 1)[0] for e in tr.devices["/device:TPU:0"]}
    assert {"paged_decode_attention", "flash_attention"} <= ops
