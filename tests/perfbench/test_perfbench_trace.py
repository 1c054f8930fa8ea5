"""The reduction from a profiler trace to device time: interval
arithmetic on hand-made events, then the whole reduction on a small
trace recorded on a TPU v5e and kept as a fixture."""
import gzip
import os
import shutil

import pytest

from harness import trace as tm
from harness.trace import Event, Trace


def _ev(name, a, b):
    return Event(name, a, b)


@pytest.fixture
def hand():
    """Window [0, 100) ns on one chip: a window program [10, 50) with
    three ops, a prefill program [60, 80) with one op, host annotations
    covering the gaps."""
    plane = "/device:TPU:0"
    ops = [_ev("fusion.1", 10, 20),
           _ev("paged_decode_attention.3", 20, 35),
           _ev("fusion.2", 30, 50),
           _ev("flash_attention.1", 60, 80)]
    mods = [_ev("jit_step_k(1)", 10, 50), _ev("jit_prefill_p(2)", 60, 80)]
    host = [_ev("bench.poke", 0, 12), _ev("bench.idle", 50, 58),
            _ev("bench.push", 58, 100)]
    return Trace(devices={plane: ops}, modules={plane: mods}, host=host,
                 t0=0, t1=100)


def test_union_and_busy(hand):
    assert tm.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    # ops cover [10, 50) and [60, 80): 60 ns of a 100 ns window
    assert tm.busy_s(hand) == pytest.approx(60e-9)
    assert hand.window_s == pytest.approx(100e-9)


def test_kernel_and_program_time(hand):
    assert tm.op_time_s(hand, ("paged_decode_attention",)) == \
        pytest.approx(15e-9)
    assert tm.op_time_s(hand, ("flash_attention",)) == pytest.approx(20e-9)
    assert tm.program_time_s(hand, ("jit_step_k",)) == pytest.approx(40e-9)
    assert len(tm.program_runs(hand, ("jit_step_k",))) == 1
    assert tm.program_time_s(hand, ("jit_prefill_p",)) == \
        pytest.approx(20e-9)


def test_idle_gaps_are_named_by_the_host(hand):
    assert tm.idle_gaps(hand) == [(0, 10), (50, 60), (80, 100)]
    b = tm.breakdown(hand)
    # [0,10) under bench.poke; [50,60): idle 8 ns vs push 2 ns;
    # [80,100) under bench.push
    assert dict((k, v) for k, v in b["idle_gaps"]) == pytest.approx(
        {"bench.poke": 10e-9, "bench.idle": 10e-9, "bench.push": 20e-9})
    ops = dict((k, v) for k, v in b["device_ops"])
    assert ops["fusion.2"] == pytest.approx(20e-9)
    assert len(b["device_ops"]) <= 10


def test_events_are_clipped_to_the_window(hand):
    hand.t0, hand.t1 = 15, 70
    # ops inside [15, 70): [15,20) + [20,35) + [30,50) -> union [15,50),
    # plus [60,70)
    assert tm.busy_s(hand) == pytest.approx(45e-9)
    assert tm.op_time_s(hand, ("fusion",)) == pytest.approx(25e-9)


FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "stablelm-2layer-v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """1.45 s of the chat cell's serving loop recorded on one TPU v5e:
    stablelm-3b's published widths cut to 2 layers, 8 slots x 512,
    paged pool, 49 decode windows and 9 prefill waves."""
    path = tmp_path_factory.mktemp("trace") / "fixture.xplane.pb"
    with gzip.open(FIXTURE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tm.load(str(path))


def test_recorded_trace_reduces_to_the_recorded_device_time(chip_trace):
    tr = chip_trace
    assert list(tr.devices) == ["/device:TPU:0"]
    assert tr.window_s == pytest.approx(1.450677301)
    assert tm.busy_s(tr) == pytest.approx(0.829072632)
    assert len(tm.program_runs(tr, ("jit_step_k",))) == 49
    assert len(tm.program_runs(tr, ("jit_prefill_p",))) == 9
    step = tm.program_time_s(tr, ("jit_step_k",))
    pre = tm.program_time_s(tr, ("jit_prefill_p",))
    assert step == pytest.approx(0.81440109)
    assert pre == pytest.approx(0.013682664)
    paged = tm.op_time_s(tr, ("paged_decode_attention",))
    flash = tm.op_time_s(tr, ("flash_attention",))
    assert paged == pytest.approx(0.114025839)
    assert flash == pytest.approx(0.000648304)
    # the parts never exceed the whole
    assert paged < step and flash < pre
    assert step + pre <= tm.busy_s(tr) <= tr.window_s


def test_recorded_trace_breakdown(chip_trace):
    b = tm.breakdown(chip_trace)
    ops = [name for name, _ in b["device_ops"]]
    assert len(ops) == 10 and ops[:2] == ["fusion.197",
                                          "paged_decode_attention.11"]
    assert all(" " not in name for name in ops)
    gaps = dict(b["idle_gaps"])
    assert set(gaps) == {"bench.idle", "bench.poke", "bench.push"}
    idle = chip_trace.window_s - tm.busy_s(chip_trace)
    assert sum(gaps.values()) == pytest.approx(idle)
