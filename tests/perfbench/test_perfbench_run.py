"""Whole runs of the harness on the CPU at smoke size.

The entry point refuses a machine without a TPU before it builds
anything; the rest of a run is driven here with the look for a chip
skipped: once as it is (``correct`` true, the cell's metrics printed),
and once with the timed path broken underneath — a token altered where
the program produces it — where ``correct`` has to come out false.
"""
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
from perfbench_testlib import REPO, copy_benchmark

from harness import driver, measure, runner, traffic, weights


def _entry(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "stablelm-3b.chat", "--seed", "2147483659", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(stdout: str) -> bool:
    return not any(line.lstrip().startswith("{")
                   for line in stdout.splitlines())


def test_entry_point_refuses_a_machine_without_tpu():
    p = _entry(REPO)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "not a TPU" in p.stderr


def test_entry_point_fails_with_only_the_benchmark(tmp_path):
    root = copy_benchmark(str(tmp_path))     # no program beside it
    p = _entry(root)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_open_loop_run_reports_its_metrics(tiny_bench):
    res = runner.run_cell(tiny_bench, "tiny-mha.chat", seed=7,
                          seconds=2.0, trace=False, t_start=0.0,
                          require_tpu=False, cache=False)
    assert res["correct"], res["checks"]
    names = {m["name"] for m in tiny_bench.cell("tiny-mha.chat").end_to_end}
    assert set(res["metrics"]) == names
    assert res["attempted"] == 16 and res["failed"] == 0
    assert res["device"]["count"] == 1
    json.dumps(res)                       # one JSON line


def test_closed_loop_run_reports_its_metrics(tiny_bench):
    res = runner.run_cell(tiny_bench, "tiny-mha.batch", seed=7,
                          seconds=2.0, trace=False, t_start=0.0,
                          require_tpu=False, cache=False)
    assert res["correct"], res["checks"]
    names = {m["name"]
             for m in tiny_bench.cell("tiny-mha.batch").end_to_end}
    assert "out_tok_s" in names and set(res["metrics"]) == names
    assert res["metrics"]["out_tok_s"]["value"] > 0
    assert res["attempted"] >= 6 and res["failed"] == 0


def test_requests_due_in_the_window_are_sent_after_a_stall(tiny_bench):
    """A server that stalls past the close still gets every request
    due inside the window, each stamped from its due time, so a stall
    near the close cannot hide its own victims."""
    cell = tiny_bench.cell("tiny-mha.chat")
    params = weights.make_params(cell.config, 3, cell.bench_dir)
    system = driver.System(cell, params)
    plan = traffic.plan(cell.traffic, 1.0, 3, int(cell.config["vocab"]),
                        rate_qps=12.0)
    push = system.server.push

    def slow_push(req):
        out = push(req)
        if req.rid == 0:
            time.sleep(1.5)               # past the close
        return out

    system.server.push = slow_push
    win = driver.run_window(system, plan, 1.0)
    assert len(win.stamps) == len(plan) == 12
    assert all(s.due == s.plan.due_s < 1.0 for s in win.stamps)
    assert all(s.last is not None for s in win.stamps)
    assert win.late_s > 0.5
    assert min(measure.ttft_ms(win.stamps)[1:]) > 0


def test_a_token_altered_where_it_is_produced_fails(tiny_bench,
                                                    monkeypatch):
    from repro.serving import sampling
    orig = sampling.sample_token

    def altered(keys, logits, *a, **kw):
        tok = orig(keys, logits, *a, **kw)
        vocab = logits.shape[-1]
        return jnp.where(tok % 5 == 0, (tok + 1) % vocab, tok)

    monkeypatch.setattr(sampling, "sample_token", altered)
    res = runner.run_cell(tiny_bench, "tiny-mha.chat", seed=8,
                          seconds=2.0, trace=False, t_start=0.0,
                          require_tpu=False, cache=False)
    assert not res["correct"]
    for name in ("logit_gap", "mean_gap"):
        gap = res["checks"][name]
        assert gap["value"] > gap["limit"], name
