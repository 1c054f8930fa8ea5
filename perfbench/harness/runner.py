"""One run of one cell: set up, measure, check, report."""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

from harness import check, driver, measure, spec, traffic
from harness import trace as tracemod
from harness import weights


class DeviceError(RuntimeError):
    pass


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """The devices as JAX reports them; no TPU, or fewer chips than the
    cell asks for, is an error raised before anything is built."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if require_tpu and d0.platform != "tpu":
        raise DeviceError(
            f"JAX found platform {d0.platform!r} ({d0.device_kind}), not "
            f"a TPU; the benchmark measures the chip only")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips; JAX found "
                          f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when set, else at the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def peak_memory_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    v = stats.get("peak_bytes_in_use")
    return None if v is None else int(v)


def run_cell(bench: spec.Benchmark, name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_tpu: bool = True,
             control: bool = False, cache: bool = True,
             log=sys.stderr) -> dict:
    """Run cell ``name`` once; returns the result line's dict.

    ``require_tpu=False`` and ``cache=False`` serve the CPU tests: the
    first skips the look for a chip, the second leaves JAX's
    persistent compilation cache as the caller set it.  ``control``
    also reads the int8 control's gap on the same sample."""
    cell = bench.cell(name)
    device = device_info(cell.chips, require_tpu)
    import jax
    if cache:
        enable_compile_cache(bench.root)
    plan = traffic.plan(cell.traffic, seconds, seed,
                        int(cell.config["vocab"]),
                        rate_qps=cell.setup.get("rate_qps"))
    params = weights.make_params(cell.config, seed, cell.bench_dir)
    jax.block_until_ready(params)
    system = driver.System(cell, params)
    del params
    system.warm_up()
    setup_s = time.perf_counter() - t_start
    print(f"set-up {setup_s:.2f}s; {len(plan)} requests planned",
          file=log, flush=True)

    tdir, ann, span = None, None, None
    if trace:
        tdir = tempfile.mkdtemp(prefix="perfbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        t_on, t_off = cell.setup.get("trace_span_s", (0.0, seconds))
        span = (min(t_on, seconds), min(t_off, seconds),
                lambda: jax.profiler.start_trace(
                    tdir, profiler_options=opts),
                jax.profiler.stop_trace)
        ann = jax.profiler.TraceAnnotation
    try:
        win = driver.run_window(system, plan, seconds, annotate=ann,
                                trace=span)
        tr = None
        if trace:
            tr = tracemod.load(tracemod.find_xplane(tdir))
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    if win.compiles:
        print(f"warning: {win.compiles} compiles inside the window",
              file=log, flush=True)
    device["memory_peak_bytes"] = peak_memory_bytes()
    slots, sync_every = system.engine.n_slots, system.engine.sync_every
    vocab = system.cfg.vocab
    system.close()
    del system

    attempted = len(win.stamps)
    unanswered, malformed = check.answer_faults(cell, win.stamps, vocab)
    picked = check.sample(win.stamps, seed,
                          int(cell.setup["check"]["tokens"]))
    cmp = check.compare(cell, seed, picked, control=control)
    checks, correct = check.judge(cmp, cell.limits,
                                  unanswered=unanswered,
                                  malformed=malformed)
    if trace:
        ctx = measure.Context(cell=cell, win=win, trace=tr,
                              device_kind=device["kind"], slots=slots,
                              sync_every=sync_every)
        metrics = measure.per_layer(cell, ctx)
        device["busy_s"] = tracemod.busy_s(tr)
        device["window_s"] = tr.window_s
    else:
        metrics = measure.end_to_end(cell, win, setup_s)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": unanswered + malformed, "metrics": metrics,
              "device": device}
    if trace:
        result["breakdown"] = tracemod.breakdown(tr)
    info = measure.latency(win.stamps)
    info.update({k: cmp[k] for k in check.READINGS})
    info.update(requests_compared=cmp["requests"],
                tokens_compared=cmp["tokens"], window_end_s=win.end_s,
                drain_s=win.drain_s, calls=win.calls,
                compiles_in_window=win.compiles,
                generator_late_s=win.late_s)
    if control:
        info.update({k: v for k, v in cmp.items()
                     if k.startswith("control_")})
    print("run: " + json.dumps(info), file=log, flush=True)
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})", file=log,
              flush=True)
    if control:
        ctl_checks, ctl_correct = check.judge(cmp, cell.limits,
                                              prefix="control_")
        result["control"] = {"correct": ctl_correct,
                             "checks": ctl_checks,
                             "readings": {k: cmp["control_" + k]
                                          for k in check.READINGS}}
    result["readings"] = {k: cmp[k] for k in check.READINGS}
    result["checks"] = checks
    return result
