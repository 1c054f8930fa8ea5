"""Find an open-loop cell's knee: serve it at a list of fixed rates.

    python3 perfbench/sweep.py --workload <cell> --rates 3,4,5,6 \
        --seconds 20 [--seed 1]

One process, one set-up: the weights and programs are made once, then
each rate gets a fresh server and a window of ``--seconds`` of the
cell's own traffic generator.  For each rate it prints the backlog
(requests due but not yet answered: unsent, queued or in flight) at
the middle of the window and at its close, how late the sender ran,
how long the drain took, and the latency tails.  The knee is the
highest rate whose backlog does not grow over the window; a cell then
runs at a fixed rate below it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from harness import driver, measure, runner, spec, traffic, weights
    bench = spec.load(ROOT)
    cell = bench.cell(args.workload)
    runner.device_info(cell.chips)
    runner.enable_compile_cache(ROOT)
    params = weights.make_params(cell.config, args.seed)
    jax.block_until_ready(params)
    system = driver.System(cell, params)
    del params
    system.warm_up()
    for rate in (float(r) for r in args.rates.split(",")):
        plan = traffic.plan(cell.traffic, args.seconds, args.seed,
                            int(cell.config["vocab"]), rate_qps=rate)
        t0 = time.perf_counter()
        # past the knee the backlog never drains: give up 15 s on
        win = driver.run_window(system, plan, args.seconds,
                                drain_limit_s=15.0)

        def backlog(t: float) -> int:
            return sum(1 for s in win.stamps if s.due <= t and
                       (s.last is None or s.last > t))
        print(json.dumps({
            "rate_qps": rate, "sent": len(win.stamps),
            "backlog_at_half": backlog(args.seconds / 2),
            "backlog_at_close": backlog(win.end_s),
            "drain_s": win.drain_s - win.end_s,
            "unanswered": sum(1 for s in win.stamps if s.last is None),
            "queue_mean": win.marks["close"].queue_area / win.end_s,
            **measure.latency(win.stamps),
            "late_s": win.late_s, "wall_s": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
