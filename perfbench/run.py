"""Run one cell of the benchmark once, on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  The cells, configurations, traffic mixes
and metrics are named in ``BENCHMARK.json`` and found as files under
this directory.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` with ``--trace 1``, and ``checks``: each number compared
with its limit); the checks are also the last lines of standard error.
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
same window.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, and when any step fails.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import runner, spec
    try:
        bench = spec.load(ROOT)
        chips = bench.cell(args.workload).chips
        runner.device_info(chips)          # before anything is built
        result = runner.run_cell(bench, args.workload, args.seed,
                                 args.seconds, bool(args.trace),
                                 t_start=T_START)
    except (runner.DeviceError, spec.SpecError) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
