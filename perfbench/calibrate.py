"""Readings that a cell's correctness limit is set from.

    python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 10

Runs the cell once per seed in one process (the window at the cell's
own load, then the comparison with the plain reference), and on the
control seeds also reads the int8 control on the same prompts and
served tokens, and judges its readings by the cell's own limits, as a
run judges the program's.  Prints one JSON line per seed and, last,
for every reading the lower reading (the largest the program gives
over the seeds), the upper reading (the smallest the control gives)
and whether the control came out as not correct on every control
seed.  Each limit in ``workloads/<cell>.json`` is set between its two
readings; the benchmark's own runs never run the control.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import check, runner, spec
    bench = spec.load(ROOT)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    prog, ctl, ctl_correct = [], [], []
    for seed in seeds:
        t0 = time.perf_counter()
        res = runner.run_cell(bench, args.workload, seed, args.seconds,
                              False, t_start=t0, control=seed in ctl_seeds)
        row = {"seed": seed, "correct": res["correct"],
               "failed": res["failed"], "attempted": res["attempted"],
               "seconds": time.perf_counter() - t0, **res["readings"]}
        prog.append(res["readings"])
        if "control" in res:
            c = res["control"]
            row["control_correct"] = c["correct"]
            row.update({"control_" + k: v
                        for k, v in c["readings"].items()})
            ctl.append(c["readings"])
            ctl_correct.append(c["correct"])
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload,
               "limits": bench.cell(args.workload).limits,
               "control_never_correct": bool(ctl_correct)
               and not any(ctl_correct)}
    for k in check.READINGS:
        summary[k] = {"lower": max(r[k] for r in prog),
                      "upper": min(r[k] for r in ctl) if ctl else None}
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
