"""Helpers of the benchmark's own tests (run on the CPU).

``tiny_root`` is a copy of the benchmark with two tiny configurations
(a GQA/RMSNorm and an MHA/LayerNorm/partial-rotary decoder), a tiny
open-loop and a tiny closed-loop mix and their four cells, all added as
new files and entries, the way a later change adds a cell.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "perfbench"))

TINY = {"n_layers": 2, "d_model": 128, "n_heads": 4, "head_dim": 32,
        "d_ff": 256, "vocab": 512}
TINY_CONFIGS = {
    "tiny-gqa": dict(TINY, program_arch="internlm2-20b", n_kv_heads=2,
                     norm="rmsnorm", norm_eps=1e-6, rope_pct=1.0,
                     rope_theta=1e6),
    "tiny-mha": dict(TINY, program_arch="stablelm-3b", n_kv_heads=4,
                     norm="layernorm", norm_eps=1e-5, rope_pct=0.25,
                     rope_theta=1e4),
}
TINY_TRAFFIC = {
    "tiny-chat": {"loop": "open", "prompt_len": 16,
                  "output": {"median": 8, "sigma": 0.6, "min": 2,
                             "max": 16}},
    "tiny-batch": {"loop": "closed", "clients": 6, "per_client": 16,
                   "prompt_len": 16,
                   "output": {"median": 8, "sigma": 0.6, "min": 2,
                              "max": 16}},
}
TINY_CELL = {"rate_qps": 8.0, "slots": 4, "max_seq": 64,
             "kv_block_size": 16, "sync_every": 4,
             "check": {"tokens": 48, "rows_per_block": 2},
             "limits": {"logit_gap": 0.1, "mean_gap": 0.002}}


def copy_benchmark(dst: str) -> str:
    """A copy of BENCHMARK.json and the benchmark's directory."""
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    os.path.join(dst, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def add_tiny(root: str) -> list[str]:
    """Add the tiny configurations, mixes and cells to the copy at
    ``root`` as new files plus new entries; returns the cell names."""
    bpath = os.path.join(root, "BENCHMARK.json")
    with open(bpath) as f:
        bench = json.load(f)
    pb = os.path.join(root, "perfbench")
    base = os.path.join(pb, "configs", "stablelm-3b.json")
    with open(base) as f:
        base_cfg = json.load(f)
    for name, over in TINY_CONFIGS.items():
        cfg = dict(base_cfg, name=name, reduced=[], **over)
        with open(os.path.join(pb, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"perfbench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    for name, tr in TINY_TRAFFIC.items():
        with open(os.path.join(pb, "traffic", name + ".json"), "w") as f:
            json.dump(tr, f)
    cells = []
    for cfg in TINY_CONFIGS:
        for tr in TINY_TRAFFIC:
            cell = f"{cfg}.{tr[5:]}"
            with open(os.path.join(pb, "workloads", cell + ".json"),
                      "w") as f:
                json.dump(TINY_CELL, f)
            bench["workloads"].append({"name": cell, "config": cfg,
                                       "traffic": tr, "chips": 1,
                                       "why": "test"})
            cells.append(cell)
    # metrics scoped to cells: a tiny cell reports what the cells of
    # its kind (open or closed loop) report
    for m in bench["end_to_end"] + bench["per_layer"]:
        wl = m.get("workloads")
        if wl is not None:
            m["workloads"] = wl + [
                c for c in cells
                if any(w.endswith(c[c.index("."):]) for w in wl)]
    with open(bpath, "w") as f:
        json.dump(bench, f, indent=1)
    return cells
