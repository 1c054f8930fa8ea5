"""Helpers of the benchmark's own tests (run on the CPU).

``tiny_root`` is a copy of the benchmark with two tiny configurations
(a GQA/RMSNorm and an MHA/LayerNorm/partial-rotary decoder), a tiny
open-loop and a tiny closed-loop mix and their four cells, all added as
new files and entries, the way a later change adds a cell.
``add_tiny_moe`` adds a tiny sparse-expert configuration with a
reference of its own, its chat cell and two metrics the same way.
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


# A sparse-expert configuration added the way a later change adds one:
# its file holds every size (the registry's granite-moe entry gives
# the rest), and its own reference beside it makes the weights, gives
# the logits and counts the work.  capacity_factor = n_experts / top_k
# lets each expert take every token of a group: the program drops none.
TINY_MOE = dict(TINY, program_arch="granite-moe-3b-a800m", n_kv_heads=2,
                norm="rmsnorm", norm_eps=1e-6, rope_pct=1.0,
                rope_theta=1e4, d_ff=64, n_experts=4, top_k=2,
                d_ff_expert=64, capacity_factor=2.0,
                reference="tiny_moe")

MOE_REFERENCE = '''"""Plain reference of a sparse-expert decoder, in
float32: the dense decoder's attention, then per layer a softmax
router over every expert, the top-k renormalised to sum to one, and
each chosen expert's SwiGLU weighted by it, with no token dropped."""
import functools
import os

import jax
import jax.numpy as jnp

from harness import spec, weights

dense = spec.reference(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "dense_decoder")
CFG_KEYS = dense.CFG_KEYS + ("n_experts", "top_k", "d_ff_expert")
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "vocab", "norm", "dtype", "n_experts", "d_ff_expert")


def layer_matmul_params(cfg):
    d, H, K, hd = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                   cfg["head_dim"])
    return (d * (H + 2 * K) * hd + H * hd * d + d * cfg["n_experts"]
            + cfg["top_k"] * 3 * d * cfg["d_ff_expert"])


def attn_pair_flops(cfg):
    return 4 * cfg["n_heads"] * cfg["head_dim"]


@functools.partial(jax.jit, static_argnums=0)
def _make(frozen, key):
    cfg = dict(frozen)
    d, V, L = cfg["d_model"], cfg["vocab"], cfg["n_layers"]
    E, f = cfg["n_experts"], cfg["d_ff_expert"]
    dt = jnp.dtype(cfg["dtype"])

    def layer(k):
        # the router's logits spread ~4 (fan-in d / 16), as a trained
        # router's do: at unit spread some tokens sit within 1e-3 of a
        # tie between the k-th and the next expert, which the served
        # bf16 activations break the other way
        ks = jax.random.split(k, 8)
        moe = {"router": weights.normal(ks[4], (d, E), d / 16, jnp.float32),
               "w_gate": weights.normal(ks[5], (E, d, f), d, dt),
               "w_up": weights.normal(ks[6], (E, d, f), d, dt),
               "w_down": weights.normal(ks[7], (E, f, d), f, dt)}
        return {"mix": dense.attention_params(cfg, ks[:4]), "moe": moe}

    k_emb, k_un, k_layers = jax.random.split(key, 3)
    layers = jax.lax.map(layer, jax.random.split(k_layers, L))
    layers["norm1"] = weights.norm(cfg["norm"], d, L)
    layers["norm2"] = weights.norm(cfg["norm"], d, L)
    return {"emb": weights.normal(k_emb, (V, d), d, dt),
            "unemb": weights.normal(k_un, (d, V), d, dt),
            "final_norm": weights.norm(cfg["norm"], d),
            "layers": layers}


def make_params(cfg, key):
    return _make(tuple((k, cfg[k]) for k in MODEL_KEYS), key)


def _experts(cfg, p, x, quant):
    gates = jax.nn.softmax(x @ p["router"], axis=-1)
    w, idx = jax.lax.top_k(gates, cfg["top_k"])
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(cfg["n_experts"]):
        g = dense.linear(x, p["w_gate"][e], quant)
        u = dense.linear(x, p["w_up"][e], quant)
        y = dense.linear(jax.nn.silu(g) * u, p["w_down"][e], quant)
        out = out + jnp.sum(jnp.where(idx == e, w, 0.0), -1)[..., None] * y
    return out


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _logits(frozen, params, tokens, start, quant):
    cfg = dict(frozen)
    h = params["emb"][tokens].astype(jnp.float32)

    def layer(h, lp):
        h = h + dense.attention(cfg, lp["mix"],
                                dense.apply_norm(cfg, lp["norm1"], h), quant)
        h = h + _experts(cfg, lp["moe"],
                         dense.apply_norm(cfg, lp["norm2"], h), quant)
        return h, None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    h = dense.apply_norm(cfg, params["final_norm"], h[:, start:])
    return dense.linear(h, params["unemb"], quant)


def logits(cfg, params, tokens, start, quant=None):
    frozen = tuple((k, cfg[k]) for k in CFG_KEYS)
    with jax.default_matmul_precision("highest"):
        return _logits(frozen, params, tokens, start, quant)
'''

# two per-layer metrics a later change brings with such a mechanism:
# one reads a counter of the decode session beyond the four the
# benchmark's own metrics read, one a span under a prefix no harness
# file names
MOE_METRICS = {
    "sched.blocks_allocated": (
        "def read(ctx):\n"
        "    n = ctx.counter_delta('blocks_allocated')\n"
        "    return n or None\n",
        {"unit": "blocks", "better": "lower", "source": "program_counter",
         "layer": "scheduler"}),
    "moe.route_ms": (
        "def read(ctx):\n"
        "    if ctx.trace is None:\n"
        "        return None\n"
        "    ns = sum(e.end - e.start for e in ctx.trace.spans\n"
        "             if e.name.startswith('moe.'))\n"
        "    return ns / 1e6 or None\n",
        {"unit": "ms", "better": "lower", "source": "program_span",
         "layer": "experts"}),
}
MOE_CELL = "tiny-moe.chat"


def add_tiny_moe(root: str) -> str:
    """Add the sparse-expert configuration, its reference, its chat cell
    and its two metrics to a copy that ``add_tiny`` has filled, as new
    files plus new entries; returns the cell's name."""
    bpath = os.path.join(root, "BENCHMARK.json")
    with open(bpath) as f:
        bench = json.load(f)
    pb = os.path.join(root, "perfbench")
    with open(os.path.join(pb, "configs", "stablelm-3b.json")) as f:
        cfg = dict(json.load(f), name="tiny-moe", reduced=[], **TINY_MOE)
    with open(os.path.join(pb, "configs", "tiny-moe.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(pb, "configs", "tiny_moe.py"), "w") as f:
        f.write(MOE_REFERENCE)
    with open(os.path.join(pb, "workloads", MOE_CELL + ".json"), "w") as f:
        json.dump(TINY_CELL, f)
    bench["configs"].append({"name": "tiny-moe", "source": "test",
                             "file": "perfbench/configs/tiny-moe.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": MOE_CELL, "config": "tiny-moe",
                               "traffic": "tiny-chat", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-gqa.chat" in m.get("workloads", ()):
            m["workloads"].append(MOE_CELL)
    for name, (src, entry) in MOE_METRICS.items():
        with open(os.path.join(pb, "metrics", name + ".py"), "w") as f:
            f.write(src)
        bench["per_layer"].append(dict(entry, name=name,
                                       moves="tpot_p50_ms",
                                       workloads=[MOE_CELL]))
    with open(bpath, "w") as f:
        json.dump(bench, f, indent=1)
    return MOE_CELL
