"""BENCHMARK.json and the files it names: every cell, configuration,
mix and metric loads; names, units and keys keep to the benchmark's
rules; a cell reports what its per-layer metrics move; and adding a
cell, a configuration, a mix or a metric takes only new files."""
import hashlib
import json
import os
import re

import pytest
from perfbench_testlib import (MOE_CELL, REPO, add_tiny, add_tiny_moe,
                               copy_benchmark)

from harness import check, runner, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_WORDS = ("d_model", "hidden", "d_ff", "intermediate", "head",
               "_dim", "_rank", "latent", "state", "expand", "top_k",
               "vocab")


@pytest.fixture(scope="module")
def bench_json():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_loads_every_named_file():
    bench = spec.load(REPO)
    assert set(bench.cells) == {"stablelm-3b.chat", "stablelm-3b.batch",
                                "internlm2-20b-stage8.docqa"}
    for cell in bench.cells.values():
        assert cell.chips == 1
        assert spec.reference_module(cell).logits
        for m in cell.per_layer:
            assert callable(spec.metric_reader(cell.bench_dir,
                                               m["name"]).read)


def test_keys_names_and_units(bench_json):
    b = bench_json
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    assert len(b["command"]) <= 32
    for p in b["paths"]:
        assert re.match(r"^[A-Za-z0-9_./\-]{1,200}$", p)
        assert not p.startswith("/") and ".." not in p
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(b["paths"][0] + "/")
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not any(w in k for w in WIDTH_WORDS), k
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
    for m in b["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_what_its_layers_move(bench_json):
    b = bench_json
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for w in b["workloads"]:
        cell = w["name"]
        reported = {n for n, m in e2e.items()
                    if cell in m.get("workloads", [cell])}
        assert "setup_s" in reported and len(reported) >= 2
        layers = [m for m in b["per_layer"]
                  if cell in m.get("workloads", [cell])]
        assert layers
        for m in layers:
            assert m["moves"] in reported, (cell, m["name"])
    # a kernel's roofline is reported beside the whole step's share of
    # the peak, moving the same metric
    for m in b["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in x["name"] and x["moves"] == m["moves"]
                       for x in b["per_layer"])


def test_configuration_files_hold_the_run(bench_json):
    for c in bench_json["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        for k in ("n_layers", "d_model", "n_heads", "n_kv_heads",
                  "head_dim", "d_ff", "vocab", "dtype"):
            assert k in cfg


@pytest.mark.parametrize("name", ["stablelm-3b.chat",
                                  "internlm2-20b-stage8.docqa"])
def test_model_config_is_the_same_for_the_dense_files(name):
    """Every size from the file gives the ``ModelConfig`` the fourteen
    keys copied before gave, for both dense configurations."""
    from harness import driver
    from repro.configs import get_config
    cell = spec.load(REPO).cell(name)
    c = cell.config
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "rope_pct", "rope_theta", "norm", "act",
            "qkv_bias", "tie_embeddings", "dtype")
    before = get_config(c["program_arch"]).replace(
        **{k: c[k] for k in keys},
        kv_block_size=int(cell.setup["kv_block_size"]), draft_layers=0,
        temperature=0.0, remat=False)
    assert driver.model_config(cell) == before


def test_model_config_takes_the_sparse_sizes_from_the_file(added):
    """The sparse-expert file's sizes are served, not the registry's
    (40 experts, top-8, tied embeddings); a list becomes a tuple."""
    from harness import driver
    root, _, _ = added
    cell = spec.load(root).cell(MOE_CELL)
    cfg = driver.model_config(cell)
    assert (cfg.family, cfg.n_experts, cfg.top_k, cfg.d_ff_expert,
            cfg.capacity_factor, cfg.tie_embeddings) == (
        "moe", 4, 2, 64, 2.0, False)
    assert cfg.capacity_factor >= cfg.n_experts / cfg.top_k
    listed = spec.Cell(**{**cell.__dict__, "config": dict(
        cell.config, layer_pattern=["attn"])})
    assert driver.model_config(listed).layer_pattern == ("attn",)


def test_cells_hold_a_rate_and_known_limits():
    """Every open-loop cell runs Poisson traffic at a fixed rate, every
    closed-loop one a fixed number of callers, and each holds at least
    one of the comparison's readings to a limit."""
    from harness import check
    bench = spec.load(REPO)
    for name, cell in bench.cells.items():
        if cell.traffic["loop"] == "open":
            assert cell.traffic["arrivals"] in (
                "poisson", "poisson_quantiles"), name
            assert float(cell.setup["rate_qps"]) > 0, name
        else:
            assert cell.traffic["loop"] == "closed", name
            assert int(cell.traffic["clients"]) > cell.setup["slots"]
        assert cell.limits and set(cell.limits) <= set(check.READINGS)
        assert all(float(v) > 0 for v in cell.limits.values()), name


def _hashes(root: str) -> dict:
    out = {}
    for d, _, files in os.walk(os.path.join(root, "perfbench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _scope_harvest(monkeypatch):
    """The program opens a span under a prefix no harness file names:
    each window's harvest is scoped ``moe.route``."""
    from repro.serving import continuous
    harvest = continuous.DecodeSession._harvest

    def scoped(self, *a):
        with self.tracer.scope("moe.route"):
            return harvest(self, *a)

    monkeypatch.setattr(continuous.DecodeSession, "_harvest", scoped)


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A copy of the benchmark to which a later change has added, by
    new files and new entries alone, two dense configurations, a
    sparse-expert one with its own reference, two mixes, their cells
    and three per-layer metrics; with the hashes of the files that
    were there before."""
    root = copy_benchmark(str(tmp_path_factory.mktemp("added")))
    before = _hashes(root)
    cells = add_tiny(root)
    cells.append(add_tiny_moe(root))
    pb = os.path.join(root, "perfbench")
    with open(os.path.join(pb, "metrics", "sched.syncs_per_s.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    n = ctx.counter_delta('host_syncs')\n"
                "    return n / ctx.span_s if n else None\n")
    bpath = os.path.join(root, "BENCHMARK.json")
    with open(bpath) as f:
        b = json.load(f)
    b["per_layer"].append({"name": "sched.syncs_per_s", "unit": "1/s",
                           "better": "higher",
                           "source": "program_counter",
                           "layer": "scheduler", "moves": "tpot_p50_ms",
                           "workloads": ["tiny-gqa.chat"]})
    with open(bpath, "w") as f:
        json.dump(b, f)
    return root, before, cells


def test_adding_cells_configs_mixes_metrics_takes_only_new_files(
        added, monkeypatch):
    """A later change adds configurations, a mix, cells and per-layer
    metrics by new files and new entries alone, and the harness runs
    the new cells and reads the new metrics: a dense cell, and a
    sparse-expert cell whose weights, sizes and work come from its own
    reference and file, whose counter metric reads a counter none of
    the benchmark's metrics reads, and whose span metric reads a span
    under a new prefix."""
    root, before, cells = added
    after = _hashes(root)
    assert all(after[k] == v for k, v in before.items())
    bench = spec.load(root)
    assert set(cells) <= set(bench.cells)
    res = runner.run_cell(bench, "tiny-gqa.chat", seed=2**31 + 11,
                          seconds=2.0, trace=True, t_start=0.0,
                          require_tpu=False, cache=False)
    assert res["correct"], res["checks"]
    assert res["metrics"]["sched.syncs_per_s"]["value"] > 0
    # the device-trace readers find no chip programs in a CPU trace and
    # leave their metrics out of the line instead of reading 0
    assert set(res["metrics"]) == {"sched.syncs_per_s"}
    assert list(res)[-1] == "checks"

    _scope_harvest(monkeypatch)
    res = runner.run_cell(bench, MOE_CELL, seed=2**31 + 13, seconds=2.0,
                          trace=True, t_start=0.0, require_tpu=False,
                          cache=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 16 and res["failed"] == 0
    assert set(res["metrics"]) == {"sched.blocks_allocated",
                                   "moe.route_ms"}
    assert res["metrics"]["sched.blocks_allocated"]["value"] > 0
    assert res["metrics"]["moe.route_ms"]["value"] > 0


def test_an_added_architecture_fails_on_one_changed_token(added,
                                                          monkeypatch):
    """The sparse-expert cell's comparison sees one served token
    changed: the longest request's middle token, once the window has
    closed, before the reference reads it."""
    root, _, _ = added
    bench = spec.load(root)
    vocab = bench.cell(MOE_CELL).config["vocab"]
    sample = check.sample

    def changed(stamps, seed, target):
        picked = sample(stamps, seed, target)
        toks = picked[0].tokens
        k = len(toks) // 2
        toks[k] = (toks[k] + vocab // 2) % vocab
        return picked

    monkeypatch.setattr(check, "sample", changed)
    res = runner.run_cell(bench, MOE_CELL, seed=2**31 + 13, seconds=2.0,
                          trace=False, t_start=0.0, require_tpu=False,
                          cache=False)
    assert not res["correct"]
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
