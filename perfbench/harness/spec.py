"""The benchmark's data, found by name.

Everything that belongs to one cell, one model configuration, one
traffic mix or one per-layer metric is a file of its own under the
benchmark's directory, and this module finds it by the name that
``BENCHMARK.json`` gives:

    configs/<config>.json         sizes of the model as it is run
    configs/<reference>.py        its plain float32 reference
    traffic/<traffic>.json        parameters of one traffic mix
    workloads/<cell>.json         the serving set-up, fixed rate and
                                  correctness limits of one cell
    metrics/<metric>.py           the reader of one per-layer metric

Adding a cell, a configuration, a mix or a metric therefore means
adding files and entries, never editing a file that is there.

A configuration of any architecture the program serves enters so.
Its file holds every size as the program's ``ModelConfig`` names it
(``harness.driver.model_config`` takes each such key, lists as
tuples; the registry entry named by ``program_arch`` gives only what
the file leaves out), and names its reference module, which defines:

    logits(cfg, params, tokens, start, quant=None)
        next-token logits [B, S - start, vocab] in float32 at the
        highest matmul precision; ``quant="int8"`` is the control
    make_params(cfg, key)
        the weights for a PRNG key, in the program's tree of this
        architecture, bf16 where served and norms in float32, on the
        device in one jitted call

and may define the work one token needs, which ``harness.flops``
takes in place of the dense decoder's formulas:

    layer_matmul_params(cfg)
        weights of the linear maps one token touches in one layer
    attn_pair_flops(cfg)
        operations per (query, key) pair in one layer

A per-layer metric reads what :class:`harness.measure.Context` holds:
the window's stamps, every integer counter of the decode session at
the span's ends, and with a trace the device's operations and
programs, the benchmark's ``bench.*`` annotations and the program's
own spans under any prefix (``harness.trace``).
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import os
import re
import sys
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)                 # .../perfbench
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_E2E = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SpecError(what)


def _load_json(path: str) -> dict:
    _check(os.path.isfile(path), f"missing file {path}")
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    """One entry of ``workloads`` with every file it names resolved."""
    name: str
    chips: int
    config: dict                  # configs/<config>.json
    traffic: dict                 # traffic/<traffic>.json
    setup: dict                   # workloads/<cell>.json
    end_to_end: list              # metric entries this cell reports
    per_layer: list
    bench_dir: str = BENCH_DIR

    @property
    def limits(self) -> dict:
        return self.setup["limits"]


@dataclass
class Benchmark:
    root: str                     # directory holding BENCHMARK.json
    spec: dict
    bench_dir: str
    cells: dict = field(default_factory=dict)

    def cell(self, name: str) -> Cell:
        if name not in self.cells:
            raise SpecError(f"unknown workload {name!r}; the benchmark "
                            f"has {sorted(self.cells)}")
        return self.cells[name]


def _reports(metric: dict, cell: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or cell in wl


def load(root: str, bench_dir: str | None = None) -> Benchmark:
    """Read ``<root>/BENCHMARK.json`` and every file its entries name;
    raise :class:`SpecError` on the first thing out of place."""
    spec = _load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = bench_dir or os.path.join(root, spec["paths"][0])
    e2e, per_layer = spec["end_to_end"], spec["per_layer"]
    names = [m["name"] for m in e2e + per_layer]
    names += [c["name"] for c in spec["configs"]]
    names += [w["name"] for w in spec["workloads"]]
    for n in names:
        _check(bool(NAME_RE.match(n)), f"bad name {n!r}")
    for m in e2e + per_layer:
        _check(bool(UNIT_RE.match(m["unit"])), f"bad unit {m['unit']!r}")
        _check(m["better"] in ("lower", "higher"),
               f"{m['name']}: better must be lower or higher")
    for m in e2e:
        _check(m["source"] in SOURCES_E2E,
               f"{m['name']}: end-to-end source {m['source']!r}")
    for m in per_layer:
        _check(m["source"] in SOURCES, f"{m['name']}: source")
        _check(any(x["name"] == m["moves"] for x in e2e),
               f"{m['name']} moves unknown metric {m['moves']!r}")
        _check(os.path.isfile(metric_file(bench_dir, m["name"])),
               f"{m['name']}: no reader {metric_file(bench_dir, m['name'])}")
    _check("setup_s" in [m["name"] for m in e2e], "setup_s is required")
    configs = {}
    for c in spec["configs"]:
        path = os.path.join(root, c["file"])
        cfg = _load_json(path)
        _check(cfg.get("name") == c["name"],
               f"{path}: name {cfg.get('name')!r} != {c['name']!r}")
        _check(sorted(cfg.get("reduced", [])) == sorted(c["reduced"]),
               f"{path}: reduced differs from BENCHMARK.json")
        ref = os.path.join(bench_dir, "configs", cfg["reference"] + ".py")
        _check(os.path.isfile(ref), f"{path}: no reference {ref}")
        configs[c["name"]] = cfg
    bench = Benchmark(root=root, spec=spec, bench_dir=bench_dir)
    for w in spec["workloads"]:
        name = w["name"]
        _check(w["config"] in configs, f"{name}: unknown config")
        _check(w["chips"] in (1, 4), f"{name}: chips must be 1 or 4")
        traffic = _load_json(os.path.join(bench_dir, "traffic",
                                          w["traffic"] + ".json"))
        setup = _load_json(os.path.join(bench_dir, "workloads",
                                        name + ".json"))
        cell_e2e = [m for m in e2e if _reports(m, name)]
        cell_pl = [m for m in per_layer if _reports(m, name)]
        reported = {m["name"] for m in cell_e2e}
        _check("setup_s" in reported and len(reported) >= 2,
               f"{name}: reports setup_s and at least one more metric")
        _check(bool(cell_pl), f"{name}: reports no per-layer metric")
        for m in cell_pl:
            _check(m["moves"] in reported,
                   f"{name}: {m['name']} moves {m['moves']}, which the "
                   f"cell does not report")
        bench.cells[name] = Cell(name=name, chips=w["chips"],
                                 config=configs[w["config"]],
                                 traffic=traffic, setup=setup,
                                 end_to_end=cell_e2e, per_layer=cell_pl,
                                 bench_dir=bench_dir)
    return bench


def metric_file(bench_dir: str, name: str) -> str:
    return os.path.join(bench_dir, "metrics", name + ".py")


def _load_module(path: str, kind: str):
    """The module at ``path``, executed once per process: a later call
    with the same path gets the same module, so its jitted functions
    keep their caches."""
    path = os.path.abspath(path)
    modname = (f"perfbench_{kind}_"
               + hashlib.sha256(path.encode()).hexdigest()[:16])
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[modname] = mod
    return sys.modules[modname]


def metric_reader(bench_dir: str, name: str):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return _load_module(metric_file(bench_dir, name), "metric")


def reference(bench_dir: str, name: str):
    """The reference module ``configs/<name>.py`` under ``bench_dir``;
    one that lacks ``logits`` or ``make_params`` is refused."""
    mod = _load_module(os.path.join(bench_dir, "configs", name + ".py"),
                       "ref")
    for fn in ("logits", "make_params"):
        _check(callable(getattr(mod, fn, None)),
               f"reference {name} defines no {fn}")
    return mod


def reference_module(cell: Cell):
    """The plain reference named by the cell's configuration."""
    return reference(cell.bench_dir, cell.config["reference"])
