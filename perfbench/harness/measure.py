"""From one run's stamps, counters and trace to the metrics it prints.

End-to-end metrics come from the benchmark's own clock; per-layer
metrics come from the readers in ``metrics/<name>.py``, each handed a
:class:`Context`.  A reader returns a number or ``None`` (nothing to
read in this run), and ``None`` leaves the metric out of the line.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

from harness import flops, spec
from harness.traffic import percentile


def load_peaks(bench_dir: str, device_kind: str) -> dict:
    """The chip's published peaks; a chip not in the table is an
    error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"peaks.json ({sorted(table)})")
    return table[device_kind]


def completed(stamps) -> list:
    return [s for s in stamps if s.last is not None]


def ttft_ms(stamps) -> list[float]:
    return [1000.0 * (s.first - s.due) for s in completed(stamps)]


def tpot_ms(stamps) -> list[float]:
    out = []
    for s in completed(stamps):
        n = len(s.tokens)
        if n > 1:
            out.append(1000.0 * (s.last - s.first) / (n - 1))
    return out


def latency(stamps) -> dict:
    """The 50th, 90th and 95th percentiles of TTFT and TPOT over every
    request due in the window, by name (``ttft_p90_ms``, ...)."""
    out = {}
    for name, xs in (("ttft", ttft_ms(stamps)), ("tpot", tpot_ms(stamps))):
        if xs:
            for q in (50, 90, 95):
                out[f"{name}_p{q}_ms"] = percentile(xs, q)
    return out


def end_to_end(cell, win, setup_s: float) -> dict:
    """Every end-to-end metric the cell reports, by name."""
    vals = dict(latency(win.stamps), setup_s=setup_s,
                out_tok_s=win.tokens_in_window / win.end_s)
    out = {}
    for m in cell.end_to_end:
        if m["name"] in vals:
            out[m["name"]] = {"value": float(vals[m["name"]]),
                              "unit": m["unit"]}
    return out


@dataclass
class Context:
    """What a per-layer reader may read."""
    cell: object                  # spec.Cell
    win: object                   # driver.WindowResult
    trace: object | None          # trace.Trace (traced runs only)
    device_kind: str
    slots: int
    sync_every: int

    @property
    def peaks(self) -> dict:
        return load_peaks(self.cell.bench_dir, self.device_kind)

    @property
    def cfg(self) -> dict:
        return self.cell.config

    @property
    def plen(self) -> int:
        return int(self.cell.traffic["prompt_len"])

    def _span(self):
        return self.win.span()

    @property
    def span_s(self) -> float:
        """Length of the traced span (the whole window untraced)."""
        a, b = self._span()
        return b.t - a.t

    def decode_contexts(self) -> np.ndarray:
        """Context of every decode step whose token became visible in
        the span (one entry per token, not per step)."""
        a, b = self._span()
        parts = []
        for s in self.win.stamps:
            lo, hi = a.seen.get(s.plan.rid, 0), b.seen.get(s.plan.rid, 0)
            if hi > max(lo, 1):
                parts.append(flops.decode_contexts(self.plen, hi, lo))
        return np.concatenate(parts) if parts else np.zeros(0, np.int64)

    def prefilled(self) -> int:
        """Requests whose first token became visible in the span."""
        a, b = self._span()
        return sum(1 for s in self.win.stamps
                   if a.seen.get(s.plan.rid, 0) == 0
                   and b.seen.get(s.plan.rid, 0) >= 1)

    def counter_delta(self, key: str) -> int:
        a, b = self._span()
        return b.counters[key] - a.counters[key]


def per_layer(cell, ctx: Context) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = spec.metric_reader(cell.bench_dir, m["name"])
        v = mod.read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
