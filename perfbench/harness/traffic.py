"""One general generator for every traffic mix.

A mix is a data file (``traffic/<name>.json``) of parameters; the cell
file sets the fixed rate it runs at.  Keys:

    loop            "open": arrivals on a schedule, sent whether or not
                    earlier requests were answered; "closed":
                    ``clients`` callers, each sending its next request
                    the moment its previous reply completes
    rate_qps        open loop: mean arrival rate (the cell may set it)
    clients         closed loop: number of callers, no think time
    per_client      closed loop: requests planned per caller, more
                    than any caller can finish in a window
    prompt_len      tokens in every prompt (one length per mix)
    output          {"median", "sigma", "min", "max"}: lognormal
                    output lengths, clipped

Arrivals are Poisson: the gaps come from ``poisson_arrivals``, a
verbatim copy of the program's sampler
(``repro.serving.workload.poisson_arrivals``) kept here so the
yardstick cannot move with the program.  A run of ``seconds`` at rate
``r`` holds ``n = round(r * seconds)`` arrivals: the copied sampler
draws ``n + 1`` arrival times and the plan scales them so the
``n + 1``-th falls at the close.  That is a Poisson process
conditioned on ``n`` arrivals in the window (its arrival times are
then uniform order statistics), so bursts and lulls come as Poisson
traffic brings them, while every seed sends the same amount of work.

Output lengths are the same set for every seed, the midpoint
quantiles of the clipped lognormal, in a seeded order over the whole
window; the seed also picks the prompts' tokens.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


def poisson_arrivals(n: int, rate_qps: float, *, seed: int = 0
                     ) -> np.ndarray:
    """Arrival times of ``n`` Poisson arrivals (copied sampler)."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_qps, size=n)
    return np.cumsum(gaps)


def window_arrivals(n: int, rate_qps: float, seconds: float,
                    seed) -> np.ndarray:
    """``n`` Poisson arrival times in ``[0, seconds)``: the copied
    sampler's first ``n`` of ``n + 1`` arrivals, scaled so the last
    falls at ``seconds``."""
    times = poisson_arrivals(n + 1, rate_qps, seed=seed)
    return times[:n] * (seconds / times[n])


def stratified_lognormal(n: int, median: float, sigma: float, lo: int,
                         hi: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` clipped lognormal integers at the midpoint quantiles, in a
    seeded order."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    vals = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi)
    return rng.permutation(vals.astype(np.int64))


@dataclass
class Planned:
    """One request as the generator plans it."""
    rid: int
    due_s: float | None          # seconds after the window opens; None
                                 # in a closed loop (due on a reply)
    prompt: np.ndarray           # [prompt_len] int32
    max_new: int
    client: int = -1             # closed loop: the caller that sends it


def plan(traffic: dict, seconds: float, seed: int, vocab: int,
         rate_qps: float | None = None) -> list[Planned]:
    """Every request a run of ``seconds`` may send, in plan order.

    Open loop: ``round(rate * seconds)`` Poisson arrivals inside the
    window.  Closed loop: ``per_client`` requests for each of the
    ``clients`` callers, dealt client by client (request ``i`` belongs
    to caller ``i % clients``), each due when its caller's previous
    reply completes."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0])
    plen = int(traffic["prompt_len"])
    o = traffic["output"]
    loop = traffic.get("loop", "open")
    if loop == "open":
        rate = float(rate_qps if rate_qps is not None
                     else traffic["rate_qps"])
        n = max(int(round(rate * seconds)), 1)
        due = list(window_arrivals(n, rate, seconds,
                                   [seed & 0xFFFFFFFF, seed >> 32, 1]))
        clients = [-1] * n
    elif loop == "closed":
        c = int(traffic["clients"])
        n = c * int(traffic["per_client"])
        due = [None] * n
        clients = [i % c for i in range(n)]
    else:
        raise ValueError(f"unknown loop {loop!r}")
    lens = stratified_lognormal(n, float(o["median"]), float(o["sigma"]),
                                int(o["min"]), int(o["max"]), rng)
    prompts = rng.integers(0, vocab, size=(n, plen), dtype=np.int64)
    return [Planned(rid=i,
                    due_s=None if due[i] is None else float(due[i]),
                    prompt=prompts[i].astype(np.int32),
                    max_new=int(lens[i]), client=clients[i])
            for i in range(n)]


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (``q`` in 0..100) of a non-empty
    sequence: the smallest value with at least ``q`` percent of the
    sample at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = max(int(math.ceil(q / 100.0 * len(xs))) - 1, 0)
    return float(xs[k])
