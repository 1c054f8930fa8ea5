"""The benchmark's FLOP and byte functions against counts worked out by
hand at both configurations' published widths, and the traffic
generator's seeded plan."""
import json
import os

import numpy as np
import pytest
from perfbench_testlib import MOE_CELL, REPO

from harness import flops, measure, spec, traffic


def _cfg(name):
    with open(os.path.join(REPO, "perfbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


# stablelm-3b: d 2560, 32 MHA heads of 80, d_ff 6912, vocab 50304
#   per layer: 2560*(32+2*32)*80 + 32*80*2560 + 3*2560*6912
#            = 19_660_800 + 6_553_600 + 53_084_160 = 79_298_560
# internlm2-20b: d 6144, 48 q / 8 kv heads of 128, d_ff 16384, vocab 92544
#   per layer: 6144*(48+16)*128 + 48*128*6144 + 3*6144*16384
#            = 50_331_648 + 37_748_736 + 301_989_888 = 390_070_272
HAND = {
    "stablelm-3b": dict(layer=79_298_560, head=128_778_240, L=32,
                        pair=4 * 32 * 80, kv_row=2 * 32 * 80 * 2,
                        q_io=2 * 32 * 80 * 2, heads=(32, 32, 80)),
    "internlm2-20b-stage8": dict(layer=390_070_272, head=568_590_336,
                                 L=8, pair=4 * 48 * 128,
                                 kv_row=2 * 8 * 128 * 2,
                                 q_io=2 * 48 * 128 * 2,
                                 heads=(48, 8, 128)),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_matmul_and_head_params(name):
    cfg, h = _cfg(name), HAND[name]
    assert flops.layer_matmul_params(cfg) == h["layer"]
    assert flops.head_params(cfg) == h["head"]
    assert flops.attn_pair_flops(cfg) == h["pair"]


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_and_prefill_flops(name):
    cfg, h = _cfg(name), HAND[name]
    L = h["L"]
    assert flops.decode_token_flops(cfg, 200) == (
        2 * (L * h["layer"] + h["head"]) + L * h["pair"] * 200)
    plen = 128
    assert flops.prefill_request_flops(cfg, plen) == (
        2 * L * h["layer"] * plen + L * h["pair"] * (128 * 129 // 2)
        + 2 * h["head"])


@pytest.mark.parametrize("name", sorted(HAND))
def test_the_dense_reference_keeps_the_dense_counts(name):
    """The dense reference defines no counts of its own: the counts
    the mfu readers take through it are the hand counts above."""
    cfg, h = _cfg(name), HAND[name]
    ref = spec.reference(os.path.join(REPO, "perfbench"), "dense_decoder")
    assert not hasattr(ref, "layer_matmul_params")
    assert not hasattr(ref, "attn_pair_flops")
    L = h["L"]
    ctxs = np.array([129, 200, 383])
    assert list(flops.decode_token_flops(cfg, ctxs, ref)) == [
        2 * (L * h["layer"] + h["head"]) + L * h["pair"] * c for c in ctxs]
    assert flops.prefill_request_flops(cfg, 2048, ref) == (
        flops.prefill_request_flops(cfg, 2048))


def test_a_reference_gives_its_own_counts(moe_root):
    """A sparse-expert reference counts top-k of the experts held and
    the router; the attention's pairs stay as they are."""
    cell = spec.load(moe_root).cell(MOE_CELL)
    cfg, ref = cell.config, spec.reference_module(cell)
    d, E, k, f = 128, 4, 2, 64
    layer = d * (4 + 2 * 2) * 32 + 4 * 32 * d + d * E + k * 3 * d * f
    assert ref.layer_matmul_params(cfg) == layer
    assert flops.decode_token_flops(cfg, 20, ref) == (
        2 * (2 * layer + d * 512) + 2 * 4 * 4 * 32 * 20)
    assert flops.prefill_request_flops(cfg, 16, ref) == (
        2 * 2 * layer * 16 + 2 * 4 * 4 * 32 * (16 * 17 // 2)
        + 2 * d * 512)
    # the mfu readers take the work through the cell's reference
    ctx = measure.Context(cell=cell, win=None, trace=None, device_kind="",
                          slots=4, sync_every=4)
    assert ctx.reference is ref


def test_published_sizes():
    s, i = _cfg("stablelm-3b"), _cfg("internlm2-20b-stage8")
    s_params = 32 * HAND["stablelm-3b"]["layer"] + 2 * 128_778_240
    assert abs(s_params - 2.795e9) < 1e6          # 2.80 B, 5.59 GB bf16
    i_params = 8 * 390_070_272 + 2 * 568_590_336
    assert abs(i_params - 4.258e9) < 1e6          # 8.52 GB bf16
    # a 2048-token internlm2 stage prefill is ~13.2 TFLOP (issue's count
    # of the 8 layers and head)
    assert 13.0e12 < flops.prefill_request_flops(i, 2048) < 13.5e12
    assert flops.itemsize(s) == 2


@pytest.mark.parametrize("name", sorted(HAND))
def test_kernel_flops_and_bytes(name):
    cfg, h = _cfg(name), HAND[name]
    f, b = flops.paged_decode_kernel(cfg, 300)
    assert f == h["pair"] * 300
    assert b == 300 * h["kv_row"] + h["q_io"]
    H, K, hd = h["heads"]
    f, b = flops.flash_prefill_kernel(cfg, 2048)
    assert f == h["pair"] * (2048 * 2049 // 2)
    assert b == 2048 * (2 * H + 2 * K) * hd * 2


def test_roofline_takes_the_binding_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(50.0, 1.0, peak) == 0.5      # compute-bound
    assert flops.roofline_s(50.0, 20.0, peak) == 2.0     # memory-bound


def test_decode_contexts():
    # prompt 128, 4 tokens: token 0 from prefill, tokens 1..3 decoded
    # with contexts 129, 130, 131
    assert list(flops.decode_contexts(128, 4)) == [129, 130, 131]
    assert list(flops.decode_contexts(128, 1)) == []


def test_poisson_sampler_is_the_programs():
    from repro.serving import workload
    got = traffic.poisson_arrivals(50, 4.0, seed=9)
    want = [r.arrival_s for r in workload.poisson_arrivals(50, 4.0,
                                                           seed=9)]
    assert np.allclose(got, want)


def test_plan_same_sizes_for_every_seed():
    t = {"loop": "open", "prompt_len": 16,
         "output": {"median": 64, "sigma": 0.6, "min": 16, "max": 256}}
    a = traffic.plan(t, 40.0, 1, 1000, rate_qps=5.0)
    b = traffic.plan(t, 40.0, 2**31 + 5, 1000, rate_qps=5.0)
    assert len(a) == len(b) == 200
    assert sorted(p.max_new for p in a) == sorted(p.max_new for p in b)
    assert [p.max_new for p in a] != [p.max_new for p in b]
    gaps_a = np.diff([p.due_s for p in a])
    gaps_b = np.diff([p.due_s for p in b])
    assert abs(gaps_a.mean() - 0.2) < 0.01 and abs(gaps_b.mean() - 0.2) < 0.01
    assert all(0 <= p.due_s < 40.0 for p in a)
    lens = [p.max_new for p in a]
    assert min(lens) >= 16 and max(lens) <= 256
    assert abs(np.median(lens) - 64) <= 2
    again = traffic.plan(t, 40.0, 1, 1000, rate_qps=5.0)
    assert all((x.prompt == y.prompt).all() and x.due_s == y.due_s
               for x, y in zip(a, again))


def test_arrivals_are_poisson():
    """The plan's arrivals are the copied sampler's, scaled to the
    window: a Poisson process held to ``n`` arrivals in it, so a
    quarter of the window holds a binomial count, not a fixed one, and
    the gaps spread like exponential ones (bursts and lulls)."""
    t = {"loop": "open", "prompt_len": 4,
         "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
    n, secs = 120, 50.0
    first_quarter, cvs = [], []
    for seed in range(200):
        due = np.array([p.due_s for p in
                        traffic.plan(t, secs, 2**31 + seed, 100,
                                     rate_qps=n / secs)])
        raw = traffic.poisson_arrivals(n + 1, n / secs,
                                       seed=[(2**31 + seed) & 0xFFFFFFFF,
                                             0, 1])
        assert np.allclose(due, raw[:n] * secs / raw[n])
        first_quarter.append(int((due < secs / 4).sum()))
        g = np.diff(due)
        cvs.append(g.std() / g.mean())
    var = np.var(first_quarter)
    assert 0.6 * n * 3 / 16 < var < 1.5 * n * 3 / 16
    assert 0.85 < np.mean(cvs) < 1.1


def test_quantile_arrivals_hold_one_set_of_gaps():
    """``poisson_quantiles``: every seed gets the same gaps, the
    exponential's midpoint quantiles scaled to the window, in its own
    order; so the seed moves where a burst falls, not how bursty the
    window is."""
    t = {"loop": "open", "arrivals": "poisson_quantiles", "prompt_len": 4,
         "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
    n, secs = 160, 50.0
    gaps, orders = [], []
    for seed in (3, 2**31 + 7, 2**32 + 11):
        due = np.array([p.due_s for p in
                        traffic.plan(t, secs, seed, 100, rate_qps=n / secs)])
        assert len(due) == n and 0 < due[0] and due[-1] < secs
        assert np.all(np.diff(due) > 0)
        g = np.diff(np.concatenate([[0.0], due, [secs]]))
        gaps.append(np.sort(g))
        orders.append(g)
    u = (np.arange(n + 1) + 0.5) / (n + 1)
    want = np.sort(-np.log1p(-u))
    want *= secs / want.sum()
    for g in gaps:
        assert np.allclose(g, want)
    assert not np.allclose(orders[0], orders[1])
    assert 0.85 < want.std() / want.mean() < 1.1   # exponential's cv
    t["arrivals"] = "uniform"
    with pytest.raises(ValueError, match="arrivals"):
        traffic.plan(t, secs, 3, 100, rate_qps=1.0)


def test_closed_plan():
    t = {"loop": "closed", "clients": 4, "per_client": 3, "prompt_len": 8,
         "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
    p = traffic.plan(t, 10.0, 3, 100)
    assert len(p) == 12 and all(x.due_s is None for x in p)
    assert [x.client for x in p[:5]] == [0, 1, 2, 3, 0]


def test_percentile():
    assert traffic.percentile(range(1, 101), 95) == 95.0
    assert traffic.percentile(range(1, 101), 90) == 90.0
    assert traffic.percentile([3.0], 50) == 3.0
