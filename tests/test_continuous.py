"""Continuous batching: per-row decode positions + slot splicing must
reproduce exactly what isolated lockstep generation produces, and the
in-graph fused loop must reproduce exactly what the legacy per-step
host loop produces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import AdmissionController, DecayingThreshold
from repro.models import transformer as tfm
from repro.serving.continuous import (ContinuousBatchingEngine,
                                      GenRequest, _leaf_batch_axis,
                                      _splice, cache_batch_axes,
                                      slot_write)

KEY = jax.random.PRNGKey(0)


def test_per_row_positions_match_lockstep():
    """decode_step with a pos VECTOR must agree with scalar pos when
    all rows share the position (regression for the vector path)."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 9), 0, cfg.vocab)
    c1 = tfm.init_cache(cfg, 3, 32)
    _, c1 = tfm.prefill(cfg, params, toks[:, :8], c1)
    c2 = jax.tree_util.tree_map(lambda x: x, c1)
    lg_s, _ = tfm.decode_step(cfg, params, toks[:, 8:9], c1, 8)
    lg_v, _ = tfm.decode_step(cfg, params, toks[:, 8:9], c2,
                              jnp.array([8, 8, 8]))
    np.testing.assert_allclose(
        np.asarray(lg_s, np.float32), np.asarray(lg_v, np.float32),
        rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("arch", ["stablelm-3b", "minicpm3-4b"])
def test_per_row_positions_staggered(arch):
    """Rows at DIFFERENT positions: each must match its own isolated
    batch-1 decode."""
    cfg = get_smoke_config(arch).replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    seqs = [jax.random.randint(jax.random.PRNGKey(i), (1, 6 + 2 * i),
                               0, cfg.vocab) for i in range(2)]
    # isolated references
    refs = []
    for s in seqs:
        c = tfm.init_cache(cfg, 1, 32)
        _, c = tfm.prefill(cfg, params, s[:, :-1], c)
        lg, _ = tfm.decode_step(cfg, params, s[:, -1:], c,
                                s.shape[1] - 1)
        refs.append(np.asarray(lg[0, 0], np.float32))

    # batched with staggered positions: prefill each row separately
    # into a shared pool via per-row writes
    pool = tfm.init_cache(cfg, 2, 32)
    from repro.serving.continuous import _splice
    toks_last = np.zeros((2, 1), np.int32)
    pos = np.zeros(2, np.int32)
    for i, s in enumerate(seqs):
        row = tfm.init_cache(cfg, 1, 32)
        _, row = tfm.prefill(cfg, params, s[:, :-1], row)
        pool = _splice(pool, row, i)
        toks_last[i, 0] = int(s[0, -1])
        pos[i] = s.shape[1] - 1
    lg, _ = tfm.decode_step(cfg, params, jnp.asarray(toks_last), pool,
                            jnp.asarray(pos))
    for i in range(2):
        np.testing.assert_allclose(np.asarray(lg[i, 0], np.float32),
                                   refs[i], rtol=2e-2, atol=2e-2)


def test_continuous_engine_end_to_end():
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [GenRequest(rid=i,
                       prompt=rng.integers(0, cfg.vocab, 8),
                       max_new=5 + (i % 4))
            for i in range(7)]
    stats = eng.serve(reqs, prompt_len=8)
    assert stats["n_admitted"] == 7
    assert all(r.done for r in reqs)
    assert all(len(r.generated) >= r.max_new for r in reqs)
    # more requests than slots => multiple refill waves, occupancy > 0.5
    assert stats["occupancy"] > 0.5


def _seeded_workload(cfg, n=9, plen=8, seed=0):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, plen) for _ in range(n)]
    return lambda: [GenRequest(rid=i, prompt=prompts[i],
                               max_new=4 + (i % 4)) for i in range(n)]


def test_fused_loop_parity_with_legacy():
    """The in-graph k-step loop must produce byte-identical greedy
    token sequences vs the legacy per-step Python loop; at k=1 (same
    refill cadence) the summary stats must match too."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    mk = _seeded_workload(cfg)

    eng = ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64)
    rl = mk()
    sl = eng.serve(rl, prompt_len=8, legacy=True)
    for k in (1, 4):
        eng_f = ContinuousBatchingEngine(cfg, params, n_slots=3,
                                         max_seq=64, sync_every=k)
        rf = mk()
        sf = eng_f.serve(rf, prompt_len=8)
        assert [r.generated for r in rf] == [r.generated for r in rl], \
            f"greedy tokens diverged at sync_every={k}"
        assert all(r.done for r in rf)
    # k=1: refill cadence identical to legacy -> identical stats
    eng1 = ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64,
                                    sync_every=1)
    s1 = eng1.serve(mk(), prompt_len=8)
    for key in ("decode_steps", "occupied_slot_steps", "occupancy",
                "tokens_generated", "n_admitted"):
        assert s1[key] == sl[key], (key, s1[key], sl[key])


def test_decode_window_compiles_once_across_refills():
    """Shape-drift regression: the fused decode window must trace
    exactly once no matter how many refill waves the workload needs."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                                   sync_every=4)
    stats = eng.serve(_seeded_workload(cfg, n=7)(), prompt_len=8)
    assert stats["prefill_calls"] >= 3          # several refill waves
    assert eng.decode_compile_count == 1


def test_fused_loop_respects_max_seq():
    """Budgets larger than the pool allow must stop at max_seq-1, like
    the legacy loop does."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    mk = lambda: [GenRequest(rid=0, prompt=np.arange(8) % cfg.vocab,
                             max_new=100)]
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=16)
    rl = mk()
    eng.serve(rl, prompt_len=8, legacy=True)
    eng_f = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=16,
                                     sync_every=4)
    rf = mk()
    eng_f.serve(rf, prompt_len=8)
    assert rf[0].generated == rl[0].generated
    assert rf[0].done


def test_eos_stops_generation_in_both_loops():
    """A request with an eos_id must stop at the first emitted EOS —
    identically in the fused window and the legacy loop."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    mk0 = _seeded_workload(cfg, n=4, seed=5)
    probe = mk0()
    ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64) \
        .serve(probe, prompt_len=8)
    # pick each request's 3rd emitted token as its EOS so every
    # request stops early on a token we KNOW the model emits
    def mk():
        reqs = mk0()
        for r, p in zip(reqs, probe):
            r.max_new = 7
            r.eos_id = p.generated[2]
        return reqs
    eng_l = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64)
    rl = mk()
    eng_l.serve(rl, prompt_len=8, legacy=True)
    eng_f = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                                     sync_every=4)
    rf = mk()
    eng_f.serve(rf, prompt_len=8)
    assert [r.generated for r in rf] == [r.generated for r in rl]
    for r in rf:
        assert r.done
        # stopped AT the eos token, well before the max_new budget
        assert r.generated[-1] == r.eos_id
        assert len(r.generated) <= 3


def test_eos_prefill_wave_does_not_drop_queue():
    """If every request of a refill wave hits EOS straight out of
    prefill, the slot must be retried with the next queued request —
    not leave the rest of the queue stranded (legacy regression)."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    mk0 = _seeded_workload(cfg, n=3, seed=9)
    probe = mk0()
    ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64) \
        .serve(probe, prompt_len=8)

    def mk():
        reqs = mk0()
        # first two die at their prefill token; the third runs free
        for r, p in zip(reqs[:2], probe[:2]):
            r.eos_id = p.generated[0]
        return reqs

    rl = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64) \
        .serve(rl, prompt_len=8, legacy=True)
    rf = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                             sync_every=4).serve(rf, prompt_len=8)
    assert all(r.done for r in rl) and all(r.done for r in rf)
    assert [r.generated for r in rf] == [r.generated for r in rl]
    assert len(rl[0].generated) == 1          # stopped at prefill
    assert len(rl[2].generated) > 1           # still served


def test_single_slot_pool_parity():
    """n_slots == 1: the batch-1 pool is shape-identical to the row
    cache, which the axis detector cannot see — both loops must still
    serve correctly (legacy assigns the row, fused scatters)."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    mk = _seeded_workload(cfg, n=3, seed=11)
    rl = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=1, max_seq=64) \
        .serve(rl, prompt_len=8, legacy=True)
    rf = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=1, max_seq=64,
                             sync_every=4).serve(rf, prompt_len=8)
    assert all(r.done for r in rl) and all(r.done for r in rf)
    assert [r.generated for r in rf] == [r.generated for r in rl]
    # against isolated lockstep generation: slot pool of one must
    # equal a plain batch-1 prefill+decode
    r0 = mk()[0]
    cache = tfm.init_cache(cfg, 1, 64)
    p = jnp.asarray(np.asarray(r0.prompt[:8], np.int32)[None])
    logits, cache = tfm.prefill(cfg, params, p, cache)
    toks = [int(jnp.argmax(logits[0, -1]))]
    pos = 8
    while len(toks) < len(rl[0].generated):
        logits, cache = tfm.decode_step(
            cfg, params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
            pos)
        toks.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    assert toks == rl[0].generated


def test_admission_uses_request_arrival_times():
    """The controller must be driven by the workload's arrival clock
    (``arrival_t``), not a fake fixed-increment one."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    ctrl = AdmissionController(
        threshold=DecayingThreshold(0.2, 0.2, 1.0))
    for v in np.linspace(0, 1, 32):
        ctrl.cost.observe(v, 1.0, 0.0)
    ctrl.meter.record(1.0)
    rng = np.random.default_rng(3)
    arrivals = [0.0, 1.5, 2.25, 7.75]
    reqs = [GenRequest(rid=i, prompt=rng.integers(0, cfg.vocab, 8),
                       max_new=3, arrival_t=arrivals[i])
            for i in range(4)]
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                                   controller=ctrl, sync_every=2)
    eng.serve(reqs, prompt_len=8)
    assert [d.t for d in ctrl.history] == arrivals


# ---------------------------------------------------------------------------
# slot writes
# ---------------------------------------------------------------------------

def test_leaf_batch_axis_raises_on_unknown_layouts():
    with pytest.raises(ValueError):
        _leaf_batch_axis((4, 4), (5, 5))        # two differing axes
    with pytest.raises(ValueError):
        _leaf_batch_axis((4, 4), (4, 4, 4))     # rank change
    assert _leaf_batch_axis((2, 7), (3, 7)) == 0
    assert _leaf_batch_axis((5, 5), (5, 5)) == -1


def test_slot_write_raises_on_mismatched_leaf():
    """A cache row that doesn't fit the pool at the derived batch axis
    must raise, not silently drop the prefilled row."""
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    axes = cache_batch_axes(cfg, 32)
    pool = tfm.init_cache(cfg, 4, 32)
    bad_rows = jax.tree_util.tree_map(
        lambda x: (x[..., :-1] if hasattr(x, "ndim") and x.ndim >= 4
                   else x),
        tfm.init_cache(cfg, 2, 32))
    with pytest.raises(ValueError, match="refusing to drop"):
        slot_write(pool, bad_rows, jnp.array([0, 1]), axes)


def test_legacy_splice_raises_on_ambiguous_leaf():
    pool = {"x": jnp.zeros((4, 5))}
    row = {"x": jnp.zeros((1, 3))}              # two differing axes
    with pytest.raises(ValueError):
        _splice(pool, row, 0)


# ---------------------------------------------------------------------------
# paged KV pool
# ---------------------------------------------------------------------------

def _smoke_cfg():
    return get_smoke_config("stablelm-3b").replace(remat=False)


def _paged(cfg, **kw):
    return cfg.replace(kv_block_size=8, **kw)


def test_paged_parity_with_contiguous_across_refills():
    """The paged pool must produce byte-identical greedy tokens to the
    contiguous parity oracle over multiple refill waves."""
    cfg = _smoke_cfg()
    params = tfm.init_lm(cfg, KEY)
    mk = _seeded_workload(cfg, n=9)
    rc = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64,
                             sync_every=4).serve(rc, prompt_len=8)
    rp = mk()
    stats = ContinuousBatchingEngine(_paged(cfg), params, n_slots=3,
                                     max_seq=64, sync_every=4) \
        .serve(rp, prompt_len=8)
    assert [r.generated for r in rp] == [r.generated for r in rc]
    assert all(r.done for r in rp)
    assert stats["mode"] == "paged"
    assert stats["prefill_calls"] >= 3           # several refill waves


@pytest.mark.parametrize("impl", ["auto", "pallas"])
def test_paged_parity_with_contiguous_three_layers(impl):
    """Three layers deep, where a layer index mixed up with another
    (the layer loop carries the whole stacked pool and each layer
    writes and reads its own layer of it) cannot hide as it could in
    the two-layer smoke stack: the paged pool must produce
    byte-identical greedy tokens to the contiguous oracle across
    refills, both through the jnp path and through the kernels (the
    table-native paged kernel against the contiguous decode kernel)."""
    cfg = _smoke_cfg().replace(n_layers=3, attn_impl=impl)
    params = tfm.init_lm(cfg, KEY)
    mk = _seeded_workload(cfg, n=5)
    rc = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=32,
                             sync_every=2).serve(rc, prompt_len=8)
    rp = mk()
    stats = ContinuousBatchingEngine(
        _paged(cfg), params, n_slots=2, max_seq=32,
        sync_every=2).serve(rp, prompt_len=8)
    assert [r.generated for r in rp] == [r.generated for r in rc]
    assert all(r.done for r in rp)
    assert stats["mode"] == "paged"
    assert stats["prefill_calls"] >= 3           # several refill waves


def test_paged_cache_write_on_stacked_pool_writes_one_layer():
    """A token written into layer l of a stacked pool lands where the
    one-layer write into ``pool[l]`` puts it, and every other layer is
    left as it was."""
    from repro.models import attention as attn
    L, B, K, hd, bs, mb = 3, 2, 2, 4, 4, 2
    nb = 1 + B * mb
    one = attn.init_paged_kv_cache(B, mb * bs, K, hd, n_blocks=nb,
                                   block_size=bs, dtype=jnp.float32)
    stack = jax.tree_util.tree_map(lambda x: jnp.stack([x] * L), one)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    k_new = jnp.ones((B, 1, K, hd))
    v_new = 2 * jnp.ones((B, 1, K, hd))
    pos = jnp.asarray([5, 2], jnp.int32)
    want = attn.paged_cache_write(one, k_new, v_new, pos, table, bs)
    got = attn.paged_cache_write(stack, k_new, v_new, pos, table, bs,
                                 jnp.int32(1))
    for leaf_got, leaf_one, leaf_want in zip(got, one, want):
        np.testing.assert_array_equal(leaf_got[1], leaf_want)
        for other in (0, 2):
            np.testing.assert_array_equal(leaf_got[other], leaf_one)


def test_paged_native_kernel_token_parity_end_to_end():
    """The table-native paged flash-decode kernel (attn_impl="pallas",
    interpret mode on CPU) must produce byte-identical greedy tokens
    to the default dispatch through a full DecodeSession serve —
    refills, block tables, trash-block masking and all."""
    cfg = _paged(_smoke_cfg())
    params = tfm.init_lm(cfg, KEY)
    mk = _seeded_workload(cfg, n=4)
    r_ref = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=32,
                             sync_every=2).serve(r_ref, prompt_len=8)
    r_nat = mk()
    stats = ContinuousBatchingEngine(
        cfg.replace(attn_impl="pallas"), params, n_slots=2, max_seq=32,
        sync_every=2).serve(r_nat, prompt_len=8)
    assert [r.generated for r in r_nat] == [r.generated for r in r_ref]
    assert all(r.done for r in r_nat)
    assert stats["mode"] == "paged"


def test_paged_parity_with_eos_waves():
    """EOS early-stops — mid-decode and straight out of prefill — must
    free blocks and keep token parity with the contiguous oracle."""
    cfg = _smoke_cfg()
    params = tfm.init_lm(cfg, KEY)
    mk0 = _seeded_workload(cfg, n=4, seed=5)
    probe = mk0()
    ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64) \
        .serve(probe, prompt_len=8)

    def mk():
        reqs = mk0()
        for r, p in zip(reqs, probe):
            r.max_new = 7
        reqs[0].eos_id = probe[0].generated[0]   # dies at prefill
        reqs[1].eos_id = probe[1].generated[2]   # dies mid-decode
        return reqs

    rc = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                             sync_every=4).serve(rc, prompt_len=8)
    rp = mk()
    eng = ContinuousBatchingEngine(_paged(cfg), params, n_slots=2,
                                   max_seq=64, sync_every=4)
    stats = eng.serve(rp, prompt_len=8)
    assert [r.generated for r in rp] == [r.generated for r in rc]
    assert all(r.done for r in rp)
    assert stats["blocks_allocated"] == stats["blocks_freed"]


def test_paged_block_accounting_across_windows():
    """Every block is free or owned by exactly one slot after every
    window; the ledger balances when the session drains."""
    cfg = _paged(_smoke_cfg())
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                                   sync_every=2)
    sess = eng.start_session(8)
    for r in _seeded_workload(cfg, n=7)():
        sess.push(r)
    allocatable = eng.pool_blocks - 1
    windows = 0
    while not sess.idle:
        sess.advance()
        windows += 1
        owned = [b for bl in sess._slot_blocks.values() for b in bl]
        assert len(owned) == len(set(owned))          # unique owners
        assert 0 not in owned                         # trash reserved
        assert set(owned).isdisjoint(sess._free_blocks)
        assert len(owned) + len(sess._free_blocks) == allocatable
    assert windows > 2
    assert sess.blocks_allocated == sess.blocks_freed > 0
    assert len(sess._free_blocks) == allocatable
    assert sess.peak_blocks_in_use <= allocatable


def test_paged_pool_exhaustion_queue_waits():
    """A pool too small for all slots serialises admission: requests
    WAIT in the queue (never dropped) and tokens stay byte-identical
    to the contiguous oracle."""
    cfg = _smoke_cfg()
    params = tfm.init_lm(cfg, KEY)
    mk = _seeded_workload(cfg, n=5, seed=3)
    rc = mk()
    ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64,
                             sync_every=2).serve(rc, prompt_len=8)
    # each request needs 2 blocks (8 prompt + <8 new rows @ bs=8);
    # 3 allocatable blocks fit only ONE request at a time
    pcfg = _paged(cfg, kv_pool_blocks=4)
    eng = ContinuousBatchingEngine(pcfg, params, n_slots=3, max_seq=64,
                                   sync_every=2)
    sess = eng.start_session(8)
    rp = mk()
    for r in rp:
        sess.push(r)
    while not sess.idle:
        sess.advance()
        assert sess.n_active <= 1        # pool admits one at a time
    assert all(r.done for r in rp)       # queue waited, nothing lost
    assert [r.generated for r in rp] == [r.generated for r in rc]


def test_paged_request_too_big_raises():
    """A request whose budget exceeds the WHOLE pool can never be
    served — that is a config error, not a queue wait."""
    cfg = _smoke_cfg()
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(_paged(cfg, kv_pool_blocks=2),
                                   params, n_slots=2, max_seq=64)
    reqs = [GenRequest(rid=0, prompt=np.arange(8) % cfg.vocab,
                       max_new=8)]
    with pytest.raises(ValueError, match="never be served"):
        eng.serve(reqs, prompt_len=8)


def test_paged_long_prompt_does_not_inflate_earlier_budget():
    """A long prompt deeper in the queue must not re-pad an earlier
    short request past the pool: the short one serves in its own wave
    at its own padding, the long one follows when blocks free up."""
    cfg = _paged(_smoke_cfg(), kv_pool_blocks=13)   # 12 allocatable
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=128,
                                   sync_every=2)
    sess = eng.start_session(None)                  # dynamic plen
    rng = np.random.default_rng(0)
    short = GenRequest(rid=0, prompt=rng.integers(0, cfg.vocab, 8),
                       max_new=4)                   # solo: 2 blocks
    long_ = GenRequest(rid=1, prompt=rng.integers(0, cfg.vocab, 40),
                       max_new=4)                   # solo: 9 blocks
    sess.push(short)
    sess.push(long_)
    # co-padding both to the long prompt's bucket would cost 9 blocks
    # EACH (18 > 12) — the wave must instead split, not raise
    while not sess.idle:
        sess.advance()
    assert short.done and long_.done
    assert len(short.generated) >= 4 and len(long_.generated) >= 4
    assert sess.blocks_allocated == sess.blocks_freed
    assert len(sess._free_blocks) == 12


def test_paged_unservable_request_raise_leaves_state_clean():
    """The can-never-be-served error must fire BEFORE any block is
    popped: no leaked blocks, no half-admitted wave, queue intact."""
    cfg = _paged(_smoke_cfg(), kv_pool_blocks=4)    # 3 allocatable
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                                   sync_every=2)
    sess = eng.start_session(8)
    rng = np.random.default_rng(1)
    ok = GenRequest(rid=0, prompt=rng.integers(0, cfg.vocab, 8),
                    max_new=4)                      # needs 2 blocks
    too_big = GenRequest(rid=1, prompt=rng.integers(0, cfg.vocab, 8),
                         max_new=60)                # needs > 3 blocks
    sess.push(ok)
    sess.push(too_big)
    with pytest.raises(ValueError, match="never be served"):
        sess.advance()
    assert len(sess._free_blocks) == 3              # nothing stranded
    assert sess._slot_blocks == {}
    assert sess.n_queued == 2                       # queue untouched


def test_paged_decode_window_compiles_once():
    """Shape-drift regression for the paged scan: one trace no matter
    how many refill waves (block tables ride the cache pytree with a
    static shape)."""
    cfg = _paged(_smoke_cfg())
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                                   sync_every=4)
    stats = eng.serve(_seeded_workload(cfg, n=7)(), prompt_len=8)
    assert stats["prefill_calls"] >= 3
    assert eng.decode_compile_count == 1


def test_paged_legacy_loop_refuses():
    cfg = _paged(_smoke_cfg())
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64)
    with pytest.raises(ValueError, match="contiguous"):
        eng.serve(_seeded_workload(cfg, n=2)(), prompt_len=8,
                  legacy=True)


def test_paged_prefill_into_pool_raises():
    """tfm.prefill must refuse a paged pool — prefill goes through a
    contiguous row cache + block scatter, never table indirection."""
    cfg = _paged(_smoke_cfg())
    params = tfm.init_lm(cfg, KEY)
    pool = tfm.init_cache(cfg, 2, 32)
    toks = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError, match="paged pool"):
        tfm.prefill(cfg, params, toks, pool)


def test_paged_rejects_unsupported_layouts():
    """Windowed / recurrent stacks keep constant-size state per slot —
    the paged pool refuses them instead of silently mislaying rows."""
    cfg = _paged(_smoke_cfg(), window=16)       # -> local_attn kinds
    with pytest.raises(ValueError, match="paged KV pool"):
        tfm.init_cache(cfg, 2, 64)


def test_paged_misconfigurations_rejected():
    """Half-configured paging must be loud: a pool size without a
    block size would silently serve contiguous, and forcing
    layout='paged' on a contiguous config has no geometry."""
    with pytest.raises(ValueError, match="kv_block_size"):
        _smoke_cfg().replace(kv_pool_blocks=8)
    with pytest.raises(ValueError, match="kv_block_size"):
        tfm.init_cache(_smoke_cfg(), 2, 64, layout="paged")


def test_splice_batch1_pool_raises():
    """The n_slots == 1 caveat is now a hard error at the call
    boundary: a batch-1 pool has no identifiable batch axis."""
    cfg = _smoke_cfg()
    pool = tfm.init_cache(cfg, 1, 32)
    row = tfm.init_cache(cfg, 1, 32)
    with pytest.raises(ValueError, match="batch-1"):
        _splice(pool, row, 0)


def test_paged_decode_attend_kernel_path_matches_jnp():
    """The block-table kernel shim (kops dispatch) must agree with the
    pure-jnp gather path on a scattered block layout."""
    from repro.models import attention as attn
    B, K, H, hd, bs, mb = 2, 2, 4, 16, 8, 3
    C = mb * bs
    nb = 1 + B * mb
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    cache = attn.init_paged_kv_cache(B, C, K, hd, n_blocks=nb,
                                     block_size=bs, dtype=jnp.float32)
    rng = np.random.default_rng(2)
    perm = rng.permutation(np.arange(1, nb)).reshape(B, mb)
    table = jnp.asarray(perm, jnp.int32)
    k_pool = jax.random.normal(ks[0], cache.k.shape)
    v_pool = jax.random.normal(ks[1], cache.v.shape)
    pos = jnp.broadcast_to(jnp.arange(C), (B, C))
    pos = pos.at[:, C - 5:].set(-1)              # unwritten tail
    cache = cache._replace(k=k_pool, v=v_pool, pos=pos)
    q = jax.random.normal(ks[2], (B, 1, H, hd))
    cur = jnp.array([C - 6, C - 8], jnp.int32)
    o_jnp = attn.paged_decode_attend(q, cache, table, pos=cur)
    o_ker = attn.paged_decode_attend_kernel(q, cache, table, pos=cur,
                                            impl="ref")
    np.testing.assert_allclose(np.asarray(o_jnp, np.float32),
                               np.asarray(o_ker, np.float32),
                               rtol=2e-5, atol=2e-5)


def test_continuous_engine_with_controller():
    cfg = get_smoke_config("stablelm-3b").replace(remat=False)
    params = tfm.init_lm(cfg, KEY)
    ctrl = AdmissionController(
        threshold=DecayingThreshold(0.2, 0.2, 1.0))
    for v in np.linspace(0, 1, 32):
        ctrl.cost.observe(v, 1.0, 0.0)
    ctrl.meter.record(1.0)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                                   controller=ctrl)
    rng = np.random.default_rng(1)
    reqs = [GenRequest(rid=i, prompt=rng.integers(0, cfg.vocab, 8),
                       max_new=4, entropy_hint=float(i % 10) / 10)
            for i in range(10)]
    stats = eng.serve(reqs, prompt_len=8)
    assert 0 < stats["n_admitted"] < 10      # controller pruned some
    skipped = [r for r in reqs if not r.admitted]
    assert all(r.done and not r.generated for r in skipped)

# ---------------------------------------------------------------------------
# SlotClock — direct unit coverage (previously only exercised through
# SimContinuousEngine / the fleet layer)
# ---------------------------------------------------------------------------

def test_slot_clock_reserve_picks_earliest_free_slot():
    from repro.serving.continuous import SlotClock
    clk = SlotClock(n_slots=2)
    s0, st0, f0 = clk.reserve(0.0, 1.0)
    s1, st1, f1 = clk.reserve(0.0, 0.25)
    assert s0 != s1 and st0 == st1 == 0.0
    # the slot freeing at 0.25 (not the 1.0 one) takes the next job,
    # and service starts at that slot's horizon, not at now
    s2, st2, f2 = clk.reserve(0.0, 0.5)
    assert s2 == s1
    assert st2 == pytest.approx(0.25) and f2 == pytest.approx(0.75)
    # start never precedes now on an already-free slot
    s3, st3, f3 = clk.reserve(2.0, 0.5)
    assert st3 == 2.0 and f3 == 2.5


def test_slot_clock_pressure_monotone_and_zero_when_free():
    from repro.serving.continuous import SlotClock
    clk = SlotClock(n_slots=2)
    clk.reserve(0.0, 1.0)
    clk.reserve(0.0, 2.0)
    ps = [clk.pressure(t) for t in (0.0, 0.5, 1.0, 1.5, 2.0, 3.0)]
    assert all(a >= b for a, b in zip(ps, ps[1:]))   # non-increasing
    # pressure is the wait for a NEW arrival: the earliest-free slot
    assert ps[0] == pytest.approx(1.0)
    assert clk.pressure(1.0) == 0.0                  # a slot just freed
    # polling is side-effect-free
    assert clk.pressure(0.0) == clk.pressure(0.0) == pytest.approx(1.0)


def test_slot_clock_busy_counts_and_reset_clears():
    from repro.serving.continuous import SlotClock
    clk = SlotClock(n_slots=3)
    clk.reserve(0.0, 1.0)
    clk.reserve(0.0, 2.0)
    assert clk.busy(0.5) == 2
    assert clk.busy(1.5) == 1
    assert clk.busy(2.5) == 0
    clk.reset()
    assert clk.busy(0.0) == 0
    assert clk.pressure(0.0) == 0.0
    assert clk.free_at == [0.0] * 3
