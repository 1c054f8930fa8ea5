"""Property and parity tests for the on-device sampling layer.

The serving stack has exactly ONE sampling rule
(``repro.serving.sampling.sample_token`` under request-derived,
position-folded keys), so these tests pin its algebra directly:

- top-k keeps EXACTLY k logits finite (ties included, via rank mask);
- top-p keeps the MINIMAL descending-probability prefix covering p;
- temperature -> 0 is argmax, bitwise;
- identical (key, logits, params) -> identical token (determinism);
- keys derive from request ids, never slot indices, so a slot reused
  across refill waves can never replay its previous occupant's stream
  (the seeding-gap regression);
- the fused lax.scan window, the legacy per-step host loop, and the
  paged pool all agree token-for-token under nonzero temperature; and
  an EXPLICIT SamplingParams(temperature=0) is byte-identical to the
  default greedy path on both KV layouts.

``hypothesis`` drives the property sweeps when installed; the conftest
fallback runs a bounded deterministic random sweep otherwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.models import transformer as tfm
from repro.serving import sampling
from repro.serving.continuous import ContinuousBatchingEngine, GenRequest
from repro.serving.sampling import SamplingParams

KEY = jax.random.PRNGKey(0)


def _logits(seed: int, b: int = 1, v: int = 37) -> jnp.ndarray:
    return jax.random.normal(jax.random.PRNGKey(seed), (b, v)) * 4.0


# ---------------------------------------------------------------------------
# masking algebra
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(0, 48))
def test_top_k_keeps_exactly_k(seed, k):
    """top_k_mask leaves exactly min(k, V) finite entries (k=0 = all),
    and they are the k HIGHEST by the rank order."""
    v = 37
    logits = _logits(seed, v=v)
    masked = np.asarray(sampling.top_k_mask(logits, jnp.array([k])))
    finite = np.isfinite(masked[0])
    expect = v if k == 0 else min(k, v)
    assert finite.sum() == expect
    if 0 < k < v:
        # every kept logit must be >= every dropped logit
        raw = np.asarray(logits[0])
        assert raw[finite].min() >= raw[~finite].max()


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000),
       p=st.floats(0.05, 1.0))
def test_top_p_minimal_covering_prefix(seed, p):
    """The kept set is the minimal descending-probability prefix whose
    mass covers p: dropping its smallest member must leave mass < p,
    and p >= 1 keeps everything.  Top-1 always survives."""
    logits = _logits(seed)
    masked = np.asarray(sampling.top_p_mask(logits, jnp.array([p])),
                        np.float32)
    keep = np.isfinite(masked[0])
    raw = np.asarray(logits[0], np.float32)
    probs = np.exp(raw - raw.max())
    probs = probs / probs.sum()
    if p >= 1.0:
        assert keep.all()
        return
    assert keep[np.argmax(raw)]                      # top-1 survives
    kept_sorted = np.sort(probs[keep])[::-1]
    # minimality: the prefix minus its last element does not cover p
    assert kept_sorted[:-1].sum() < p + 1e-5
    # coverage: the full kept set reaches p OR is the whole vocab
    assert keep.all() or kept_sorted.sum() >= p - 1e-5
    # prefix property: every kept prob >= every dropped prob
    if not keep.all():
        assert probs[keep].min() >= probs[~keep].max() - 1e-12


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), b=st.integers(1, 5))
def test_temperature_zero_is_argmax_bitwise(seed, b):
    """T=0 rows return jnp.argmax over the RAW logits regardless of
    top-k/top-p settings — the greedy paths stay byte-stable."""
    logits = _logits(seed, b=b)
    keys = jnp.asarray(
        np.stack([sampling.request_key(0, i) for i in range(b)]))
    tok = sampling.sample_token(keys, logits,
                                jnp.zeros(b, jnp.float32),
                                jnp.full(b, 7, jnp.int32),
                                jnp.full(b, 0.3, jnp.float32))
    assert np.array_equal(np.asarray(tok),
                          np.asarray(jnp.argmax(logits, -1), np.int32))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000),
       temp=st.floats(0.1, 2.0),
       k=st.integers(0, 20),
       p=st.floats(0.3, 1.0))
def test_sampling_deterministic_under_key(seed, temp, k, p):
    """Identical (key, logits, temperature, top_k, top_p) -> identical
    token; folding a different position in changes the stream."""
    logits = _logits(seed, b=2)
    base = jnp.asarray(
        np.stack([sampling.request_key(3, 11), sampling.request_key(3, 12)]))
    keys = sampling.step_keys(base, jnp.array([5, 5]))
    args = (jnp.full(2, temp, jnp.float32), jnp.full(2, k, jnp.int32),
            jnp.full(2, p, jnp.float32))
    t1 = np.asarray(sampling.sample_token(keys, logits, *args))
    t2 = np.asarray(sampling.sample_token(keys, logits, *args))
    assert np.array_equal(t1, t2)


def test_sampled_token_respects_masks():
    """A sampled token always lies inside the top-k/top-p kept set."""
    rng = np.random.default_rng(0)
    for trial in range(25):
        logits = _logits(trial, b=1)
        k, p = int(rng.integers(1, 10)), float(rng.uniform(0.2, 0.9))
        keys = sampling.step_keys(
            jnp.asarray(sampling.request_key(1, trial)[None]),
            jnp.array([trial]))
        tok = int(np.asarray(sampling.sample_token(
            keys, logits, jnp.array([0.8], jnp.float32),
            jnp.array([k], jnp.int32), jnp.array([p], jnp.float32)))[0])
        masked = sampling.top_p_mask(
            sampling.top_k_mask(logits / 0.8, jnp.array([k])),
            jnp.array([p]))
        assert np.isfinite(np.asarray(masked)[0, tok])


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.5)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    assert SamplingParams().greedy
    assert not SamplingParams(temperature=0.7).greedy


def test_request_key_is_rid_derived():
    """Keys depend on (seed, rid) only — distinct rids give distinct
    keys, the same (seed, rid) always gives the same key."""
    a = sampling.request_key(0, 1)
    b = sampling.request_key(0, 2)
    c = sampling.request_key(1, 1)
    assert not np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert np.array_equal(a, sampling.request_key(0, 1))


# ---------------------------------------------------------------------------
# engine-level parity
# ---------------------------------------------------------------------------

def _cfg():
    return get_smoke_config("stablelm-3b").replace(remat=False)


def _reqs(cfg, n=6, plen=8, seed=0, sampling_params=None, max_new=None):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, plen) for _ in range(n)]
    return [GenRequest(rid=i, prompt=prompts[i],
                       max_new=(max_new or 4 + (i % 4)),
                       sampling=sampling_params)
            for i in range(n)]


SP = SamplingParams(temperature=0.9, top_k=20, top_p=0.95, seed=7)


def test_explicit_t0_matches_default_greedy_contiguous_and_paged():
    """SamplingParams(temperature=0) must be byte-identical to the
    default (no sampling params at all) greedy window on BOTH KV
    layouts — the CI-gated greedy parity oracle under the
    sampling-enabled graph."""
    cfg = _cfg()
    params = tfm.init_lm(cfg, KEY)
    for layout_cfg in (cfg, cfg.replace(kv_block_size=8)):
        eng_d = ContinuousBatchingEngine(layout_cfg, params, n_slots=3,
                                         max_seq=64, sync_every=2)
        rd = _reqs(layout_cfg)
        eng_d.serve(rd, prompt_len=8)
        eng_e = ContinuousBatchingEngine(layout_cfg, params, n_slots=3,
                                         max_seq=64, sync_every=2)
        re_ = _reqs(layout_cfg,
                    sampling_params=SamplingParams(temperature=0.0))
        eng_e.serve(re_, prompt_len=8)
        layout = "paged" if layout_cfg.kv_block_size else "contiguous"
        assert ([r.generated for r in re_]
                == [r.generated for r in rd]), layout


def test_fused_sampled_matches_legacy_sampled():
    """Nonzero temperature: the fused lax.scan window and the legacy
    per-step host loop draw from the SAME (rid, position)-folded
    streams, so tokens must match exactly."""
    cfg = _cfg()
    params = tfm.init_lm(cfg, KEY)
    eng_l = ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64)
    rl = _reqs(cfg, sampling_params=SP)
    eng_l.serve(rl, prompt_len=8, legacy=True)
    for k in (1, 4):
        eng_f = ContinuousBatchingEngine(cfg, params, n_slots=3,
                                         max_seq=64, sync_every=k)
        rf = _reqs(cfg, sampling_params=SP)
        eng_f.serve(rf, prompt_len=8)
        assert [r.generated for r in rf] == [r.generated for r in rl], \
            f"sampled tokens diverged at sync_every={k}"
    # and the sampled stream actually differs from greedy
    eng_g = ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64)
    rg = _reqs(cfg)
    eng_g.serve(rg, prompt_len=8)
    assert [r.generated for r in rl] != [r.generated for r in rg]


def test_paged_sampled_matches_contiguous_sampled():
    cfg = _cfg()
    params = tfm.init_lm(cfg, KEY)
    eng_c = ContinuousBatchingEngine(cfg, params, n_slots=3, max_seq=64,
                                     sync_every=2)
    rc = _reqs(cfg, sampling_params=SP)
    eng_c.serve(rc, prompt_len=8)
    pcfg = cfg.replace(kv_block_size=8)
    eng_p = ContinuousBatchingEngine(pcfg, params, n_slots=3,
                                     max_seq=64, sync_every=2)
    rp = _reqs(pcfg, sampling_params=SP)
    eng_p.serve(rp, prompt_len=8)
    assert [r.generated for r in rp] == [r.generated for r in rc]


def test_slot_reuse_does_not_replay_streams():
    """The seeding-gap regression: keys derive from REQUEST ids, not
    slot indices.  Two requests pushed back-to-back through the same
    single slot must each produce exactly the stream they produce when
    served alone — and the two streams must differ from each other."""
    cfg = _cfg()
    params = tfm.init_lm(cfg, KEY)
    sp = SamplingParams(temperature=1.0, seed=3)
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, 8)

    def solo(rid):
        eng = ContinuousBatchingEngine(cfg, params, n_slots=1,
                                       max_seq=64, sync_every=2)
        r = GenRequest(rid=rid, prompt=prompt, max_new=6, sampling=sp)
        eng.serve([r], prompt_len=8)
        return r.generated

    ref_a, ref_b = solo(101), solo(202)
    # same prompt, same slot, different rid -> different streams
    assert ref_a != ref_b

    eng = ContinuousBatchingEngine(cfg, params, n_slots=1, max_seq=64,
                                   sync_every=2)
    ra = GenRequest(rid=101, prompt=prompt, max_new=6, sampling=sp)
    rb = GenRequest(rid=202, prompt=prompt, max_new=6, sampling=sp)
    eng.serve([ra, rb], prompt_len=8)       # rb waits for ra's slot
    assert ra.generated == ref_a
    assert rb.generated == ref_b


def test_per_request_sampling_overrides_engine_default():
    """A request's own SamplingParams wins over the cfg-level default;
    requests without one inherit the engine default."""
    cfg = _cfg().replace(temperature=0.8, sampling_seed=5)
    params = tfm.init_lm(cfg, KEY)
    eng = ContinuousBatchingEngine(cfg, params, n_slots=2, max_seq=64,
                                   sync_every=2)
    greedy_req = GenRequest(
        rid=0, prompt=np.arange(8), max_new=5,
        sampling=SamplingParams(temperature=0.0))
    default_req = GenRequest(rid=1, prompt=np.arange(8), max_new=5)
    eng.serve([greedy_req, default_req], prompt_len=8)

    cfg_g = _cfg()
    eng_g = ContinuousBatchingEngine(cfg_g, params, n_slots=2,
                                     max_seq=64, sync_every=2)
    ref = GenRequest(rid=0, prompt=np.arange(8), max_new=5)
    eng_g.serve([ref], prompt_len=8)
    assert greedy_req.generated == ref.generated
    assert default_req.generated != ref.generated
