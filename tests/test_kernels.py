"""Per-kernel validation: shape/dtype sweeps + hypothesis properties,
each Pallas kernel (interpret mode) vs its pure-jnp ref.py oracle."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import decode_attention as dak
from repro.kernels import entropy as entk
from repro.kernels import flash_attention as fak
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# entropy kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,V,dtype", [
    (4, 1000, jnp.float32),
    (16, 4096, jnp.float32),
    (3, 257, jnp.float32),
    (8, 2048, jnp.bfloat16),
    (1, 50_304, jnp.float32),
])
def test_entropy_kernel_matches_ref(B, V, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), (B, V)) * 4).astype(dtype)
    h, p, a = entk.entropy_stats(x, b_blk=8, v_blk=512)
    hr, pr, ar = ref.entropy_stats(x)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.array(h), np.array(hr), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.array(p), np.array(pr), rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.array(a), np.array(ar))


@settings(max_examples=20, deadline=None)
@given(b=st.integers(1, 9), v=st.integers(2, 700),
       scale=st.floats(0.1, 20.0), seed=st.integers(0, 2 ** 16))
def test_entropy_kernel_property(b, v, scale, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (b, v)) * scale
    h, p, a = entk.entropy_stats(x, b_blk=4, v_blk=128)
    hr, pr, _ = ref.entropy_stats(x)
    np.testing.assert_allclose(np.array(h), np.array(hr),
                               rtol=1e-4, atol=1e-4)
    # invariants: 0 <= H <= log(V); 1/V <= p_max <= 1
    assert (np.array(h) >= -1e-5).all()
    assert (np.array(h) <= np.log(v) + 1e-4).all()
    assert (np.array(p) <= 1.0 + 1e-6).all()
    assert (np.array(p) >= 1.0 / v - 1e-6).all()


def test_entropy_extremes():
    # one-hot logits -> H ~ 0, p ~ 1; uniform -> H = log V
    V = 512
    x = jnp.zeros((2, V)).at[0, 7].set(100.0)
    h, p, a = entk.entropy_stats(x, v_blk=128)
    assert float(h[0]) < 1e-3 and abs(float(p[0]) - 1.0) < 1e-5
    assert int(a[0]) == 7
    np.testing.assert_allclose(float(h[1]), np.log(V), rtol=1e-5)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,Sq,Skv,hd,win,dtype", [
    (2, 4, 2, 64, 64, 32, 0, jnp.float32),
    (1, 8, 8, 100, 100, 16, 0, jnp.float32),
    (2, 4, 1, 128, 128, 64, 32, jnp.float32),   # MQA + window
    (1, 2, 2, 70, 70, 8, 16, jnp.float32),      # ragged
    (2, 4, 2, 64, 64, 32, 0, jnp.bfloat16),
])
def test_flash_attention_matches_ref(B, H, K, Sq, Skv, hd, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, K, Skv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, K, Skv, hd)).astype(dtype)
    o = fak.flash_attention(q, k, v, window=win, q_blk=32, k_blk=32)
    orf = ref.flash_attention(q, k, v, window=win)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.array(o, np.float32),
                               np.array(orf, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 3), g=st.integers(1, 4), k=st.integers(1, 3),
       sq=st.integers(1, 80), hd=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 999))
def test_flash_attention_property(b, g, k, sq, hd, seed):
    H = g * k
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, H, sq, hd))
    kk = jax.random.normal(ks[1], (b, k, sq, hd))
    v = jax.random.normal(ks[2], (b, k, sq, hd))
    o = fak.flash_attention(q, kk, v, q_blk=16, k_blk=16)
    orf = ref.flash_attention(q, kk, v)
    np.testing.assert_allclose(np.array(o), np.array(orf),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_q_offset():
    """Continuation chunks (q_offset > 0) see the right causal mask."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 2, 16, 8))
    k = jax.random.normal(ks[1], (1, 2, 48, 8))
    v = jax.random.normal(ks[2], (1, 2, 48, 8))
    o = fak.flash_attention(q, k, v, q_offset=32, q_blk=16, k_blk=16)
    orf = ref.flash_attention(q, k, v, q_offset=32)
    np.testing.assert_allclose(np.array(o), np.array(orf),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# decode attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,S,hd,win", [
    (2, 4, 2, 256, 32, 0),
    (3, 8, 1, 100, 16, 0),
    (2, 4, 4, 128, 64, 48),
    (1, 16, 2, 1024, 128, 0),
])
def test_decode_attention_matches_ref(B, H, K, S, hd, win):
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, K, S, hd))
    v = jax.random.normal(ks[2], (B, K, S, hd))
    kv_pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    kv_pos = kv_pos.at[:, S - 5:].set(-1)          # empty slots
    cur = jnp.full((B,), S - 1)
    o = dak.decode_attention(q, k, v, kv_pos, cur, window=win, k_blk=64)
    orf = ref.decode_attention(q, k, v, kv_pos, cur, window=win)
    np.testing.assert_allclose(np.array(o), np.array(orf),
                               rtol=3e-5, atol=3e-5)


def test_decode_attention_ring_buffer():
    """Ring-buffered (windowed) cache: slot positions out of order."""
    B, H, K, S, hd = 1, 2, 2, 32, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, K, S, hd))
    v = jax.random.normal(ks[2], (B, K, S, hd))
    # ring: slots hold positions 32..63 wrapped
    kv_pos = jnp.asarray([(np.arange(S) + 32 - (np.arange(S) >= 16) * 0)
                          % 64 + 32])[0][None, :]
    kv_pos = jnp.asarray(np.roll(np.arange(32, 64), 7))[None, :]
    cur = jnp.array([63])
    o = dak.decode_attention(q, k, v, kv_pos, cur, window=16, k_blk=16)
    orf = ref.decode_attention(q, k, v, kv_pos, cur, window=16)
    np.testing.assert_allclose(np.array(o), np.array(orf),
                               rtol=3e-5, atol=3e-5)


def _scatter_to_pool(k, v, bs, mb, seed=0, trash_fill=0.0):
    """Scatter a contiguous [B, K, C, hd] cache into shuffled pool
    blocks.  Returns (k_pool, v_pool, table) with pool block 0 kept as
    the trash block (filled with ``trash_fill`` so any accidental
    attend to it is loud, not silently zero)."""
    B, K, C, hd = k.shape
    assert C == mb * bs
    NB = 1 + B * mb                      # block 0 = trash
    rng = np.random.default_rng(seed)
    perm = rng.permutation(np.arange(1, NB))
    table = np.zeros((B, mb), np.int32)
    k_pool = np.full((NB, bs, K, hd), trash_fill, np.float32)
    v_pool = np.full((NB, bs, K, hd), trash_fill, np.float32)
    for b in range(B):
        for j in range(mb):
            blk = int(perm[b * mb + j])
            table[b, j] = blk
            sl = np.s_[b, :, j * bs:(j + 1) * bs]
            k_pool[blk] = np.asarray(k[sl]).transpose(1, 0, 2)
            v_pool[blk] = np.asarray(v[sl]).transpose(1, 0, 2)
    return jnp.asarray(k_pool), jnp.asarray(v_pool), jnp.asarray(table)


def _paged_case(B=2, H=4, K=2, hd=16, bs=8, mb=4, tail_empty=6, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    C = mb * bs
    q = jax.random.normal(ks[0], (B, H, hd))
    k = jax.random.normal(ks[1], (B, K, C, hd))
    v = jax.random.normal(ks[2], (B, K, C, hd))
    kv_pos = jnp.broadcast_to(jnp.arange(C), (B, C))
    if tail_empty:
        kv_pos = kv_pos.at[:, C - tail_empty:].set(-1)   # unwritten tail
    cur = jnp.full((B,), C - tail_empty - 1)
    kp, vp, table = _scatter_to_pool(k, v, bs, mb, seed=seed,
                                     trash_fill=1e3)
    return q, k, v, kp, vp, table, kv_pos, cur


def test_paged_decode_attention_shim_matches_contiguous():
    """The block-table gather shim must reproduce the contiguous
    kernel bit-for-bit in math terms: scatter a contiguous cache into
    shuffled pool blocks and compare both the Pallas shim and the ops
    ref dispatch against the contiguous reference.  k_blk=16 != bs=8
    deliberately exercises the shim's re-chunking (and the contiguous
    kernel's S % k_blk padding when the extent is ragged)."""
    q, k, v, kp, vp, table, kv_pos, cur = _paged_case()
    orf = ref.decode_attention(q, k, v, kv_pos, cur)
    o_shim = dak.paged_decode_attention_shim(
        q, kp, vp, table, kv_pos, cur, k_blk=16)
    o_ops = ops.paged_decode_attention(
        q, kp, vp, table, kv_pos, cur, impl="ref")
    np.testing.assert_allclose(np.array(o_shim), np.array(orf),
                               rtol=3e-5, atol=3e-5)
    np.testing.assert_allclose(np.array(o_ops), np.array(orf),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("win", [0, 11])
def test_paged_native_byte_identical_to_shim(win):
    """The table-native kernel must be BYTE-identical to the gather
    shim at matched chunking (k_blk == block size): same online-
    softmax schedule, same float accumulation order.  This is the
    property the CI smoke gate pins; trash block 0 is filled with 1e3
    so an index_map bug shows up as a huge error, not a rounding
    blip."""
    q, k, v, kp, vp, table, kv_pos, cur = _paged_case()
    bs = kp.shape[1]
    o_nat = dak.paged_decode_attention(q, kp, vp, table, kv_pos, cur,
                                       window=win)
    o_shim = dak.paged_decode_attention_shim(
        q, kp, vp, table, kv_pos, cur, window=win, k_blk=bs)
    assert bool(jnp.all(o_nat == o_shim))
    # and close to the contiguous oracle (different chunking — not
    # byte-identical, but tight in f32)
    orf = ref.decode_attention(q, k, v, kv_pos, cur, window=win)
    np.testing.assert_allclose(np.array(o_nat), np.array(orf),
                               rtol=3e-5, atol=3e-5)


def test_paged_native_ragged_partial_table():
    """Ragged slots: each slot maps a different number of blocks; the
    unmapped table entries stay 0 (trash) and their rows must never be
    attended — validity rides entirely on kv_pos."""
    q, k, v, kp, vp, table, kv_pos, cur = _paged_case(tail_empty=0)
    B, C = kv_pos.shape
    bs = kp.shape[1]
    lens = np.array([5, 27])              # slot 0 uses 1 block, slot 1 all 4
    kv_pos = np.full((B, C), -1, np.int32)
    for b in range(B):
        kv_pos[b, :lens[b]] = np.arange(lens[b])
    kv_pos = jnp.asarray(kv_pos)
    cur = jnp.asarray(lens - 1, dtype=jnp.int32)
    # point slot 0's unused table entries at the trash block, as the
    # pool allocator does for never-reserved blocks
    table = np.asarray(table).copy()
    table[0, 1:] = 0
    table = jnp.asarray(table)
    o_nat = dak.paged_decode_attention(q, kp, vp, table, kv_pos, cur)
    o_shim = dak.paged_decode_attention_shim(
        q, kp, vp, table, kv_pos, cur, k_blk=bs)
    assert bool(jnp.all(o_nat == o_shim))
    assert bool(jnp.all(jnp.isfinite(o_nat)))
    # oracle on the contiguous view with the same masking
    orf = ref.decode_attention(q, k, v, kv_pos, cur)
    np.testing.assert_allclose(np.array(o_nat), np.array(orf),
                               rtol=3e-5, atol=3e-5)


@settings(max_examples=8, deadline=None)
@given(b=st.integers(1, 3), g=st.integers(1, 2), k=st.integers(1, 2),
       mb=st.integers(1, 4), win=st.sampled_from([0, 7]),
       seed=st.integers(0, 99))
def test_paged_native_property(b, g, k, mb, win, seed):
    """Native == shim byte-identically, and both track the oracle,
    for random pool geometries, ragged lengths, and windows."""
    H = g * k
    q, kc, vc, kp, vp, table, kv_pos, cur = _paged_case(
        B=b, H=H, K=k, hd=8, bs=4, mb=mb, tail_empty=0, seed=seed)
    C = kv_pos.shape[1]
    rng = np.random.default_rng(seed)
    lens = rng.integers(1, C + 1, size=b)
    pos = np.full((b, C), -1, np.int32)
    for i in range(b):
        pos[i, :lens[i]] = np.arange(lens[i])
    pos = jnp.asarray(pos)
    cur = jnp.asarray(lens - 1, dtype=jnp.int32)
    o_nat = dak.paged_decode_attention(q, kp, vp, table, pos, cur,
                                       window=win)
    o_shim = dak.paged_decode_attention_shim(
        q, kp, vp, table, pos, cur, window=win, k_blk=int(kp.shape[1]))
    assert bool(jnp.all(o_nat == o_shim))
    orf = ref.decode_attention(q, kc, vc, pos, cur, window=win)
    np.testing.assert_allclose(np.array(o_nat), np.array(orf),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("win", [0, 11])
def test_paged_native_reads_each_layer_of_a_stacked_pool(win):
    """On a stacked pool [L, NB, bs, K, hd] the layer-indexed kernel —
    the layer a traced scalar, as in the decode stack's layer loop —
    equals the one-pool call on ``pool[l]`` byte for byte, for every
    l; so do the gather shim and the jnp oracle given the same
    arguments.  Every layer holds other values and the trash block
    1e3, so reading a wrong layer or block shows.  Slot 0 maps two
    blocks and leaves the rest on the trash block; the slots sit at
    different positions."""
    L, B, H, K, hd, bs, mb = 3, 2, 4, 2, 16, 8, 4
    C = mb * bs
    ks = jax.random.split(jax.random.PRNGKey(3), 2 * L + 1)
    q = jax.random.normal(ks[-1], (B, H, hd))
    pools = [_scatter_to_pool(jax.random.normal(ks[2 * i], (B, K, C, hd)),
                              jax.random.normal(ks[2 * i + 1],
                                                (B, K, C, hd)),
                              bs, mb, seed=7, trash_fill=1e3)
             for i in range(L)]
    kst = jnp.stack([p[0] for p in pools])
    vst = jnp.stack([p[1] for p in pools])
    table = np.asarray(pools[0][2]).copy()
    table[0, 2:] = 0
    table = jnp.asarray(table)
    lens = np.array([13, C - 3])
    kv_pos = np.full((B, C), -1, np.int32)
    for b in range(B):
        kv_pos[b, :lens[b]] = np.arange(lens[b])
    kv_pos = jnp.asarray(kv_pos)
    cur = jnp.asarray(lens - 1, dtype=jnp.int32)

    def per_layer(fn):
        return jax.lax.map(lambda l: fn(q, kst, vst, table, kv_pos, cur,
                                        l),
                           jnp.arange(L, dtype=jnp.int32))

    o_nat = per_layer(functools.partial(dak.paged_decode_attention,
                                        window=win))
    o_shim = per_layer(functools.partial(
        dak.paged_decode_attention_shim, window=win, k_blk=bs))
    o_ref = per_layer(functools.partial(ops.paged_decode_attention,
                                        window=win, impl="ref"))
    for i in range(L):
        one = dak.paged_decode_attention(q, kst[i], vst[i], table, kv_pos,
                                         cur, window=win)
        assert bool(jnp.all(o_nat[i] == one))
        assert bool(jnp.all(o_shim[i] == dak.paged_decode_attention_shim(
            q, kst[i], vst[i], table, kv_pos, cur, window=win, k_blk=bs)))
        assert bool(jnp.all(o_ref[i] == ops.paged_decode_attention(
            q, kst[i], vst[i], table, kv_pos, cur, window=win,
            impl="ref")))
        np.testing.assert_allclose(np.array(o_nat[i]), np.array(o_ref[i]),
                                   rtol=3e-5, atol=3e-5)
    assert not bool(jnp.all(o_nat[0] == o_nat[1]))


def test_paged_native_on_a_pool_padded_past_the_head_size():
    """Pool rows zero-padded from the model's head size to a lane
    multiple (``attn.pool_head_dim``, as the paged cache stores them)
    give what the unpadded pool gives: the gather shim and the jnp
    oracle drop the pad lanes, byte for byte, and the kernel pads q
    with zeros, so the pad lanes add only zero products."""
    from repro.models import attention as attn
    q, k, v, kp, vp, table, kv_pos, cur = _paged_case(tail_empty=3)
    hdp = attn.pool_head_dim(kp.shape[-1])
    assert hdp > kp.shape[-1]
    kpp, vpp = attn.pad_head(kp, hdp), attn.pad_head(vp, hdp)
    bs = kp.shape[1]
    o_nat = dak.paged_decode_attention(q, kpp, vpp, table, kv_pos, cur)
    assert o_nat.shape == q.shape
    np.testing.assert_allclose(
        np.array(o_nat),
        np.array(dak.paged_decode_attention(q, kp, vp, table, kv_pos,
                                            cur)),
        rtol=1e-6, atol=1e-6)
    for fn in (functools.partial(dak.paged_decode_attention_shim,
                                 k_blk=bs),
               functools.partial(ops.paged_decode_attention, impl="ref")):
        assert bool(jnp.all(fn(q, kpp, vpp, table, kv_pos, cur)
                            == fn(q, kp, vp, table, kv_pos, cur)))


def test_gather_block_views_rejects_ragged_extent():
    """Regression: n_ctx % bs != 0 used to silently truncate the tail
    rows; it must raise with the offending shapes instead."""
    kp = jnp.zeros((5, 8, 2, 4))
    vp = jnp.zeros((5, 8, 2, 4))
    table = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="not a multiple"):
        dak.gather_block_views(kp, vp, table, 12)
    with pytest.raises(ValueError, match="maps only"):
        dak.gather_block_views(kp, vp, table, 24)
    q = jnp.zeros((2, 4, 4))
    with pytest.raises(ValueError, match="not a multiple"):
        dak.paged_decode_attention(q, kp, vp, table,
                                   jnp.zeros((2, 12), jnp.int32),
                                   jnp.zeros((2,), jnp.int32))


def test_interpret_default_tracks_backend():
    """interpret=None resolves through the shared runtime helper:
    interpreted off-TPU, compiled on TPU — a direct kernel call can
    never land in interpret mode on real hardware."""
    from repro.kernels import runtime
    assert runtime.resolve_interpret(None) == (not runtime.on_tpu())
    assert runtime.resolve_interpret(True) is True
    assert runtime.resolve_interpret(False) is False
    # and the kernels accept the None default end-to-end
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64))
    h, _, _ = entk.entropy_stats(x, v_blk=32)
    assert h.shape == (2,)


# ---------------------------------------------------------------------------
# ops dispatch layer
# ---------------------------------------------------------------------------

def test_ops_dispatch_ref_equals_kernel():
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 512))
    for impl in ("auto", "ref"):
        h, p, a = ops.entropy_stats(x, impl=impl)
        assert h.shape == (4,)


# ---------------------------------------------------------------------------
# SSD chunked-scan kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,hd,N,chunk", [
    (2, 24, 3, 8, 16, 8),
    (1, 40, 2, 16, 8, 16),
    (2, 33, 4, 8, 8, 8),           # ragged tail
    (1, 16, 1, 32, 32, 16),
])
def test_ssd_scan_kernel_matches_ref(B, S, H, hd, N, chunk):
    from repro.kernels import ssd_scan as ssdk
    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 5)
    x = jax.random.normal(ks[0], (B, S, H, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y_k = ssdk.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y_r = ref.ssd_scan(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.array(y_k), np.array(y_r),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(4, 40), chunk=st.sampled_from([4, 8, 16]),
       seed=st.integers(0, 99))
def test_ssd_scan_chunk_invariance(s, chunk, seed):
    """The chunk size must not change the result."""
    from repro.kernels import ssd_scan as ssdk
    B, H, hd, N = 1, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, s, H, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, s, N))
    Cm = jax.random.normal(ks[4], (B, s, N))
    y1 = ssdk.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    y2 = ssdk.ssd_scan(x, dt, A, Bm, Cm, chunk=max(s, 4))
    np.testing.assert_allclose(np.array(y1), np.array(y2),
                               rtol=2e-4, atol=2e-4)
