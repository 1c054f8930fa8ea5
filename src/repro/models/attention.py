"""Attention variants: GQA/MQA/MHA, sliding-window (local), cross, decode.

Layout convention: activations are [B, S, D]; per-head tensors are
[B, S, H, hd] ("BSHD").  KV caches are [B, S_cache, K, hd] plus an int32
position vector for ring-buffered (windowed) caches.

Full-sequence attention is *chunked over query blocks* so the scores
tensor never exceeds [B, H, q_block, S_kv] — this is the pure-jnp
production path (the Pallas flash kernel in ``repro.kernels`` is the TPU
hot-spot version and is validated against ``repro.kernels.ref``).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import nn

NEG_INF = -2.0 ** 30  # large-but-finite; avoids NaN from (-inf) - (-inf)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attn_params(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                *, bias: bool = False, dtype=jnp.float32) -> dict:
    k1, k2, k3, k4 = nn.split(key, 4)
    p = {"wq": nn.dense_init(k1, d_model, n_heads * head_dim, dtype=dtype),
         "wk": nn.dense_init(k2, d_model, n_kv * head_dim, dtype=dtype),
         "wv": nn.dense_init(k3, d_model, n_kv * head_dim, dtype=dtype),
         "wo": nn.dense_init(k4, n_heads * head_dim, d_model, dtype=dtype)}
    if bias:
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv * head_dim,), dtype)
        p["bo"] = jnp.zeros((d_model,), dtype)
    return p


def project_qkv(p: dict, x: jax.Array, n_heads: int, n_kv: int,
                head_dim: int, x_kv: jax.Array | None = None):
    """Project to q [B,S,H,hd], k/v [B,Skv,K,hd].  ``x_kv`` for cross-attn."""
    B, S, _ = x.shape
    xk = x if x_kv is None else x_kv
    Skv = xk.shape[1]
    q = x @ p["wq"]
    k = xk @ p["wk"]
    v = xk @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(B, S, n_heads, head_dim),
            k.reshape(B, Skv, n_kv, head_dim),
            v.reshape(B, Skv, n_kv, head_dim))


def out_proj(p: dict, o: jax.Array) -> jax.Array:
    B, S, H, hd = o.shape
    y = o.reshape(B, S, H * hd) @ p["wo"]
    if "bo" in p:
        y = y + p["bo"]
    return y


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------

def _gqa_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """q [B,Sq,H,hd] x k [B,Skv,K,hd] -> scores [B,K,G,Sq,Skv] (H = K*G)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    return jnp.einsum("bqkgd,bskd->bkgqs", qg * scale, k,
                      preferred_element_type=jnp.float32)


def _gqa_combine(w: jax.Array, v: jax.Array) -> jax.Array:
    """w [B,K,G,Sq,Skv] x v [B,Skv,K,hd] -> out [B,Sq,H,hd].

    The softmax weights are cast DOWN to v's dtype (bf16) rather than
    upcasting the (much larger, cache-resident) v to f32 — the flash-
    attention convention (P in bf16, f32 accumulation).  Avoiding the
    f32 cache copy cuts decode HBM traffic ~3x (§Perf iteration 1).
    """
    B, K, G, Sq, Skv = w.shape
    hd = v.shape[-1]
    o = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, K * G, hd)


def mask_bias(q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
              window: int = 0, prefix_len: jax.Array | int = 0,
              k_valid: jax.Array | None = None) -> jax.Array:
    """Additive mask [..., Sq, Skv] built from absolute positions.

    - causal:   admit k_pos <= q_pos
    - window>0: additionally require q_pos - k_pos < window
    - prefix:   positions < prefix_len are mutually visible (PaliGemma
                prefix-LM image+prompt block)
    - k_valid:  optional bool [Skv] / [B,Skv] validity (ring buffers).
    """
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if causal:
        cau = kp <= qp
        if not isinstance(prefix_len, int) or prefix_len != 0:
            pl = jnp.asarray(prefix_len)
            while pl.ndim < 2:
                pl = pl[..., None]
            # prefix tokens are mutually (bidirectionally) visible
            cau = cau | (kp < pl)
        ok = ok & cau
    if window:
        ok = ok & (qp - kp < window)
    if k_valid is not None:
        kv = k_valid[..., None, :]
        ok = ok & kv
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attend(q: jax.Array, k: jax.Array, v: jax.Array, bias: jax.Array,
           scale: float | None = None) -> jax.Array:
    """Masked GQA attention. bias broadcasts against [B,K,G,Sq,Skv]."""
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    s = _gqa_scores(q, k, scale)
    while bias.ndim < s.ndim:
        bias = bias[None]
    s = s + bias
    w = jax.nn.softmax(s, axis=-1)
    return _gqa_combine(w, v).astype(q.dtype)


# ---------------------------------------------------------------------------
# full (chunked) causal attention — prefill / training
# ---------------------------------------------------------------------------

def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     q_offset: int | jax.Array = 0, window: int = 0,
                     prefix_len: jax.Array | int = 0,
                     q_chunk: int = 1024,
                     scale: float | None = None) -> jax.Array:
    """Chunked full attention; memory O(B·H·q_chunk·Skv).

    Supports sliding-window masking (FLOPs are NOT reduced here — use
    ``local_attention`` for the sub-quadratic path) and prefix-LM.
    """
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(Skv)
    if Sq <= q_chunk:
        bias = mask_bias(q_pos, k_pos, causal=True, window=window,
                         prefix_len=prefix_len)
        return attend(q, k, v, bias, scale)

    # static python loop over query chunks: bounds the scores tensor to
    # [B,H,q_chunk,Skv] AND keeps every FLOP visible to cost_analysis
    # (a lax.map would hide all but one trip inside a while loop).
    n = -(-Sq // q_chunk)
    pad = n * q_chunk - Sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, pad))
    outs = []
    for i in range(n):
        qc = qp[:, i * q_chunk:(i + 1) * q_chunk]
        pc = qpos[i * q_chunk:(i + 1) * q_chunk]
        bias = mask_bias(pc, k_pos, causal=True, window=window,
                         prefix_len=prefix_len)
        outs.append(attend(qc, k, v, bias, scale))
    out = jnp.concatenate(outs, axis=1)
    return out[:, :Sq]


# ---------------------------------------------------------------------------
# sub-quadratic local (sliding-window) attention — prefill / training
# ---------------------------------------------------------------------------

def local_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    window: int, q_offset: int = 0,
                    scale: float | None = None) -> jax.Array:
    """Blocked sliding-window attention, FLOPs O(S · 2·window).

    Queries in block i attend to keys in blocks i-1 and i with a causal
    + window mask, giving an effective receptive field in
    [window, 2·window).  Sequence is padded to a block multiple.
    """
    B, S, H, hd = q.shape
    w = window
    n = -(-S // w)
    pad = n * w - S

    def blockify(x):
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return x.reshape(B, n, w, x.shape[2], hd)

    qb, kb, vb = blockify(q), blockify(k), blockify(v)
    # keys for block i: [block i-1 ; block i]
    kprev = jnp.pad(kb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :n]
    vprev = jnp.pad(vb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :n]
    k2 = jnp.concatenate([kprev, kb], axis=2)          # [B,n,2w,K,hd]
    v2 = jnp.concatenate([vprev, vb], axis=2)

    pos = jnp.arange(n * w).reshape(n, w) + q_offset
    kpos = jnp.concatenate([pos - w, pos], axis=1)         # [n, 2w]

    # static unroll over blocks (see causal_attention for rationale)
    outs = []
    for i in range(n):
        valid = jnp.concatenate(
            [jnp.full((w,), i > 0, bool), jnp.ones((w,), bool)])
        bias = mask_bias(pos[i], kpos[i], causal=True, window=w,
                         k_valid=valid)
        outs.append(attend(qb[:, i], k2[:, i], v2[:, i], bias, scale))
    out = jnp.concatenate(outs, axis=1)
    return out[:, :S]


# ---------------------------------------------------------------------------
# fused-kernel dispatch (repro.kernels) — BSHD layout shims
# ---------------------------------------------------------------------------

def causal_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            window: int = 0, q_offset: int = 0,
                            impl: str = "auto") -> jax.Array:
    """Full causal attention through ``kops.flash_attention``.

    The model speaks BSHD (q [B,S,H,hd], k/v [B,Skv,K,hd]); the kernel
    speaks BHSD — two transposes at the boundary buy the fused online-
    softmax kernel on TPU (``impl='auto'`` falls back to the jnp
    oracle elsewhere).  Window masking matches ``mask_bias``
    (q_pos - k_pos < window)."""
    from repro.kernels import ops as kops
    o = kops.flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, window=window,
        q_offset=q_offset, impl=impl)
    return o.transpose(0, 2, 1, 3)


def decode_attend_kernel(q: jax.Array, cache: "KVCache", *,
                         pos: jax.Array, window: int = 0,
                         impl: str = "auto") -> jax.Array:
    """One-token attention via ``kops.decode_attention`` (the flash-
    decode kernel: KV streamed through VMEM, online softmax, per-slot
    absolute positions so ring-buffered windows just work).

    q [B,1,H,hd]; ``pos`` scalar (lockstep) or [B] (continuous
    batching).  Same validity rule as :func:`decode_attend`."""
    from repro.kernels import ops as kops
    B = q.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    cur = jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos
    o = kops.decode_attention(
        q[:, 0], cache.k.transpose(0, 2, 1, 3),
        cache.v.transpose(0, 2, 1, 3), cache.pos, cur,
        window=window, impl=impl)
    return o[:, None]


# ---------------------------------------------------------------------------
# KV cache (full or ring-buffered) + decode step
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: jax.Array          # [B, C, K, hd]   C = min(max_seq, window or inf)
    v: jax.Array          # [B, C, K, hd]
    pos: jax.Array        # [B, C] int32 absolute position held in each slot
    length: jax.Array     # [] int32 — number of tokens processed so far


def init_kv_cache(batch: int, max_seq: int, n_kv: int, head_dim: int,
                  *, window: int = 0, dtype=jnp.bfloat16) -> KVCache:
    C = min(max_seq, window) if window else max_seq
    return KVCache(
        k=jnp.zeros((batch, C, n_kv, head_dim), dtype),
        v=jnp.zeros((batch, C, n_kv, head_dim), dtype),
        pos=jnp.full((batch, C), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32))


def cache_write(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                start: jax.Array | int) -> KVCache:
    """Write S_new tokens starting at absolute position ``start``.

    ``start`` may be a scalar (lockstep decode / prefill) or a [B]
    vector (continuous batching: every slot at its own position).
    Full caches write at [start, start+S); ring caches (C < needed)
    write modulo C.  For prefill into a ring we only keep the last C
    tokens (earlier writes are overwritten anyway once S_new >= C).
    """
    B, C, K, hd = cache.k.shape
    S_new = k_new.shape[1]
    start = jnp.asarray(start, jnp.int32)
    steps = jnp.arange(S_new, dtype=jnp.int32)
    if start.ndim == 0:
        idx = (start + steps) % C                                # [S_new]
        k = cache.k.at[:, idx].set(k_new.astype(cache.k.dtype))
        v = cache.v.at[:, idx].set(v_new.astype(cache.v.dtype))
        pos = cache.pos.at[:, idx].set(start + steps)
        return KVCache(k=k, v=v, pos=pos, length=start + S_new)
    # per-row start positions
    idx = (start[:, None] + steps[None, :]) % C                  # [B,S]
    b = jnp.arange(B, dtype=jnp.int32)[:, None]
    k = cache.k.at[b, idx].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[b, idx].set(v_new.astype(cache.v.dtype))
    pos = cache.pos.at[b, idx].set(start[:, None] + steps[None, :])
    return KVCache(k=k, v=v, pos=pos,
                   length=jnp.max(start) + S_new)


# ---------------------------------------------------------------------------
# paged KV pool (vLLM-style): block pool + per-slot block table
# ---------------------------------------------------------------------------
#
# The paged layout reuses the :class:`KVCache` container with a
# different shape convention so cache pytrees stay structurally
# identical to the contiguous layout (slot scatters are plain
# ``tree_map``-free indexed writes either way):
#
#   k, v  [NB, bs, K, hdp]  one physical pool of NB blocks of bs rows,
#                           shared by every slot (block 0 is reserved
#                           as the trash block — writes by retired
#                           slots land there harmlessly); the head axis
#                           is padded to hdp = pool_head_dim(hd)
#   pos   [B, C]            per-slot LOGICAL validity/position array,
#                           C = max_blocks_per_slot * bs (-1 = empty);
#                           identical semantics to the contiguous pos
#   length []               bookkeeping scalar, as contiguous
#
# A per-slot block table [B, MB] int32 (carried on the enclosing
# ``transformer.Cache``) maps logical block j of slot b to a physical
# pool block; unmapped entries point at the trash block and are
# excluded by the pos validity mask, never by the table itself.
#
# The functions below take one layer's cache, or, with ``layer``
# given, the stacked cache of a layer stack (k, v [L, NB, bs, K, hdp],
# pos [L, B, C], length [L]) and the index of the layer they act on:
# the decode stack carries the stacked pool and writes and reads it in
# place, so no layer slices it out or stacks it back.

_LANES = 128


def pool_head_dim(head_dim: int) -> int:
    """Head size of a pool row: ``head_dim`` rounded up to a multiple
    of 128 lanes.  A Pallas operand must be row-major, and a TPU lays
    out a pool whose minor dim is not a lane multiple otherwise (a head
    dim of 80 puts the block axis minor-most), so every program that
    took or returned such a pool would convert both whole pools at its
    boundary.  Padded, the default layout is row-major, in the same
    bytes: row-major tiles pad 80 lanes to 128 anyway.  The pad lanes
    hold zeros and add nothing: the kernel pads ``q`` with zeros and
    the gather path drops them."""
    return -(-head_dim // _LANES) * _LANES


def pad_head(x: jax.Array, n: int) -> jax.Array:
    """``x`` with its last (head) axis zero-padded to ``n`` lanes."""
    pad = n - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def init_paged_kv_cache(batch: int, logical_len: int, n_kv: int,
                        head_dim: int, *, n_blocks: int, block_size: int,
                        dtype=jnp.bfloat16) -> KVCache:
    """Pool-layout KVCache: ``n_blocks`` x ``block_size`` rows shared
    by ``batch`` slots whose logical extent is ``logical_len`` rows;
    rows of ``pool_head_dim(head_dim)`` lanes."""
    hdp = pool_head_dim(head_dim)
    return KVCache(
        k=jnp.zeros((n_blocks, block_size, n_kv, hdp), dtype),
        v=jnp.zeros((n_blocks, block_size, n_kv, hdp), dtype),
        pos=jnp.full((batch, logical_len), -1, jnp.int32),
        length=jnp.zeros((), jnp.int32))


def paged_cache_write(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                      pos, block_table: jax.Array, block_size: int,
                      layer=None) -> KVCache:
    """Write ONE token per slot at its own absolute position.

    k_new/v_new [B, 1, K, hd]; ``pos`` scalar or [B]; the physical row
    is ``(block_table[b, pos_b // bs], pos_b % bs)`` — of layer
    ``layer`` when the cache is stacked, one scatter into the stack.
    Slots whose table row points at the trash block (retired slots
    still being stepped inside a fused window) write there harmlessly;
    their pos entry is per-slot and reset at the next prefill."""
    B = k_new.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    posv = jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos
    b = jnp.arange(B, dtype=jnp.int32)
    blk = block_table[b, posv // block_size]            # [B]
    off = posv % block_size
    at = () if layer is None else (layer,)
    hdp = cache.k.shape[-1]
    k = cache.k.at[at + (blk, off)].set(
        pad_head(k_new[:, 0], hdp).astype(cache.k.dtype))
    v = cache.v.at[at + (blk, off)].set(
        pad_head(v_new[:, 0], hdp).astype(cache.v.dtype))
    p = cache.pos.at[at + (b, posv)].set(posv, mode="drop")
    length = jnp.max(posv) + 1
    if layer is not None:
        length = cache.length.at[layer].set(length)
    return KVCache(k=k, v=v, pos=p, length=length)


def _layer_pos(cache: KVCache, layer) -> jax.Array:
    """The [B, C] position rows of layer ``layer`` (or of the one
    layer an unstacked cache holds)."""
    return cache.pos if layer is None else cache.pos[layer]


def paged_gather(cache: KVCache, block_table: jax.Array, layer=None,
                 head_dim: int | None = None) -> KVCache:
    """Materialise each slot's logical [B, C, K, hd] view of the pool
    (gather over the block table).  The result is a CONTIGUOUS-layout
    KVCache, so every downstream consumer (``decode_attend``, the
    gather-shim flash-decode path) runs unchanged on it.  The serving
    hot path no longer needs this — the table-native kernel reads the
    pool in place — but the table indexing stays single-sourced in
    ``repro.kernels.decode_attention.gather_block_views``.  Rows keep
    their first ``head_dim`` lanes (all of them when ``None``)."""
    from repro.kernels.decode_attention import gather_block_views
    kv_pos = _layer_pos(cache, layer)
    k, v = gather_block_views(cache.k, cache.v, block_table,
                              kv_pos.shape[1], 0 if layer is None
                              else layer, head_dim=head_dim)
    return KVCache(k=k, v=v, pos=kv_pos, length=cache.length)


def paged_decode_attend(q: jax.Array, cache: KVCache,
                        block_table: jax.Array, *, pos: jax.Array,
                        window: int = 0, scale: float | None = None,
                        layer=None) -> jax.Array:
    """One-token attention over the slot's mapped blocks (jnp path).

    Validity comes from the per-slot ``pos`` array exactly as in the
    contiguous layout — unmapped blocks are never valid because their
    logical rows were never written."""
    kv = paged_gather(cache, block_table, layer, head_dim=q.shape[-1])
    return decode_attend(q, kv, pos=pos, window=window, scale=scale)


def paged_decode_attend_kernel(q: jax.Array, cache: KVCache,
                               block_table: jax.Array, *,
                               pos: jax.Array, window: int = 0,
                               impl: str = "auto",
                               layer=None) -> jax.Array:
    """One-token paged attention through the block-table-aware
    ``kops.paged_decode_attention`` dispatch: the TABLE-NATIVE
    flash-decode kernel on TPU (block table scalar-prefetched, pool
    read in place), the jnp oracle elsewhere; ``impl="shim"`` keeps
    the materialised-gather parity oracle reachable."""
    from repro.kernels import ops as kops
    B = q.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    cur = jnp.broadcast_to(pos, (B,)) if pos.ndim == 0 else pos
    o = kops.paged_decode_attention(
        q[:, 0], cache.k, cache.v, block_table, _layer_pos(cache, layer),
        cur, 0 if layer is None else layer, window=window, impl=impl)
    return o[:, None]


def cache_write_chunk(cache: KVCache, k_new: jax.Array, v_new: jax.Array,
                      start: jax.Array) -> KVCache:
    """Write S tokens per row at per-row absolute ``start`` positions
    WITHOUT ring wrap-around (the speculative verify write).

    Unlike :func:`cache_write`, rows past the cache extent are CLAMPED
    onto the last row instead of wrapping modulo C — a draft chunk
    issued near the ``max_seq`` stop must never overwrite a slot's
    early prompt rows.  The spill row's ``pos`` entry lands >= C-1,
    and the engine's emission guard keeps every query position < C-1,
    so the spill is never attended."""
    B, C, K, hd = cache.k.shape
    S = k_new.shape[1]
    start = jnp.asarray(start, jnp.int32)
    posm = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None]  # [B,S]
    idx = jnp.minimum(posm, C - 1)
    b = jnp.arange(B, dtype=jnp.int32)[:, None]
    k = cache.k.at[b, idx].set(k_new.astype(cache.k.dtype))
    v = cache.v.at[b, idx].set(v_new.astype(cache.v.dtype))
    pos = cache.pos.at[b, idx].set(posm)
    return KVCache(k=k, v=v, pos=pos, length=jnp.max(posm) + 1)


def chunk_attend(q: jax.Array, cache: KVCache, *, qpos: jax.Array,
                 window: int = 0, scale: float | None = None) -> jax.Array:
    """Multi-token decode attention (the speculative verify step).

    q: [B, S, H, hd] with per-query absolute positions ``qpos``
    [B, S]; the validity rule is exactly :func:`decode_attend`'s
    (k_pos >= 0 and k_pos <= q_pos, windowed if asked), applied per
    query row — at S == 1 this degenerates to ``decode_attend``."""
    qpos = jnp.asarray(qpos, jnp.int32)
    k_pos = cache.pos[:, None, :]            # [B,1,C]
    valid = (k_pos >= 0) & (k_pos <= qpos[..., None])
    if window:
        valid = valid & (qpos[..., None] - k_pos < window)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    bias = bias[:, None, None]               # [B,1,1,S,C] vs [B,K,G,S,C]
    return attend(q, cache.k, cache.v, bias, scale)


def decode_attend(q: jax.Array, cache: KVCache, *, pos: jax.Array,
                  window: int = 0, scale: float | None = None) -> jax.Array:
    """One-token attention against the cache.

    q: [B, 1, H, hd]; ``pos`` is the new token's absolute position —
    scalar (lockstep) or [B] (continuous batching).
    """
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        pos = pos[:, None]                  # [B,1] vs k_pos [B,C]
    k_pos = cache.pos                       # [B, C]
    valid = (k_pos >= 0) & (k_pos <= pos)
    if window:
        valid = valid & (pos - k_pos < window)
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    bias = bias[:, None, None, None, :]     # [B,1,1,1,C] vs [B,K,G,1,C]
    return attend(q, cache.k, cache.v, bias, scale)
