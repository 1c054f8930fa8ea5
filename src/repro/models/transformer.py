"""Unified decoder LM covering all assigned families.

One generic stack: per-layer temporal mixing (GQA attention, sliding
window attention, MLA, RG-LRU, Mamba-2 SSD) + channel mixing
(SwiGLU MLP or MoE), pre-norm residual blocks, tied or untied unembed.

Homogeneous stacks (llama/internlm/stablelm/minicpm/mamba/moe archs) are
scanned with ``jax.lax.scan`` over stacked layer params (small HLO, fast
multi-device compile); heterogeneous stacks (recurrentgemma's 2:1
recurrent:attention pattern) unroll a Python loop.

Three execution modes share the same layer code:
  - ``forward``      full sequence, no cache (training)
  - ``prefill``      full sequence, writes the decode cache
  - ``decode_step``  one token against the cache
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import nn
from repro.models import rglru as rglru_mod
from repro.models import ssd as ssd_mod
from repro.kernels.runtime import on_tpu


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def _mla_cfg(cfg: ModelConfig) -> mla_mod.MLAConfig:
    return mla_mod.MLAConfig(
        n_heads=cfg.n_heads, q_lora_rank=cfg.q_lora_rank,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_dim=cfg.qk_nope_dim,
        qk_rope_dim=cfg.qk_rope_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def init_layer(cfg: ModelConfig, kind: str, key) -> dict:
    d = cfg.d_model
    dt = _dtype(cfg)
    k_mix, k_mlp = nn.split(key, 2)
    p: dict[str, Any] = {"norm1": nn.norm_params(cfg.norm, d)}
    if kind in ("attn", "local_attn"):
        p["mix"] = attn.attn_params(k_mix, d, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, bias=cfg.qkv_bias, dtype=dt)
    elif kind == "mla":
        p["mix"] = mla_mod.mla_params(k_mix, d, _mla_cfg(cfg), dtype=dt)
    elif kind == "rglru":
        p["mix"] = rglru_mod.rglru_params(k_mix, d, cfg.lru_width or d,
                                          cfg.conv_width, dtype=dt)
    elif kind == "ssd":
        p["mix"] = ssd_mod.ssd_params(
            k_mix, d, expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
            d_state=cfg.ssm_state, conv_width=cfg.ssm_conv, dtype=dt)
    else:
        raise ValueError(kind)
    if cfg.is_moe:
        p["norm2"] = nn.norm_params(cfg.norm, d)
        p["moe"] = moe_mod.moe_params(k_mlp, d, cfg.n_experts,
                                      cfg.d_ff_expert, dtype=dt)
    elif cfg.d_ff:
        p["norm2"] = nn.norm_params(cfg.norm, d)
        if cfg.act == "gelu_mlp":
            p["mlp"] = nn.mlp_params(k_mlp, d, cfg.d_ff, dtype=dt)
        else:
            p["mlp"] = nn.swiglu_params(k_mlp, d, cfg.d_ff, dtype=dt)
    return p


def init_lm(cfg: ModelConfig, key) -> dict:
    dt = _dtype(cfg)
    keys = nn.split(key, cfg.n_layers + 4)
    params: dict[str, Any] = {
        "emb": nn.embed_init(keys[0], cfg.vocab, cfg.d_model, dtype=dt),
        "final_norm": nn.norm_params(cfg.norm, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["unemb"] = nn.dense_init(keys[1], cfg.d_model, cfg.vocab,
                                        dtype=dt)
    kinds = cfg.block_kinds
    if cfg.homogeneous:
        per = [init_layer(cfg, kinds[0], keys[2 + i])
               for i in range(cfg.n_layers)]
        params["layers"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per)
    else:
        params["layers"] = [init_layer(cfg, kinds[i], keys[2 + i])
                            for i in range(cfg.n_layers)]
    if cfg.family == "encdec":
        params["encoder"] = _init_encoder(cfg, keys[-1])
        params["xattn"] = _init_xattn(cfg, keys[-2])
    return params


def _init_encoder(cfg: ModelConfig, key) -> dict:
    """Whisper-style bidirectional encoder over (stubbed) frame embeds."""
    ed = cfg.enc_d_model or cfg.d_model
    keys = nn.split(key, cfg.n_enc_layers + 1)
    per = []
    for i in range(cfg.n_enc_layers):
        k1, k2 = nn.split(keys[i], 2)
        per.append({
            "norm1": nn.norm_params(cfg.norm, ed),
            "mix": attn.attn_params(k1, ed, cfg.n_heads, cfg.n_kv_heads,
                                    cfg.head_dim, bias=cfg.qkv_bias,
                                    dtype=_dtype(cfg)),
            "norm2": nn.norm_params(cfg.norm, ed),
            "mlp": nn.mlp_params(k2, ed, cfg.d_ff, dtype=_dtype(cfg)),
        })
    return {"layers": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per),
            "final_norm": nn.norm_params(cfg.norm, ed)}


def _init_xattn(cfg: ModelConfig, key) -> dict:
    """Per-decoder-layer cross-attention params (stacked)."""
    keys = nn.split(key, cfg.n_layers)
    per = []
    for i in range(cfg.n_layers):
        per.append({
            "norm": nn.norm_params(cfg.norm, cfg.d_model),
            "mix": attn.attn_params(keys[i], cfg.d_model, cfg.n_heads,
                                    cfg.n_kv_heads, cfg.head_dim,
                                    bias=cfg.qkv_bias, dtype=_dtype(cfg)),
        })
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

class LayerCache(NamedTuple):
    """Union cache — exactly one field is meaningful per layer kind."""
    kv: Any = None        # attn.KVCache | mla.MLACache
    rec: Any = None       # rglru.RGLRUState | ssd.SSDState


def init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                     dtype=jnp.bfloat16) -> LayerCache:
    if kind == "attn":
        return LayerCache(kv=attn.init_kv_cache(
            batch, max_seq, cfg.n_kv_heads, cfg.head_dim, dtype=dtype))
    if kind == "local_attn":
        return LayerCache(kv=attn.init_kv_cache(
            batch, max_seq, cfg.n_kv_heads, cfg.head_dim,
            window=cfg.window, dtype=dtype))
    if kind == "mla":
        return LayerCache(kv=mla_mod.init_mla_cache(
            batch, max_seq, _mla_cfg(cfg), dtype=dtype))
    if kind == "rglru":
        return LayerCache(rec=rglru_mod.init_rglru_state(
            batch, cfg.lru_width or cfg.d_model, cfg.conv_width))
    if kind == "ssd":
        return LayerCache(rec=ssd_mod.init_ssd_state(
            batch, cfg.d_model, expand=cfg.ssm_expand,
            headdim=cfg.ssm_headdim, d_state=cfg.ssm_state,
            conv_width=cfg.ssm_conv))
    raise ValueError(kind)


class Cache(NamedTuple):
    layers: Any                       # stacked LayerCache or list
    cross: Any = None                 # encdec: (k, v) [L,B,Senc,K,hd]
    length: jax.Array | None = None   # [] int32 tokens consumed
    block_table: Any = None           # paged pool only: [B, MB] int32
                                      # slot -> physical block map
                                      # (shared by every layer)


def paged_geometry(cfg: ModelConfig, batch: int,
                   max_seq: int) -> tuple[int, int, int]:
    """(blocks_per_slot, logical_len, pool_blocks) for a paged cache.

    ``pool_blocks`` honours ``cfg.kv_pool_blocks`` when set; the
    default sizes the pool for capacity parity with the contiguous
    layout (every slot can map its full logical extent) plus the
    reserved trash block 0."""
    bs = cfg.kv_block_size
    if bs <= 0:
        raise ValueError(
            "paged cache geometry needs cfg.kv_block_size > 0 "
            f"(got {bs}) — set it, or use the contiguous layout")
    mb = -(-max_seq // bs)
    nb = cfg.kv_pool_blocks or (batch * mb + 1)
    return mb, mb * bs, nb


def _check_paged_supported(cfg: ModelConfig) -> None:
    kinds = set(cfg.block_kinds)
    if kinds != {"attn"} or cfg.family == "encdec":
        raise ValueError(
            f"paged KV pool (kv_block_size={cfg.kv_block_size}) only "
            f"supports homogeneous full-attention stacks; got block "
            f"kinds {sorted(kinds)} (family={cfg.family!r}).  Windowed "
            f"ring caches and recurrent states are constant-size per "
            f"slot already — run them on the contiguous layout.")


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, *, layout: str = "auto") -> Cache:
    """Decode cache for ``batch`` slots of up to ``max_seq`` tokens.

    ``layout='auto'`` follows ``cfg.kv_block_size`` (paged when > 0);
    ``'contiguous'``/``'paged'`` force it — the continuous engine
    forces contiguous ROW caches for prefill even when the pool it
    scatters them into is paged."""
    if layout not in ("auto", "contiguous", "paged"):
        raise ValueError(f"unknown cache layout {layout!r}")
    paged = (cfg.paged_kv if layout == "auto" else layout == "paged")
    if paged:
        _check_paged_supported(cfg)
        mb, logical, nb = paged_geometry(cfg, batch, max_seq)
        one = attn.init_paged_kv_cache(
            batch, logical, cfg.n_kv_heads, cfg.head_dim, n_blocks=nb,
            block_size=cfg.kv_block_size, dtype=dtype)
        # made stacked in one step: stacking per-layer pools would hold
        # two whole pools at once
        kv = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)), one)
        return Cache(layers=LayerCache(kv=kv), cross=None,
                     length=jnp.zeros((), jnp.int32),
                     block_table=jnp.zeros((batch, mb), jnp.int32))
    kinds = cfg.block_kinds
    if cfg.homogeneous:
        per = [init_layer_cache(cfg, kinds[0], batch, max_seq, dtype)
               for _ in range(cfg.n_layers)]
        layers = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)
    else:
        layers = [init_layer_cache(cfg, k, batch, max_seq, dtype)
                  for k in kinds]
    cross = None
    if cfg.family == "encdec":
        shape = (cfg.n_layers, batch, cfg.enc_seq, cfg.n_kv_heads,
                 cfg.head_dim)
        cross = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
    return Cache(layers=layers, cross=cross,
                 length=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _channel_mix(cfg: ModelConfig, p: dict, h: jax.Array):
    aux = jnp.zeros((), jnp.float32)
    if cfg.is_moe:
        y, aux = moe_mod.moe_forward(
            p["moe"], nn.apply_norm(cfg.norm, p["norm2"], h),
            top_k=cfg.top_k, capacity_factor=cfg.capacity_factor)
    elif cfg.d_ff:
        hn = nn.apply_norm(cfg.norm, p["norm2"], h)
        if cfg.act == "gelu_mlp":
            y = nn.mlp(p["mlp"], hn)
        else:
            act = nn.gelu if cfg.act == "gelu" else jax.nn.silu
            y = nn.swiglu(p["mlp"], hn, act=act)
    else:
        return h, aux
    return h + y, aux


def _attn_mix(cfg: ModelConfig, kind: str, p: dict, x: jax.Array, *,
              mode: str, lc: LayerCache, pos, prefix_len,
              block_table=None, layer=None):
    """Temporal mixing for attn/local_attn. Returns (y, new LayerCache).

    ``block_table`` is non-None only on the paged decode path: the KV
    leaves are then pool-layout — the whole stack's ([L, NB, bs, K,
    hdp]) when ``layer`` is given — and both the single-token write and
    the attention read of layer ``layer`` go through the slot's
    block-table row."""
    window = cfg.window if kind == "local_attn" else 0
    rd = int(cfg.head_dim * cfg.rope_pct)
    # kernel dispatch: ``attn_impl="auto"`` resolves HERE, not inside
    # kops — on TPU it routes through the fused flash / flash-decode
    # kernels; elsewhere the model keeps its own einsum path, bitwise-
    # identical to ``attn_impl="xla"``.  That invariant is load-bearing:
    # the speculative verify chunk (S > 1) has no kernel form, so
    # spec/non-spec byte parity needs step decode and chunk verify to
    # share numerics exactly.  Explicit "ref"/"pallas" always take the
    # kops route (oracle / forced kernel — validation paths).  The
    # prefix-LM mask is jnp-only, so prefix batches stay on the
    # chunked path regardless of the flag.
    use_kernel = (cfg.attn_impl != "xla"
                  and isinstance(prefix_len, int) and prefix_len == 0
                  and (cfg.attn_impl != "auto" or on_tpu()))
    if mode in ("full", "prefill"):
        B, S, _ = x.shape
        positions = jnp.arange(S)
        q, k, v = attn.project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.head_dim)
        q = nn.apply_rope(q, positions, cfg.rope_theta, rotary_dim=rd)
        k = nn.apply_rope(k, positions, cfg.rope_theta, rotary_dim=rd)
        if use_kernel:
            o = attn.causal_attention_kernel(q, k, v, window=window,
                                             impl=cfg.attn_impl)
        elif window and S > window:
            o = attn.local_attention(q, k, v, window=window)
        else:
            o = attn.causal_attention(q, k, v, window=window,
                                      prefix_len=prefix_len)
        new_lc = lc
        if mode == "prefill":
            new_lc = LayerCache(kv=attn.cache_write(lc.kv, k, v, 0),
                                rec=lc.rec)
        return attn.out_proj(p, o), new_lc
    # decode: x [B,1,D]; pos scalar (lockstep) or [B] (continuous)
    q, k, v = attn.project_qkv(p, x, cfg.n_heads, cfg.n_kv_heads,
                               cfg.head_dim)
    S = x.shape[1]
    if S > 1:
        # chunked decode (speculative verify): S tokens per row, each
        # row starting at its own absolute position.  Contiguous-only
        # — the paged pool's one-row-per-step write cannot express a
        # multi-token scatter, so paged engines serve draft_depth == 0.
        if block_table is not None:
            raise ValueError(
                "chunked decode (speculative verify) supports the "
                "contiguous KV layout only; run the paged pool with "
                "draft_depth == 0")
        starts = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                                  (x.shape[0],))
        posm = starts[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        q = nn.apply_rope(q, posm, cfg.rope_theta, rotary_dim=rd)
        k = nn.apply_rope(k, posm, cfg.rope_theta, rotary_dim=rd)
        kv = attn.cache_write_chunk(lc.kv, k, v, starts)
        o = attn.chunk_attend(q, kv, qpos=posm, window=window)
        return attn.out_proj(p, o), LayerCache(kv=kv, rec=lc.rec)
    posv = jnp.asarray(pos, jnp.int32)
    posv = posv[None] if posv.ndim == 0 else posv[:, None]
    q = nn.apply_rope(q, posv, cfg.rope_theta, rotary_dim=rd)
    k = nn.apply_rope(k, posv, cfg.rope_theta, rotary_dim=rd)
    if block_table is not None:
        kv = attn.paged_cache_write(lc.kv, k, v, pos, block_table,
                                    cfg.kv_block_size, layer)
        if use_kernel:
            o = attn.paged_decode_attend_kernel(
                q, kv, block_table, pos=pos, window=window,
                impl=cfg.attn_impl, layer=layer)
        else:
            o = attn.paged_decode_attend(q, kv, block_table, pos=pos,
                                         window=window, layer=layer)
        return attn.out_proj(p, o), LayerCache(kv=kv, rec=lc.rec)
    kv = attn.cache_write(lc.kv, k, v, pos)
    if use_kernel:
        o = attn.decode_attend_kernel(q, kv, pos=pos, window=window,
                                      impl=cfg.attn_impl)
    else:
        o = attn.decode_attend(q, kv, pos=pos, window=window)
    return attn.out_proj(p, o), LayerCache(kv=kv, rec=lc.rec)


def _mla_mix(cfg: ModelConfig, p: dict, x: jax.Array, *, mode: str,
             lc: LayerCache, pos):
    m = _mla_cfg(cfg)
    if mode == "full":
        return mla_mod.mla_attention(p, m, x), lc
    if mode == "prefill":
        y = mla_mod.mla_attention(p, m, x)
        kv = mla_mod.mla_cache_write(p, m, lc.kv, x, 0)
        return y, LayerCache(kv=kv, rec=lc.rec)
    y, kv = mla_mod.mla_decode(p, m, x, lc.kv, pos=pos)
    return y, LayerCache(kv=kv, rec=lc.rec)


def _rec_mix(cfg: ModelConfig, kind: str, p: dict, x: jax.Array, *,
             mode: str, lc: LayerCache):
    single = mode == "decode"
    if kind == "rglru":
        y, st = rglru_mod.rglru_block(p, x, lc.rec, single_step=single)
    else:
        y, st = ssd_mod.ssd_block(p, x, lc.rec, expand=cfg.ssm_expand,
                                  headdim=cfg.ssm_headdim,
                                  d_state=cfg.ssm_state, chunk=cfg.ssm_chunk,
                                  single_step=single)
    new_rec = st if mode != "full" else lc.rec
    return y, LayerCache(kv=lc.kv, rec=new_rec)


def apply_layer(cfg: ModelConfig, kind: str, p: dict, h: jax.Array, *,
                mode: str, lc: LayerCache, pos=0, prefix_len=0,
                xattn=None, cross_kv=None, block_table=None, layer=None):
    """One residual block: temporal mix + optional cross-attn + channel.
    ``layer`` indexes a stacked paged pool in ``lc`` (paged decode)."""
    hn = nn.apply_norm(cfg.norm, p["norm1"], h)
    if kind in ("attn", "local_attn"):
        y, new_lc = _attn_mix(cfg, kind, p["mix"], hn, mode=mode, lc=lc,
                              pos=pos, prefix_len=prefix_len,
                              block_table=block_table, layer=layer)
    elif kind == "mla":
        y, new_lc = _mla_mix(cfg, p["mix"], hn, mode=mode, lc=lc, pos=pos)
    else:
        y, new_lc = _rec_mix(cfg, kind, p["mix"], hn, mode=mode, lc=lc)
    h = h + y

    if xattn is not None:
        hx = nn.apply_norm(cfg.norm, xattn["norm"], h)
        ck, cv = cross_kv                              # [B,Senc,K,hd]
        B, S, _ = hx.shape
        q = (hx @ xattn["mix"]["wq"]).reshape(B, S, cfg.n_heads,
                                              cfg.head_dim)
        if "bq" in xattn["mix"]:
            q = q + xattn["mix"]["bq"].reshape(cfg.n_heads, cfg.head_dim)
        bias = jnp.zeros((1, 1, 1, 1, ck.shape[1]), jnp.float32)
        o = attn.attend(q, ck, cv, bias)
        h = h + attn.out_proj(xattn["mix"], o)

    h, aux = _channel_mix(cfg, p, h)
    return h, new_lc, aux


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------

def _run_stack(cfg: ModelConfig, params: dict, h: jax.Array, *, mode: str,
               cache_layers, pos=0, prefix_len=0, cross=None,
               block_table=None):
    """Run all layers; returns (h, new_cache_layers, aux_sum).

    ``mode='full'`` carries no cache (recurrent layers start from zero
    state built inside the layer body); prefill/decode thread the cache
    through the scan as per-layer xs/ys.  ``block_table`` (paged
    decode) is one [B, MB] map shared by every layer — it enters the
    scan body as a captured constant, not a scanned-over leaf — and
    the stacked pool rides the scan's carry instead: each layer
    scatters its token into the stack at its own index and the kernel
    reads that layer in place, so no layer slices the pool out of the
    stack or writes it back.
    """
    kinds = cfg.block_kinds
    remat = cfg.remat and mode == "full" and cfg.remat_policy != "none"
    if remat:
        # "full": recompute everything between layer boundaries;
        # "dots": save matmul/einsum outputs, recompute only
        # elementwise chains (trades HBM for far fewer recompute
        # FLOPs+bytes — §Perf pair F)
        policy = (None if cfg.remat_policy == "full" else
                  jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        ckpt = (jax.checkpoint if policy is None else
                (lambda f: jax.checkpoint(f, policy=policy)))
    batch = h.shape[0]

    if block_table is not None:
        # paged decode: a homogeneous attention stack, never remat'd
        def paged_body(carry, xs):
            hh, kv = carry
            lp, layer = xs
            hh, new_lc, aux = apply_layer(cfg, kinds[0], lp, hh,
                                          mode=mode, lc=LayerCache(kv=kv),
                                          pos=pos, block_table=block_table,
                                          layer=layer)
            return (hh, new_lc.kv), aux

        layers = jnp.arange(cache_layers.kv.k.shape[0], dtype=jnp.int32)
        (h, kv), aux = jax.lax.scan(
            paged_body, (h, cache_layers.kv), (params["layers"], layers),
            unroll=cfg.n_layers if cfg.scan_unroll else 1)
        return h, LayerCache(kv=kv), jnp.sum(aux)

    if cfg.homogeneous:
        kind = kinds[0]
        xattn = params.get("xattn")

        def body(hh, xs):
            if mode == "full":
                lp = xs[0] if isinstance(xs, tuple) else xs
                xa, ckv = (xs[1], xs[2]) if (isinstance(xs, tuple)
                                             and len(xs) == 3) else (None,
                                                                     None)
                lc = init_layer_cache(cfg, kind, batch, 1)
            else:
                if xattn is None:
                    lp, lc = xs
                    xa, ckv = None, None
                else:
                    lp, lc, xa, ckv = xs
            hh, new_lc, aux = apply_layer(cfg, kind, lp, hh, mode=mode,
                                          lc=lc, pos=pos,
                                          prefix_len=prefix_len,
                                          xattn=xa, cross_kv=ckv)
            return hh, (new_lc if mode != "full" else aux, aux)

        if remat:
            body = ckpt(body)
        if mode == "full":
            xs = (params["layers"], xattn, cross) if xattn is not None \
                else params["layers"]
        else:
            xs = (params["layers"], cache_layers) if xattn is None \
                else (params["layers"], cache_layers, xattn, cross)
        h, (new_cache, aux) = jax.lax.scan(
            body, h, xs, unroll=cfg.n_layers if cfg.scan_unroll else 1)
        if mode == "full":
            new_cache = None
        return h, new_cache, jnp.sum(aux)

    # heterogeneous: python loop over per-layer param dicts
    new_layers = []
    aux_sum = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(kinds):
        lp = params["layers"][i]
        lc = (cache_layers[i] if cache_layers is not None
              else init_layer_cache(cfg, kind, batch, 1))

        def call(lp_, hh_, lc_, kind_=kind):
            return apply_layer(cfg, kind_, lp_, hh_, mode=mode, lc=lc_,
                               pos=pos, prefix_len=prefix_len)

        if remat:
            call = ckpt(call)
        h, new_lc, aux = call(lp, h, lc)
        new_layers.append(new_lc)
        aux_sum = aux_sum + aux
    if mode == "full":
        new_layers = None
    return h, new_layers, aux_sum


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def embed(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    h = params["emb"][tokens]
    if cfg.scale_embeddings:
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def unembed(cfg: ModelConfig, params: dict, h: jax.Array) -> jax.Array:
    h = nn.apply_norm(cfg.norm, params["final_norm"], h)
    if cfg.tie_embeddings:
        return h @ params["emb"].T
    return h @ params["unemb"]


def encode(cfg: ModelConfig, params: dict, enc_embeds: jax.Array):
    """Bidirectional encoder over frame embeddings [B, Senc, D_enc]."""
    enc = params["encoder"]
    ed = cfg.enc_d_model or cfg.d_model
    h = enc_embeds + nn.sinusoidal_positions(enc_embeds.shape[1],
                                             ed).astype(enc_embeds.dtype)

    def body(carry, lp):
        hh = carry
        hn = nn.apply_norm(cfg.norm, lp["norm1"], hh)
        q, k, v = attn.project_qkv(lp["mix"], hn, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.head_dim)
        bias = jnp.zeros((1, 1, 1, 1, k.shape[1]), jnp.float32)
        hh = hh + attn.out_proj(lp["mix"], attn.attend(q, k, v, bias))
        hn = nn.apply_norm(cfg.norm, lp["norm2"], hh)
        hh = hh + nn.mlp(lp["mlp"], hn)
        return hh, None

    h, _ = jax.lax.scan(body, h, enc["layers"],
                        unroll=cfg.n_enc_layers if cfg.scan_unroll else 1)
    return nn.apply_norm(cfg.norm, enc["final_norm"], h)


def compute_cross_kv(cfg: ModelConfig, params: dict, enc_out: jax.Array):
    """Project encoder output to per-decoder-layer cross K/V (stacked)."""
    xa = params["xattn"]

    def one(lp):
        B, S, _ = enc_out.shape
        k = (enc_out @ lp["mix"]["wk"]).reshape(B, S, cfg.n_kv_heads,
                                                cfg.head_dim)
        v = (enc_out @ lp["mix"]["wv"]).reshape(B, S, cfg.n_kv_heads,
                                                cfg.head_dim)
        if "bk" in lp["mix"]:
            k = k + lp["mix"]["bk"].reshape(cfg.n_kv_heads, cfg.head_dim)
            v = v + lp["mix"]["bv"].reshape(cfg.n_kv_heads, cfg.head_dim)
        return k, v

    return jax.vmap(one)(xa)      # ([L,B,S,K,hd], [L,B,S,K,hd])


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
            prefix_embeds: jax.Array | None = None,
            enc_embeds: jax.Array | None = None):
    """Full-sequence logits (training). Returns (logits, aux_loss)."""
    h = embed(cfg, params, tokens)
    prefix_len = 0
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
        prefix_len = prefix_embeds.shape[1]
    cross = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, enc_embeds)
        cross = compute_cross_kv(cfg, params, enc_out)
    h, _, aux = _run_stack(cfg, params, h, mode="full", cache_layers=None,
                           prefix_len=prefix_len if cfg.prefix_lm else 0,
                           cross=cross)
    logits = unembed(cfg, params, h)
    if prefix_len:
        logits = logits[:, prefix_len:]
    return logits, aux


def prefill(cfg: ModelConfig, params: dict, tokens: jax.Array, cache: Cache,
            *, prefix_embeds: jax.Array | None = None,
            enc_embeds: jax.Array | None = None):
    """Consume the prompt, fill the cache, return last-position logits.

    Paged pools are decode-only: prefill a contiguous ROW cache
    (``init_cache(..., layout='contiguous')``) and scatter its rows
    into the pool blocks (``repro.serving.continuous.paged_slot_write``)
    — that keeps the prefill jit free of per-token table indirection.
    """
    if cache.block_table is not None:
        raise ValueError(
            "prefill into a paged pool is not supported — prefill a "
            "contiguous row cache and scatter it into the pool blocks "
            "(see repro.serving.continuous.paged_slot_write)")
    h = embed(cfg, params, tokens)
    prefix_len = 0
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
        prefix_len = prefix_embeds.shape[1]
    cross = cache.cross
    if cfg.family == "encdec":
        enc_out = encode(cfg, params, enc_embeds)
        cross = compute_cross_kv(cfg, params, enc_out)
    h, new_layers, _ = _run_stack(
        cfg, params, h, mode="prefill", cache_layers=cache.layers,
        prefix_len=prefix_len if cfg.prefix_lm else 0, cross=cross)
    logits = unembed(cfg, params, h[:, -1:])
    total = h.shape[1]
    return logits, Cache(layers=new_layers, cross=cross,
                         length=jnp.asarray(total, jnp.int32))


def decode_step(cfg: ModelConfig, params: dict, token: jax.Array,
                cache: Cache, pos):
    """One decode step. token [B,1] int32; pos = absolute position."""
    h = embed(cfg, params, token)
    h, new_layers, _ = _run_stack(cfg, params, h, mode="decode",
                                  cache_layers=cache.layers, pos=pos,
                                  cross=cache.cross,
                                  block_table=cache.block_table)
    logits = unembed(cfg, params, h)
    pos_arr = jnp.asarray(pos, jnp.int32)
    length = (jnp.max(pos_arr) if pos_arr.ndim else pos_arr) + 1
    return logits, Cache(layers=new_layers, cross=cache.cross,
                         length=length, block_table=cache.block_table)


def decode_chunk(cfg: ModelConfig, params: dict, tokens: jax.Array,
                 cache: Cache, pos):
    """Multi-token decode: the speculative-verify primitive.

    ``tokens`` [B, n] are consumed at per-row absolute positions
    ``pos[b] .. pos[b]+n-1`` in ONE forward pass with causal intra-chunk
    attention; returns (logits [B, n, V], new cache).  Row j's logits
    condition on everything a sequential ``decode_step`` at position
    ``pos+j`` would see, so sampling from them reproduces the
    non-speculative stream exactly.  Contiguous homogeneous attention
    stacks only — paged / MLA / recurrent / enc-dec engines serve
    ``draft_depth == 0``.
    """
    kinds = set(cfg.block_kinds)
    if not kinds <= {"attn", "local_attn"} or cfg.family == "encdec":
        raise ValueError(
            f"decode_chunk needs a pure attention stack (attn / "
            f"local_attn); got kinds={sorted(kinds)} family={cfg.family}")
    if cache.block_table is not None:
        raise ValueError(
            "decode_chunk supports the contiguous KV layout only; run "
            "the paged pool with draft_depth == 0")
    h = embed(cfg, params, tokens)
    h, new_layers, _ = _run_stack(cfg, params, h, mode="decode",
                                  cache_layers=cache.layers, pos=pos,
                                  cross=cache.cross, block_table=None)
    logits = unembed(cfg, params, h)
    pos_arr = jnp.asarray(pos, jnp.int32)
    length = (jnp.max(pos_arr) if pos_arr.ndim else pos_arr) \
        + tokens.shape[1]
    return logits, Cache(layers=new_layers, cross=cache.cross,
                         length=length, block_table=None)


def draft_prefix(cfg: ModelConfig, params: dict, n: int) -> dict:
    """Self-speculative draft params: the FIRST ``n`` layers of a
    homogeneous stack, sharing embeddings / final norm / unembed with
    the full model (shallow exit).  ``_run_stack`` takes its scan
    length from the stacked leaves, so the sliced dict runs under the
    SAME cfg."""
    if not cfg.homogeneous:
        raise ValueError(
            "self-speculative drafting slices a layer prefix, which "
            "needs a homogeneous stack")
    if not 0 < n < cfg.n_layers:
        raise ValueError(
            f"draft prefix must satisfy 0 < n < n_layers, got n={n} "
            f"with n_layers={cfg.n_layers}")
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(lambda x: x[:n],
                                           params["layers"])
    return out
