"""Flash-decode Pallas kernels — one new token vs a long KV cache.

The dominant op of the decode_32k / long_500k shapes: q [B, H, hd]
against k/v [B, K, S, hd] with per-slot absolute positions (supports
ring-buffered sliding-window caches).  Grid (B, kv_blocks), KV
innermost; each step takes every head of one slot against one
[K, k_blk, hd] tile, online softmax in VMEM scratch.  The cache never
leaves HBM except for the tile streamed through VMEM — this kernel is
purely HBM-bandwidth bound, which is exactly what the roofline says.

Two paged entry points serve the vLLM-style shared block pool:

  - :func:`paged_decode_attention` — the TABLE-NATIVE kernel.  The
    slot's ``block_table`` row and the layer index are
    scalar-prefetched (``pltpu.PrefetchScalarGridSpec``) and every
    grid step's HBM→VMEM DMA is redirected through them by the
    BlockSpec index_map, so the kernel streams ``[block_size, K, hdp]``
    blocks of one layer straight out of the stacked pool
    ``[L, NB, bs, K, hdp]`` (rows zero-padded from hd to hdp lanes).  No gather, no contiguous copy, no
    per-layer slice.  The grid visits every entry of the slot's table
    row, so unmapped entries re-read trash block 0.
  - :func:`paged_decode_attention_shim` — the materialised-gather
    shim kept as the parity oracle: one XLA gather rebuilds the
    contiguous [B, K, S, hd] view, then the contiguous kernel runs on
    it.  At matched chunking (``k_blk == block_size``) both paths
    execute the identical online-softmax schedule, so their outputs
    are BYTE-identical — enforced in tests and the CI smoke gate.

Validity is carried entirely by ``kv_pos`` on both paths: unmapped
table entries point at trash block 0, whose rows are never attended
because their logical positions were never written (stay -1).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

_NEG = -1e30
# rows of K/V per grid step (summed over kv heads) when the caller
# leaves ``k_blk`` unset: 2048 x hd bf16 rows keep the double-buffered
# K/V tiles plus their f32 copies well inside the default scoped VMEM
_TILE_ROWS = 2048


def _decode_body(q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref,
                 acc_ref, *, cur, scale: float, window: int,
                 heads_minor: bool):
    """One grid step (b, ki): every head of slot b against one KV tile.

    q_ref [1, K, G, hd]; k_ref/v_ref [1, K, T, hd] (contiguous) or
    [1, T, K, hd] (``heads_minor``: a pool block); pos_ref [1, nk, T]
    holds the slot's whole position row, of which step ki reads row ki.
    Both kernels run this body, so at ``k_blk == block_size`` the
    contiguous and paged kernels execute the identical online-softmax
    schedule."""
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32) * scale              # [K, G, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    if heads_minor:                       # [T, K, hd] -> [K, T, hd]
        k = jnp.swapaxes(k, 0, 1)
        v = jnp.swapaxes(v, 0, 1)
    kv_pos = pos_ref[0, pl.ds(ki, 1), :]                  # [1, T]

    s = jnp.einsum("kgd,ktd->kgt", q, k)                  # [K, G, T]
    ok = (kv_pos >= 0) & (kv_pos <= cur)
    if window:
        ok = ok & (cur - kv_pos < window)
    s = jnp.where(ok[None], s, _NEG)

    m_old = m_ref[...]                                    # [K, G, 1]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.einsum("kgt,ktd->kgd", p, v)
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_kernel(cur_ref, q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, scale: float, window: int):
    _decode_body(q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref,
                 acc_ref, cur=cur_ref[pl.program_id(0)], scale=scale,
                 window=window, heads_minor=False)


def _scratch(K: int, G: int, hd: int) -> list:
    return [pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("window", "k_blk", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_pos: jax.Array, cur_pos: jax.Array, *,
                     window: int = 0, k_blk: int | None = None,
                     interpret: bool | None = None) -> jax.Array:
    """q [B,H,hd]; k/v [B,K,S,hd]; kv_pos [B,S]; cur_pos [B] -> [B,H,hd].

    Grid (B, kv_blocks): each step streams a [K, k_blk, hd] tile of
    every kv head.  ``k_blk=None`` sizes it to ``_TILE_ROWS`` rows in
    all.  ``cur_pos`` rides in SMEM as a scalar-prefetch operand, and
    ``kv_pos`` is read whole per slot as [nk, k_blk], so every block
    spans its array's last two dims (the TPU tiling rule).

    ``interpret=None`` resolves to compiled-on-TPU / interpreted
    elsewhere (``repro.kernels.runtime.default_interpret``)."""
    interpret = resolve_interpret(interpret)
    B, H, hd = q.shape
    K, S = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)

    if k_blk is None:
        k_blk = max(8, min(512, _TILE_ROWS // K) // 8 * 8)
    k_blk = min(k_blk, max(S, 8))
    nk = -(-S // k_blk)
    pad = nk * k_blk - S
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pp = jnp.pad(kv_pos, ((0, 0), (0, pad)), constant_values=-1)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, K, G, hd), lambda b, ki, cur: (b, 0, 0, 0)),
            pl.BlockSpec((1, K, k_blk, hd),
                         lambda b, ki, cur: (b, 0, ki, 0)),
            pl.BlockSpec((1, K, k_blk, hd),
                         lambda b, ki, cur: (b, 0, ki, 0)),
            pl.BlockSpec((1, nk, k_blk), lambda b, ki, cur: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hd),
                               lambda b, ki, cur: (b, 0, 0, 0)),
        scratch_shapes=_scratch(K, G, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(cur_pos.astype(jnp.int32), q.reshape(B, K, G, hd), kp, vp,
      pp.astype(jnp.int32).reshape(B, nk, k_blk))
    return out.reshape(B, H, hd)


def stacked_pool(pool: jax.Array) -> jax.Array:
    """A pool as a stack of per-layer pools [L, NB, bs, K, hd]: one
    pool [NB, bs, K, hd] is the stack of one layer (a free reshape)."""
    return pool[None] if pool.ndim == 4 else pool


def gather_block_views(k_pool: jax.Array, v_pool: jax.Array,
                       block_table: jax.Array, n_ctx: int, layer=0, *,
                       head_dim: int | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Gather each slot's mapped blocks into the contiguous logical
    view: pool [NB, bs, K, hdp] (or layer ``layer`` of a stacked pool
    [L, NB, bs, K, hdp]) + table [B, MB] -> k/v [B, n_ctx, K, hd],
    the first ``head_dim`` lanes of each row (all hdp when ``None``;
    a pool's head axis may be padded past the model's head size)
    (BSHD, the gather's natural layout — the decode kernels transpose
    to their BHSD at the call site).  The ONE implementation of the
    block-table gather — the Pallas shim, the jnp ops dispatch AND the
    model layer's ``attn.paged_gather`` all go through it, so table
    semantics can never diverge between paths."""
    k_pool, v_pool = stacked_pool(k_pool), stacked_pool(v_pool)
    B = block_table.shape[0]
    bs = k_pool.shape[2]
    if n_ctx % bs != 0:
        raise ValueError(
            f"paged gather: logical extent n_ctx={n_ctx} is not a "
            f"multiple of the pool block size bs={bs} (pool "
            f"{tuple(k_pool.shape)}, table {tuple(block_table.shape)}) "
            f"— the trailing n_ctx % bs = {n_ctx % bs} rows would be "
            f"silently truncated")
    n_blocks = n_ctx // bs
    if n_blocks > block_table.shape[1]:
        raise ValueError(
            f"paged gather: n_ctx={n_ctx} needs {n_blocks} blocks of "
            f"bs={bs} rows but the block table maps only "
            f"{block_table.shape[1]} per slot (table "
            f"{tuple(block_table.shape)})")
    tb = block_table[:, :n_blocks]                      # [B, MB]
    k = k_pool[layer, tb, ..., :head_dim]
    v = v_pool[layer, tb, ..., :head_dim]
    return (k.reshape(B, n_ctx, *k.shape[3:]),
            v.reshape(B, n_ctx, *v.shape[3:]))


# ---------------------------------------------------------------------------
# paged flash-decode — TABLE-NATIVE kernel (scalar-prefetched DMA)
# ---------------------------------------------------------------------------

def _paged_kernel(tbl_ref, cur_ref, layer_ref, q_ref, k_ref, v_ref,
                  pos_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                  window: int):
    """One grid step = one mapped pool block of the slot, all heads.

    ``tbl_ref`` (the block table) and ``layer_ref`` (the layer index)
    are scalar-prefetched — the kernel body never touches them; the
    BlockSpec index_maps already used them to redirect this step's
    HBM→VMEM DMA, so ``k_ref``/``v_ref`` hold the [bs, K, hd] rows of
    pool block ``tbl[b, ki]`` of that layer.  The math is
    ``_decode_body``, the contiguous kernel's own, which is what makes
    the shim byte-identical at k_blk == bs."""
    del tbl_ref, layer_ref
    _decode_body(q_ref, k_ref, v_ref, pos_ref, o_ref, m_ref, l_ref,
                 acc_ref, cur=cur_ref[pl.program_id(0)], scale=scale,
                 window=window, heads_minor=True)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_table: jax.Array,
                           kv_pos: jax.Array, cur_pos: jax.Array,
                           layer=0, *, window: int = 0,
                           interpret: bool | None = None) -> jax.Array:
    """Flash-decode over a paged block pool — TABLE-NATIVE.

    q [B,H,hd]; k_pool/v_pool [L, NB, bs, K, hdp], the per-layer pools
    of a layer stack, read at layer ``layer`` (a scalar, traced or
    not), or one pool [NB, bs, K, hdp] (the one-layer stack, layer 0),
    whose rows may be padded past hd with zeros (hdp >= hd: the kernel
    pads q to match and drops the pad lanes of its output);
    block_table [B, MB] maps each slot's logical block to a pool
    block; kv_pos [B, MB*bs] per-slot absolute positions (-1 = empty);
    cur_pos [B] -> [B,H,hd].

    The block table, ``cur_pos`` and the layer index ride in as
    scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``): they
    are resident in SMEM before the first grid step, and the k/v
    BlockSpec index_maps read ``(layer, tbl[b, ki])`` to aim each
    step's HBM→VMEM DMA at the slot's ki-th mapped pool block of that
    layer — all K heads of it, so the block spans the pool's last two
    dims.  The stacked pool is therefore consumed IN PLACE — no
    per-layer slice, no materialised gather, no contiguous copy.  The
    grid's KV chunk is the pool block size (DMAs must land on
    pool-block boundaries; a k_blk knob would either re-introduce the
    copy or be a lie).

    ``kv_pos`` validity masking is unchanged from the contiguous
    kernel, so trash-block rows (unmapped table entries point at
    block 0) are never attended."""
    interpret = resolve_interpret(interpret)
    k_pool, v_pool = stacked_pool(k_pool), stacked_pool(v_pool)
    B, H, hd = q.shape
    bs, K, hdp = k_pool.shape[2:]
    G = H // K
    C = kv_pos.shape[1]
    if C % bs != 0:
        raise ValueError(
            f"paged decode: kv_pos extent C={C} is not a multiple of "
            f"the pool block size bs={bs} — the paged layout is "
            f"block-aligned by construction, so this is a caller bug")
    nk = C // bs
    if nk > block_table.shape[1]:
        raise ValueError(
            f"paged decode: kv_pos extent C={C} needs {nk} blocks of "
            f"bs={bs} rows but the block table maps only "
            f"{block_table.shape[1]} per slot")
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_paged_kernel, scale=scale, window=window)

    def kv_block(b, ki, tbl, cur, lay):
        return lay[0], tbl[b, ki], 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nk),
        in_specs=[
            pl.BlockSpec((1, K, G, hdp),
                         lambda b, ki, tbl, cur, lay: (b, 0, 0, 0)),
            pl.BlockSpec((None, 1, bs, K, hdp), kv_block),
            pl.BlockSpec((None, 1, bs, K, hdp), kv_block),
            pl.BlockSpec((1, nk, bs),
                         lambda b, ki, tbl, cur, lay: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, K, G, hdp),
                               lambda b, ki, tbl, cur, lay: (b, 0, 0, 0)),
        scratch_shapes=_scratch(K, G, hdp),
    )
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, hdp - hd)))
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hdp), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_table.astype(jnp.int32), cur_pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      qp.reshape(B, K, G, hdp), k_pool, v_pool,
      kv_pos.astype(jnp.int32).reshape(B, nk, bs))
    return out[..., :hd].reshape(B, H, hd)


@functools.partial(jax.jit,
                   static_argnames=("window", "k_blk", "interpret"))
def paged_decode_attention_shim(q: jax.Array, k_pool: jax.Array,
                                v_pool: jax.Array, block_table: jax.Array,
                                kv_pos: jax.Array, cur_pos: jax.Array,
                                layer=0, *, window: int = 0,
                                k_blk: int | None = None,
                                interpret: bool | None = None
                                ) -> jax.Array:
    """Flash-decode over a paged block pool — block-table gather SHIM.

    The parity oracle for :func:`paged_decode_attention`: gathers each
    slot's mapped blocks into the contiguous [B, K, S, hd] layout with
    one materialised XLA gather, then runs the contiguous flash-decode
    kernel.  At ``k_blk == block_size`` the online-softmax schedule is
    the native kernel's exactly, so outputs are byte-identical — the
    property the tests and the CI smoke gate pin.  Costs one full
    extra pass over the cache bytes per micro-step, which is why it is
    no longer the serving path."""
    k, v = gather_block_views(k_pool, v_pool, block_table,
                              kv_pos.shape[1], layer,
                              head_dim=q.shape[-1])
    return decode_attention(q, k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), kv_pos, cur_pos,
                            window=window, k_blk=k_blk,
                            interpret=interpret)
