"""Public jit'd entry points for the Pallas kernels.

Dispatch policy (``impl=``):
  - ``"auto"``   — the production setting: the compiled Pallas kernel
    on TPU backends, the pure-jnp oracle (XLA-lowered) elsewhere.
    Interpret-mode Pallas is a validation tool, not a serving path —
    ``auto`` never picks it, so serving code can say ``impl="auto"``
    unconditionally and get the kernel exactly where it was written
    for.  This is ``ModelConfig.attn_impl``'s default — note the
    model layer short-circuits ``"auto"`` off-TPU to its own einsum
    path (bitwise-identical to ``"xla"``) before reaching here, so
    attention only enters this dispatch with ``auto`` on TPU.
  - ``"ref"``    — always the pure-jnp oracle (``repro.kernels.ref``).
  - ``"pallas"`` — force the kernel: native on TPU, ``interpret=True``
    (Python-evaluated body) elsewhere.  Kernel validation and
    debugging only.
  - ``"shim"``   — :func:`paged_decode_attention` only: the
    materialised block-table-gather path kept as the table-native
    kernel's parity oracle (byte-identical at matched chunking; see
    ``repro.kernels.decode_attention``).  Same backend rule as
    ``pallas``.

Backend detection lives in ``repro.kernels.runtime`` — the raw kernel
entry points share it for their ``interpret=None`` defaults, so the
dispatch here and a direct kernel call can never disagree about what
"on TPU" means.
"""
from __future__ import annotations

from repro.kernels import decode_attention as _da
from repro.kernels import entropy as _ent
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels.runtime import on_tpu as _on_tpu

_IMPLS = ("auto", "ref", "pallas")
_PAGED_IMPLS = ("auto", "ref", "pallas", "shim")


def _use_kernel(impl: str, *, impls: tuple[str, ...] = _IMPLS) -> bool:
    if impl not in impls:
        raise ValueError(f"impl must be one of {impls}, got {impl!r}")
    if impl == "ref":
        return False
    if impl in ("pallas", "shim"):
        return True
    return _on_tpu()


def entropy_stats(logits, *, impl: str = "auto"):
    """logits [B,V] -> (entropy, max_prob, argmax).  The controller's
    L(x) hot-spot (vocab streaming, one HBM pass)."""
    if not _use_kernel(impl):
        return _ref.entropy_stats(logits)
    return _ent.entropy_stats(logits)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                    impl: str = "auto"):
    """q [B,H,Sq,hd]; k/v [B,K,Skv,hd] (GQA: H = K*G) -> [B,H,Sq,hd]."""
    if not _use_kernel(impl):
        return _ref.flash_attention(q, k, v, causal=causal, window=window,
                                    q_offset=q_offset)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)


def decode_attention(q, k, v, kv_pos, cur_pos, *, window=0,
                     impl: str = "auto"):
    """q [B,H,hd]; k/v [B,K,S,hd]; kv_pos [B,S]; cur_pos [B] -> [B,H,hd]."""
    if not _use_kernel(impl):
        return _ref.decode_attention(q, k, v, kv_pos, cur_pos,
                                     window=window)
    return _da.decode_attention(q, k, v, kv_pos, cur_pos, window=window)


def paged_decode_attention(q, k_pool, v_pool, block_table, kv_pos,
                           cur_pos, layer=0, *, window=0,
                           impl: str = "auto"):
    """q [B,H,hd]; k/v pool [L,NB,bs,K,hdp] read at ``layer`` (or one
    pool [NB,bs,K,hdp]), rows padded to hdp >= hd; block_table [B,MB];
    kv_pos [B,MB*bs]; cur_pos [B] -> [B,H,hd].

    The paged serving hot path: the TABLE-NATIVE flash-decode kernel —
    the slot's block-table row and the layer are scalar-prefetched and
    each grid step's HBM→VMEM DMA is redirected through them, so the
    stacked pool is consumed in place with no slice and no materialised
    gather.  ``impl="shim"`` forces the old gather-then-contiguous-kernel
    path, kept as the parity oracle (byte-identical at
    ``k_blk == block_size``).
    Validity is carried entirely by ``kv_pos`` — unmapped table
    entries point at the trash block whose rows are never valid."""
    if not _use_kernel(impl, impls=_PAGED_IMPLS):
        k, v = _da.gather_block_views(k_pool, v_pool, block_table,
                                      kv_pos.shape[1], layer,
                                      head_dim=q.shape[-1])
        return _ref.decode_attention(q, k.transpose(0, 2, 1, 3),
                                     v.transpose(0, 2, 1, 3),
                                     kv_pos, cur_pos, window=window)
    if impl == "shim":
        return _da.paged_decode_attention_shim(
            q, k_pool, v_pool, block_table, kv_pos, cur_pos, layer,
            window=window, k_blk=k_pool.shape[-3])
    return _da.paged_decode_attention(q, k_pool, v_pool, block_table,
                                      kv_pos, cur_pos, layer,
                                      window=window)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128, impl: str = "auto"):
    """Mamba-2 SSD chunked scan (attention-free archs' hot-spot)."""
    from repro.kernels import ssd_scan as _ssd
    if not _use_kernel(impl):
        return _ref.ssd_scan(x, dt, A, Bm, Cm)
    return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
