"""Operations and bytes the algorithm needs, from shapes alone.

Counts are of the work the mathematics requires, not of what an
implementation does: attention reads only the valid key/value rows,
at the LOGICAL head dim (a head of 80 padded to 128 lanes shows as
lost roofline, not as work); padding rows of a prefill bucket, slots
that sit idle inside a decode window and retired slots' frozen steps
count nothing.  A multiply-add is two operations.  Bytes are of the
operands each call must read or write at least once in HBM, at the
served dtype.

``cfg`` is a configuration file's dict: n_layers, d_model, n_heads,
n_kv_heads, head_dim, d_ff, vocab, dtype.
"""
from __future__ import annotations

import numpy as np

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def itemsize(cfg: dict) -> int:
    return _ITEMSIZE[cfg["dtype"]]


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's linear maps (q, k, v, o, gate, up, down)."""
    d, H, K, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                      cfg["head_dim"], cfg["d_ff"])
    return d * (H + 2 * K) * hd + H * hd * d + 3 * d * f


def head_params(cfg: dict) -> int:
    return cfg["d_model"] * cfg["vocab"]


def attn_pair_flops(cfg: dict) -> int:
    """Operations per (query, key) pair per layer: QK^T and PV over
    every query head."""
    return 4 * cfg["n_heads"] * cfg["head_dim"]


def decode_token_flops(cfg: dict, ctx: int) -> int:
    """One decode step of one sequence whose new token attends to
    ``ctx`` rows (itself included): every layer, and the head."""
    L = cfg["n_layers"]
    return (2 * (L * layer_matmul_params(cfg) + head_params(cfg))
            + L * attn_pair_flops(cfg) * ctx)


def prefill_request_flops(cfg: dict, plen: int) -> int:
    """Prefill of one ``plen``-token prompt: every position through
    every layer (causal attention: ``plen (plen + 1) / 2`` pairs), and
    the head at the last position only, which is all the first token
    needs."""
    L = cfg["n_layers"]
    pairs = plen * (plen + 1) // 2
    return (2 * L * layer_matmul_params(cfg) * plen
            + L * attn_pair_flops(cfg) * pairs
            + 2 * head_params(cfg))


def paged_decode_kernel(cfg: dict, ctx: int) -> tuple[int, int]:
    """(flops, bytes) of the paged decode attention of ONE sequence in
    ONE layer at context ``ctx``: read the valid K and V rows and the
    query, write the output."""
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    b = itemsize(cfg)
    flops = attn_pair_flops(cfg) * ctx
    nbytes = 2 * ctx * K * hd * b + 2 * H * hd * b
    return flops, nbytes


def flash_prefill_kernel(cfg: dict, plen: int) -> tuple[int, int]:
    """(flops, bytes) of causal flash attention over ONE ``plen``-token
    prompt in ONE layer: read q, k, v once, write o once."""
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    b = itemsize(cfg)
    flops = attn_pair_flops(cfg) * (plen * (plen + 1) // 2)
    nbytes = plen * (2 * H + 2 * K) * hd * b
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations
    over peak FLOP/s and bytes over peak HBM bytes/s."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def decode_contexts(plen: int, n_tokens: int, first: int = 1) -> np.ndarray:
    """Contexts of the decode steps that produced tokens ``first ..
    n_tokens-1`` of a request (token 0 comes from prefill): token ``j``
    is computed by the step whose input sits at position
    ``plen + j - 1`` and attends to ``plen + j`` rows."""
    j = np.arange(max(first, 1), n_tokens)
    return plen + j
