"""Flash attention (prefill/training hot-spot) as a Pallas TPU kernel.

Causal GQA flash attention with optional sliding-window mask.  Layout
[B, H, S, hd]; grid (B, H, q_blocks, kv_blocks) with the KV axis
innermost; the online-softmax state (m, l, acc) lives in VMEM scratch
and is re-initialised per q block.  BlockSpecs tile Q/K/V into
(q_blk x hd) / (k_blk x hd) VMEM windows; the MXU sees
[q_blk, hd] x [hd, k_blk] matmuls (q_blk/k_blk default 128/512 —
lane-aligned multiples of 128).
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int, q_offset: int,
                  q_blk: int, k_blk: int, sq: int, skv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[:] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[:, :] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32) * scale           # [q_blk, hd]
    k = k_ref[0, 0].astype(jnp.float32)                   # [k_blk, hd]
    v = v_ref[0, 0].astype(jnp.float32)

    s = q @ k.T                                           # [q_blk, k_blk]
    q_pos = (qi * q_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
             + q_offset)
    k_pos = ki * k_blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = (q_pos < sq + q_offset) & (k_pos < skv)
    if causal:
        ok = ok & (k_pos <= q_pos)
    if window:
        ok = ok & (q_pos - k_pos < window)
    s = jnp.where(ok, s, _NEG)

    m_old = m_ref[:]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1)
    acc_ref[:, :] = acc_ref[:, :] * corr[:, None] + p @ v
    m_ref[:] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0] = (acc_ref[:, :] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "q_blk", "k_blk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, q_offset: int = 0,
                    q_blk: int = 128, k_blk: int = 512,
                    interpret: bool | None = None) -> jax.Array:
    """q [B,H,Sq,hd], k/v [B,K,Skv,hd] (GQA) -> [B,H,Sq,hd].

    ``interpret=None`` -> compiled on TPU, interpreted elsewhere."""
    interpret = resolve_interpret(interpret)
    B, H, Sq, hd = q.shape
    K, Skv = k.shape[1], k.shape[2]
    G = H // K
    scale = 1.0 / math.sqrt(hd)

    q_blk = min(q_blk, max(Sq, 8))
    k_blk = min(k_blk, max(Skv, 8))
    nq = -(-Sq // q_blk)
    nk = -(-Skv // k_blk)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, nq * q_blk - Sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, nk * k_blk - Skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, nk * k_blk - Skv), (0, 0)))

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, q_blk=q_blk, k_blk=k_blk, sq=Sq, skv=Skv)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, k_blk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, k_blk, hd),
                         lambda b, h, qi, ki, G=G: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * q_blk, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((q_blk,), jnp.float32),
                        pltpu.VMEM((q_blk,), jnp.float32),
                        pltpu.VMEM((q_blk, hd), jnp.float32)],
        interpret=interpret,
        name="flash_attention",
    )(qp, kp, vp)
    return out[:, :, :Sq]
