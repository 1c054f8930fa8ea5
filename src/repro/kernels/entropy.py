"""Fused softmax-entropy / confidence kernel (the controller's L(x)).

With vocab up to 257 k, materialising softmax probabilities in HBM to
compute entropy costs ~3 full passes over the logits.  This kernel
streams the vocab axis through VMEM once, maintaining running
(max, sum-exp, sum-x·exp, argmax) statistics in scratch:

    H = m + log(s) - u/s,   p_max = 1/s,
    m = max_v x_v,  s = sum_v e^{x_v - m},  u = sum_v x_v e^{x_v - m}

Grid: (batch_blocks, vocab_blocks), vocab innermost; BlockSpec tiles
(B_BLK x V_BLK) of the logits into VMEM.  Outputs are per-row scalars
written on the last vocab step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

_NEG = -1e30


def _entropy_kernel(x_ref, h_ref, maxp_ref, amax_ref,
                    m_ref, s_ref, u_ref, idx_ref, *, v_total: int,
                    v_blk: int):
    vi = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        s_ref[...] = jnp.zeros(s_ref.shape, jnp.float32)
        u_ref[...] = jnp.zeros(u_ref.shape, jnp.float32)
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    x = x_ref[...].astype(jnp.float32)                    # [B_BLK, V_BLK]
    col = vi * v_blk + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < v_total, x, _NEG)

    bm = jnp.max(x, axis=1, keepdims=True)                # block max
    bi = (jnp.argmax(x, axis=1, keepdims=True).astype(jnp.int32)
          + vi * v_blk)
    m_old = m_ref[...]                                    # [B_BLK, 1]
    m_new = jnp.maximum(m_old, bm)
    corr = jnp.exp(m_old - m_new)
    e = jnp.exp(x - m_new)
    s_ref[...] = s_ref[...] * corr + jnp.sum(e, axis=1, keepdims=True)
    u_ref[...] = u_ref[...] * corr + jnp.sum(x * e, axis=1, keepdims=True)
    idx_ref[...] = jnp.where(bm > m_old, bi, idx_ref[...])
    m_ref[...] = m_new

    @pl.when(vi == nv - 1)
    def _emit():
        m, s, u = m_ref[...], s_ref[...], u_ref[...]
        h_ref[...] = m + jnp.log(s) - u / s
        maxp_ref[...] = 1.0 / s
        amax_ref[...] = idx_ref[...]


@functools.partial(jax.jit, static_argnames=("b_blk", "v_blk", "interpret"))
def entropy_stats(logits: jax.Array, *, b_blk: int = 8, v_blk: int = 2048,
                  interpret: bool | None = None):
    """logits [B, V] -> (entropy [B], max_prob [B], argmax [B] int32).

    The per-row statistics are written as [B_BLK, 1] column blocks of
    [B_pad, 1] outputs, so every block spans its array's last dim (the
    TPU tiling rule); a vocab narrower than ``v_blk`` is one block.

    ``interpret=None`` -> compiled on TPU, interpreted elsewhere."""
    interpret = resolve_interpret(interpret)
    B, V = logits.shape
    v_blk = min(v_blk, V)
    nb = -(-B // b_blk)
    nv = -(-V // v_blk)
    pad_b = nb * b_blk - B
    x = jnp.pad(logits, ((0, pad_b), (0, 0))) if pad_b else logits

    kernel = functools.partial(_entropy_kernel, v_total=V, v_blk=v_blk)
    col = pl.BlockSpec((b_blk, 1), lambda b, v: (b, 0))
    h, maxp, amax = pl.pallas_call(
        kernel,
        grid=(nb, nv),
        in_specs=[pl.BlockSpec((b_blk, v_blk), lambda b, v: (b, v))],
        out_specs=[col, col, col],
        out_shape=[jax.ShapeDtypeStruct((nb * b_blk, 1), jnp.float32),
                   jax.ShapeDtypeStruct((nb * b_blk, 1), jnp.float32),
                   jax.ShapeDtypeStruct((nb * b_blk, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((b_blk, 1), jnp.float32),
                        pltpu.VMEM((b_blk, 1), jnp.float32),
                        pltpu.VMEM((b_blk, 1), jnp.float32),
                        pltpu.VMEM((b_blk, 1), jnp.int32)],
        interpret=interpret,
    )(x)
    return h[:B, 0], maxp[:B, 0], amax[:B, 0]
