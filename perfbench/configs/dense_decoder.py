"""Plain reference of a dense pre-norm decoder, in float32.

Serves every configuration whose file names ``"reference":
"dense_decoder"`` (stablelm-3b, internlm2-20b): token embedding, then
per layer ``h += Attn(Norm1(h))`` and ``h += SwiGLU(Norm2(h))``, a
final norm and an untied output head.  Attention is causal, grouped
(query head ``i`` reads key/value head ``i // (H / K)``), scaled by
``1/sqrt(head_dim)``, with rotary embeddings on the first
``int(head_dim * rope_pct)`` dims of each head.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision
("highest")``: no kernel, no cache, no batching of requests against one
another (each row attends to itself only), every activation and every
weight upcast to float32.  The weights are the bf16 values the
benchmark made from the seed (``harness.weights``); nothing is taken
from the program.

Where this follows the program rather than the published model, with
random weights the two are the same model up to a fixed permutation or
a negligible constant, and the configuration file lists it under
``departures``:

- rotary pairs are interleaved (dims ``2i, 2i+1``), where the Hugging
  Face code pairs ``i, i + rd/2``: a permutation of the rotated dims
  of ``wq`` and ``wk``;
- RMSNorm is ``x / rms(x) * (1 + scale)`` with ``scale`` made zero,
  the published ``weight * x / rms(x)`` with ``weight`` one; its eps
  is the configuration's ``norm_eps``.

``quant="int8"`` is the control: every linear layer (projections, MLP
and output head) takes its weight rounded to int8 per output channel
and its input rounded to int8 per token, symmetric, as a W8A8 int8
serving path does; everything else stays as above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CFG_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "norm", "norm_eps", "rope_pct", "rope_theta")


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 rounding of ``x`` with one scale per slice along
    every axis but ``axis`` (the reduction axis of the matmul)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _linear(x: jax.Array, w: jax.Array, quant: str | None) -> jax.Array:
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def _norm(cfg: dict, p: dict, x: jax.Array) -> jax.Array:
    eps = cfg["norm_eps"]
    if cfg["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + p["scale"])


def _rope(x: jax.Array, cfg: dict) -> jax.Array:
    """x [B, S, heads, hd] at positions 0..S-1."""
    hd = x.shape[-1]
    rd = int(hd * cfg["rope_pct"])
    S = x.shape[1]
    inv = 1.0 / (cfg["rope_theta"]
                 ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0:rd:2], x[..., 1:rd:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([rot.reshape(*x.shape[:-1], rd), x[..., rd:]],
                           axis=-1)


def _attention(cfg: dict, p: dict, x: jax.Array, quant) -> jax.Array:
    B, S, _ = x.shape
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = _linear(x, p["wq"], quant).reshape(B, S, H, hd)
    k = _linear(x, p["wk"], quant).reshape(B, S, K, hd)
    v = _linear(x, p["wv"], quant).reshape(B, S, K, hd)
    q, k = _rope(q, cfg), _rope(k, cfg)
    rep = H // K
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * hd)
    return _linear(o, p["wo"], quant)


def _mlp(p: dict, x: jax.Array, quant) -> jax.Array:
    g = _linear(x, p["w_gate"], quant)
    u = _linear(x, p["w_up"], quant)
    return _linear(jax.nn.silu(g) * u, p["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _logits(frozen: tuple, params: dict, tokens: jax.Array, start: int,
            quant: str | None) -> jax.Array:
    cfg = dict(frozen)
    h = params["emb"][tokens].astype(jnp.float32)

    def layer(h, lp):
        h = h + _attention(cfg, lp["mix"], _norm(cfg, lp["norm1"], h),
                           quant)
        h = h + _mlp(lp["mlp"], _norm(cfg, lp["norm2"], h), quant)
        return h, None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    h = _norm(cfg, params["final_norm"], h[:, start:])
    return _linear(h, params["unemb"], quant)


def logits(cfg: dict, params: dict, tokens: jax.Array, start: int,
           quant: str | None = None) -> jax.Array:
    """Next-token logits [B, S - start, vocab] (float32) at positions
    ``start .. S-1`` of ``tokens`` [B, S]."""
    frozen = tuple((k, cfg[k]) for k in CFG_KEYS)
    with jax.default_matmul_precision("highest"):
        return _logits(frozen, params, tokens, start, quant)
