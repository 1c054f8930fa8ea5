"""Random weights made on the device from the seed, in one jitted call.

The benchmark makes the weights itself, so that its plain reference
can make the very same ones again from the seed without taking any
array from the program.  They are laid out as the program's dense
decoder takes them (``repro.models.transformer``: stacked per-layer
leaves under ``layers``) and are bf16 where the program serves bf16;
norm parameters are float32, as the program keeps them.

Layers are drawn under ``lax.map`` so the generator's temporaries are
one layer's, not the model's.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (more than 32 bits too)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def _norm(kind: str, d: int, L: int | None = None) -> dict:
    shape = (d,) if L is None else (L, d)
    if kind == "layernorm":
        return {"scale": jnp.ones(shape, jnp.float32),
                "bias": jnp.zeros(shape, jnp.float32)}
    return {"scale": jnp.zeros(shape, jnp.float32)}


def _layer(cfg: dict, key) -> dict:
    d, H, K, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                      cfg["head_dim"], cfg["d_ff"])
    dt = jnp.dtype(cfg["dtype"])
    ks = jax.random.split(key, 7)
    mix = {"wq": _normal(ks[0], (d, H * hd), d, dt),
           "wk": _normal(ks[1], (d, K * hd), d, dt),
           "wv": _normal(ks[2], (d, K * hd), d, dt),
           "wo": _normal(ks[3], (H * hd, d), H * hd, dt)}
    mlp = {"w_gate": _normal(ks[4], (d, f), d, dt),
           "w_up": _normal(ks[5], (d, f), d, dt),
           "w_down": _normal(ks[6], (f, d), f, dt)}
    return {"mix": mix, "mlp": mlp}


@functools.partial(jax.jit, static_argnums=0)
def _make(frozen: tuple, key) -> dict:
    cfg = dict(frozen)
    d, V, L = cfg["d_model"], cfg["vocab"], cfg["n_layers"]
    dt = jnp.dtype(cfg["dtype"])
    k_emb, k_un, k_layers = jax.random.split(key, 3)
    layers = jax.lax.map(lambda k: _layer(cfg, k),
                         jax.random.split(k_layers, L))
    layers["norm1"] = _norm(cfg["norm"], d, L)
    layers["norm2"] = _norm(cfg["norm"], d, L)
    return {"emb": _normal(k_emb, (V, d), d, dt),
            "unemb": _normal(k_un, (d, V), d, dt),
            "final_norm": _norm(cfg["norm"], d),
            "layers": layers}


MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "norm", "dtype")


def make_params(cfg: dict, seed: int) -> dict:
    """The model's weights for ``seed``, on the default device."""
    frozen = tuple((k, cfg[k]) for k in MODEL_KEYS)
    return _make(frozen, seed_key(seed))
