"""Random weights made on the device from the seed, in one jitted call.

The benchmark makes the weights itself, so that its plain reference
can make the very same ones again from the seed without taking any
array from the program.  Each configuration's reference module
(``configs/<reference>.py``) defines ``make_params(cfg, key)``, which
lays the weights out as the program's tree of that architecture takes
them, bf16 where the program serves bf16 and norms in float32, as the
program keeps them; ``make_params`` here finds that module by the
configuration's ``reference`` and hands it the seed's key.  The
helpers below are the pieces the references share.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness import spec


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed (more than 32 bits too)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def normal(key, shape, fan_in, dtype):
    """Normal draws scaled by ``1/sqrt(fan_in)``, in ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / math.sqrt(fan_in))).astype(dtype)


def norm(kind: str, d: int, L: int | None = None) -> dict:
    """A norm's parameters in float32, stacked over ``L`` layers when
    given: LayerNorm's scale one and bias zero; RMSNorm's ``1 + scale``
    with scale zero."""
    shape = (d,) if L is None else (L, d)
    if kind == "layernorm":
        return {"scale": jnp.ones(shape, jnp.float32),
                "bias": jnp.zeros(shape, jnp.float32)}
    return {"scale": jnp.zeros(shape, jnp.float32)}


def make_params(cfg: dict, seed: int, bench_dir: str) -> dict:
    """The model's weights for ``seed``, on the default device, made by
    the reference module of the configuration ``cfg`` under
    ``bench_dir``."""
    ref = spec.reference(bench_dir, cfg["reference"])
    return ref.make_params(cfg, seed_key(seed))
