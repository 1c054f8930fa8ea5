"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology and refuses what the
chip would refuse — block shapes that break the tiling rule, too much
VMEM — which interpret mode never checks.  Shapes are stablelm-3b's
published widths as served by ``chip_smoke.py`` (8 slots, paged pool
of 16-row blocks, max_seq 512) and the gated classifier's logits.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as dak
from repro.kernels import entropy as entk
from repro.kernels import flash_attention as fak

CFG = get_config("stablelm-3b")
B, BS, MAX_SEQ = 8, 16, 512
H, K, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache
    off: an AOT compile for a chip that is not attached is written to
    the cache but can never be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text


def test_paged_decode_attention_compiles(one_chip):
    mb = MAX_SEQ // BS
    nb = 1 + B * mb
    _compile(one_chip, dak.paged_decode_attention,
             ((B, H, HD), jnp.bfloat16),
             ((nb, BS, K, HD), jnp.bfloat16),
             ((nb, BS, K, HD), jnp.bfloat16),
             ((B, mb), jnp.int32), ((B, MAX_SEQ), jnp.int32),
             ((B,), jnp.int32))


def test_decode_attention_compiles(one_chip):
    _compile(one_chip, dak.decode_attention,
             ((B, H, HD), jnp.bfloat16),
             ((B, K, MAX_SEQ, HD), jnp.bfloat16),
             ((B, K, MAX_SEQ, HD), jnp.bfloat16),
             ((B, MAX_SEQ), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("seq", [128, 16])
def test_flash_attention_compiles(one_chip, seq):
    _compile(one_chip, fak.flash_attention,
             ((B, H, seq, HD), jnp.bfloat16),
             ((B, K, seq, HD), jnp.bfloat16),
             ((B, K, seq, HD), jnp.bfloat16))


@pytest.mark.parametrize("vocab", [CFG.vocab, 2])
def test_entropy_stats_compiles(one_chip, vocab):
    _compile(one_chip, entk.entropy_stats, ((32, vocab), jnp.float32))
