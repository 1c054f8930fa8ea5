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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as dak
from repro.kernels import entropy as entk
from repro.kernels import flash_attention as fak
from repro.kernels import runtime
from repro.models import attention as attn
from repro.models import transformer as tfm
from repro.serving.continuous import ContinuousBatchingEngine

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


def _compile(one_chip, fn, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(functools.partial(fn, interpret=False)).lower(
        *args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


MB = MAX_SEQ // BS
PAGED = (((B, H, HD), jnp.bfloat16),
         ((1 + B * MB, BS, K, HD), jnp.bfloat16),
         ((1 + B * MB, BS, K, HD), jnp.bfloat16),
         ((B, MB), jnp.int32), ((B, MAX_SEQ), jnp.int32),
         ((B,), jnp.int32))
DECODE = (((B, H, HD), jnp.bfloat16),
          ((B, K, MAX_SEQ, HD), jnp.bfloat16),
          ((B, K, MAX_SEQ, HD), jnp.bfloat16),
          ((B, MAX_SEQ), jnp.int32), ((B,), jnp.int32))


def _flash(seq: int) -> tuple:
    return (((B, H, seq, HD), jnp.bfloat16),
            ((B, K, seq, HD), jnp.bfloat16),
            ((B, K, seq, HD), jnp.bfloat16))


def test_paged_decode_attention_compiles(one_chip):
    _compile(one_chip, dak.paged_decode_attention, *PAGED)


def test_paged_decode_attention_compiles_on_stacked_pool(one_chip):
    """The serving path's call: a stack of four layers' pools, read at
    a layer index that arrives as a traced operand."""
    q, kp, vp, *rest = PAGED
    stack = tuple(((4,) + shape, dt) for shape, dt in (kp, vp))
    _compile(one_chip, dak.paged_decode_attention, q, *stack, *rest,
             ((), jnp.int32))


def test_decode_attention_compiles(one_chip):
    _compile(one_chip, dak.decode_attention, *DECODE)


@pytest.mark.parametrize("seq", [128, 16])
def test_flash_attention_compiles(one_chip, seq):
    _compile(one_chip, fak.flash_attention, *_flash(seq))


@pytest.mark.parametrize("name, fn, shapes", [
    ("paged_decode_attention", dak.paged_decode_attention, PAGED),
    ("decode_attention", dak.decode_attention, DECODE),
    ("flash_attention", fak.flash_attention, _flash(128))])
def test_kernel_keeps_its_name_in_any_caller(one_chip, name, fn, shapes):
    """The device trace names a kernel's operation after its
    ``pallas_call``, and the benchmark's roofline readers match that
    name: a caller of another name, without the wrapper's own jit,
    must not rename it."""
    inner = fn.__wrapped__

    def renamed_caller(*args, interpret):
        return inner(*args, interpret=interpret)

    text = _compile(one_chip, renamed_caller, *shapes)
    ops = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                     r"\"tpu_custom_call\"", text)
    assert ops and all(op.rsplit(".", 1)[0] == name for op in ops), ops


@pytest.mark.parametrize("vocab", [CFG.vocab, 2])
def test_entropy_stats_compiles(one_chip, vocab):
    _compile(one_chip, entk.entropy_stats, ((32, vocab), jnp.float32))


# the decode-path programs at stablelm-3b widths, three layers deep
# (three so that a layer index mixed up with another cannot pass)
POOL_LAYERS, PLEN = 3, 128
POOL_CFG = CFG.replace(n_layers=POOL_LAYERS, kv_block_size=BS,
                       attn_impl="pallas", remat=False)


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


def _program_operands(eng, program: str, sharding) -> tuple:
    """Abstract operands of one of the engine's pool programs: the
    fused decode window, a full prefill wave, a one-row insert."""
    def a(dt, *shape):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    i32, f32 = jnp.int32, jnp.float32
    pool = _abstract(jax.eval_shape(
        lambda: tfm.init_cache(POOL_CFG, B, MAX_SEQ)), sharding)
    state = (a(i32, B, 1), a(i32, B), a(bool, B), a(i32, B))
    if program == "window":
        return (eng.params, pool, *state, a(i32, B),
                a(jnp.uint32, B, 2), a(f32, B), a(i32, B), a(f32, B))
    if program == "prefill":
        return (eng.params, a(i32, B, PLEN), pool, a(i32, B),
                a(i32, B, MB), *state, a(i32, B), a(i32, B), a(i32, B),
                a(jnp.uint32, B, 2), a(f32, B), a(i32, B), a(f32, B))
    rows = _abstract(jax.eval_shape(lambda: tfm.init_cache(
        POOL_CFG, 1, PLEN, layout="contiguous")), sharding)
    return (pool, rows, a(i32, 1), a(i32, 1, MB), a(i32, 1), a(i32, 1),
            *state, a(i32, 1), a(i32, B), a(i32, 1))


@pytest.mark.parametrize("program", ["window", "prefill", "insert"])
def test_paged_programs_keep_the_pool_in_place(one_chip, monkeypatch,
                                               program):
    """The paged pool programs — the fused decode window (8 slots x
    512, 16-row blocks, 8 micro-steps), a prefill wave of 8 and a
    disaggregated insert — hold no op that copies, slices or rewrites
    a whole K/V pool.  The only ops that produce the stacked
    [L, NB, bs, K, hdp] or a one-layer [NB, bs, K, hdp] pool shape are
    the scatters that write new rows into it, the pool's parameters and
    the loop plumbing that passes it on: the layer loop carries the
    stacked pool, the kernel reads its layer in place, and the pool's
    head axis, padded from 80 to 128 lanes, makes the device's default
    layout of the pool the row-major one the kernel reads (at 80 it
    puts the block axis minor-most, and each program would convert
    both pools at entry and exit)."""
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    params = _abstract(jax.eval_shape(
        lambda: tfm.init_lm(POOL_CFG, jax.random.PRNGKey(0))), one_chip)
    eng = ContinuousBatchingEngine(POOL_CFG, params, n_slots=B,
                                   max_seq=MAX_SEQ, sync_every=8)
    fn = {"window": eng._step_k,
          "prefill": eng._prefill_bucket_paged(B, PLEN),
          "insert": eng._insert_bucket_paged(PLEN)}[program]
    text = fn.lower(*_program_operands(eng, program, one_chip)) \
        .compile().as_text()
    if program == "window":
        assert "tpu_custom_call" in text
    nb = eng.pool_blocks
    hdp = attn.pool_head_dim(HD)
    pool = rf"bf16\[(?:{POOL_LAYERS},)?{nb},{BS},{K},{hdp}\]"
    made = re.findall(rf"^\s*(?:ROOT )?%(\S+) = {pool}\S* ([\w-]+)\("
                      rf"([^\n]*)$", text, re.M)
    assert made
    passing = {"parameter", "get-tuple-element", "tuple", "bitcast",
               "while", "scatter"}
    bad = [name for name, op, rest in made
           if op not in passing
           and not (op == "fusion" and re.search(
               r'op_name="[^"]*/scatter"', rest))]
    assert not bad, bad
