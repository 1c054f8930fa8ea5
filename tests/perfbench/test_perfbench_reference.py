"""The plain float32 reference against the program's paged path, and
the int8 control against both, on the CPU at smoke size.

The program's side is prefill into a contiguous row cache, the block
scatter into the paged pool, then cached decode steps through the pool
(``repro.models.transformer`` and ``repro.serving.continuous``, the
functions the engine's prefill and window programs compose), greedy,
in bf16.  The reference runs once over the prompt followed by the
decoded tokens.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import check, driver, spec, weights
from harness.driver import Stamp
from harness.traffic import Planned

PLEN, NEW, B = 16, 12, 3
# bf16 weights, activations and KV cache against float32 everywhere:
# logits of unit scale differ by ~1e-2 at this size; 0.08 leaves room
# on both sides and is far below the spread of the logits (~1)
LOGIT_ATOL = 0.08


def _program_logits(cell, params, prompts):
    from repro.models import transformer as tfm
    from repro.serving.continuous import paged_slot_write
    cfg = driver.model_config(cell)
    bs = cfg.kv_block_size
    max_seq = int(cell.setup["max_seq"])
    rows = tfm.init_cache(cfg, B, PLEN, layout="contiguous")
    logits, rows = tfm.prefill(cfg, params, jnp.asarray(prompts), rows)
    pool = tfm.init_cache(cfg, B, max_seq)
    mb = pool.block_table.shape[1]
    table = (1 + np.arange(B * mb, dtype=np.int32)).reshape(B, mb)
    pool = paged_slot_write(pool, rows, jnp.arange(B, dtype=jnp.int32),
                            jnp.asarray(table), block_size=bs,
                            n_pref_blocks=PLEN // bs)
    out = [logits[:, -1].astype(jnp.float32)]
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    toks = [tok[:, 0]]
    pos = jnp.full((B,), PLEN, jnp.int32)
    for _ in range(NEW - 1):
        lg, pool = tfm.decode_step(cfg, params, tok, pool, pos)
        out.append(lg[:, 0].astype(jnp.float32))
        tok = jnp.argmax(lg[:, 0], axis=-1)[:, None].astype(jnp.int32)
        toks.append(tok[:, 0])
        pos = pos + 1
    return (np.stack([np.asarray(x) for x in out], 1),
            np.stack([np.asarray(x) for x in toks], 1))


@pytest.mark.parametrize("cell_name", ["tiny-gqa.chat", "tiny-mha.chat"])
def test_reference_matches_prefill_then_paged_decode(tiny_bench,
                                                     cell_name):
    cell = tiny_bench.cell(cell_name)
    cfg = cell.config
    ref = spec.reference_module(cell)
    params = weights.make_params(cfg, 5)
    prompts = np.random.default_rng(5).integers(
        0, cfg["vocab"], (B, PLEN)).astype(np.int32)
    prog, toks = _program_logits(cell, params, prompts)
    seq = np.concatenate([prompts, toks[:, :-1]], axis=1)
    want = np.asarray(ref.logits(cfg, params, jnp.asarray(seq), PLEN - 1))
    assert want.shape == prog.shape == (B, NEW, cfg["vocab"])
    err = float(np.max(np.abs(want - prog)))
    assert err <= LOGIT_ATOL, err
    # the same comparison the benchmark makes: greedy tokens sit within
    # rounding of the reference's best
    gap = np.max(want.max(-1) - np.take_along_axis(
        want, toks[..., None], -1)[..., 0])
    assert gap <= 2 * LOGIT_ATOL


def _served(cell, seed, n=8, new=32):
    """Serve ``n`` greedy requests through the program's paged decode
    session (prefill wave + fused windows); Stamps of the answers."""
    from repro.serving.continuous import GenRequest
    params = weights.make_params(cell.config, seed)
    system = driver.System(cell, params)
    sess = system.engine.start_session(system.plen)
    rng = np.random.default_rng(seed)
    reqs = [GenRequest(rid=i, prompt=rng.integers(
        0, cell.config["vocab"], system.plen).astype(np.int32),
        max_new=new) for i in range(n)]
    for r in reqs:
        sess.push(r)
    while not sess.idle:
        sess.advance()
    return [Stamp(plan=Planned(rid=r.rid, due_s=0.0, prompt=r.prompt,
                               max_new=new), due=0.0, last=1.0,
                  tokens=list(r.generated)) for r in reqs]


def test_int8_control_reads_wider_gaps_than_the_program(tiny_root):
    """The control (the reference in int8) is kept as a test at a size
    a test run holds: on three seeds, the widest gap of the token int8
    arithmetic puts first exceeds the program's widest gap on every
    seed, the tiny cells' limit sits between them, and the cell's own
    judgement finds the program correct and the control not."""
    cell = spec.load(tiny_root).cell("tiny-gqa.chat")
    cfg = dict(cell.config, d_model=512, n_layers=8, vocab=4096,
               n_heads=8, n_kv_heads=2, head_dim=64, d_ff=1024)
    mix = dict(cell.traffic, output=dict(cell.traffic["output"], max=32))
    cell = spec.Cell(**{**cell.__dict__, "config": cfg, "traffic": mix})
    prog, ctl, mprog, mctl = [], [], [], []
    for seed in (0, 1, 2):
        stamps = _served(cell, seed)
        out = check.compare(cell, seed, stamps, control=True)
        prog.append(out["logit_gap"])
        ctl.append(out["control_logit_gap"])
        mprog.append(out["mean_gap"])
        mctl.append(out["control_mean_gap"])
        assert check.judge(out, cell.limits)[1]
        assert not check.judge(out, cell.limits, prefix="control_")[1]
    limit = cell.limits["logit_gap"]
    assert max(prog) < limit < min(ctl), (prog, ctl, limit)
    # the mean gap alone separates them too, as it must where a cell
    # holds only that reading
    assert max(mprog) < cell.limits["mean_gap"] < min(mctl)
    jax.clear_caches()
