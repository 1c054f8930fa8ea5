"""The comparison that decides ``correct``.

Once the window has closed, the program's state is freed and the
device's peak memory read, a sample of the requests the window
finished is drawn from the seed, the longest one always among them,
until it holds at least ``check.tokens`` served tokens.  The plain
reference (``configs/<reference>.py``, float32 at the highest matmul
precision) makes the same weights again from the seed and runs once
over each prompt followed by its served tokens.  For every served
token it reads the GAP: how far that token's logit lies below the
reference's best logit at that position.  Greedy decoding in the
served precision picks the reference's best token or one within
rounding of it, so the widest gap stays small; a token altered where
it is produced, a wrong position, a stale cache row or a cruder
arithmetic widens it.

The control (``quant="int8"``) is the reference computed in the next
precision below bf16: at the same positions it reads the gap of the
token that int8 arithmetic puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import spec, weights


def sample(stamps, seed: int, target_tokens: int) -> list:
    """The longest finished request, then others in a seeded order,
    until ``target_tokens`` served tokens are in the sample."""
    done = [s for s in stamps if s.tokens]
    if not done:
        return []
    done.sort(key=lambda s: (-len(s.tokens), s.plan.rid))
    picked, total = [done[0]], len(done[0].tokens)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    for i in rng.permutation(len(done) - 1) + 1:
        if total >= target_tokens:
            break
        picked.append(done[i])
        total += len(done[i].tokens)
    return picked


@functools.partial(jax.jit, static_argnames=("with_control",))
def _gaps(ref, ctl, served, n_valid, with_control: bool):
    """ref/ctl [R, T, V] logits; served [R, T] tokens; n_valid [R].
    Per row: the widest and the summed gap of the served tokens, and
    how many of them are not the reference's first choice; with the
    control, the same three for the token the control puts first."""
    best = jnp.max(ref, axis=-1)
    top = jnp.argmax(ref, axis=-1)
    valid = jnp.arange(ref.shape[1])[None, :] < n_valid[:, None]

    def read(tok):
        at = jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
        gap = jnp.where(valid, best - at, 0.0)
        miss = jnp.where(valid, tok != top, False)
        return jnp.stack([gap.max(1), gap.sum(1),
                          miss.sum(1).astype(gap.dtype)], axis=1)

    prog = read(served)
    return prog, (read(jnp.argmax(ctl, axis=-1)) if with_control
                  else prog)


def compare(cell, seed: int, picked: list, *, control: bool = False
            ) -> dict:
    """Run the reference over ``picked`` and return the widest gap of a
    served token (``logit_gap``), their mean gap and the share that
    is not the reference's first choice; with ``control``, the same
    readings of the int8 control's first choice (``control_*``)."""
    cfg = cell.config
    ref = spec.reference_module(cell)
    plen = int(cell.traffic["prompt_len"])
    max_out = int(cell.traffic["output"]["max"])
    rows = int(cell.setup["check"]["rows_per_block"])
    S = plen + max_out - 1
    params = weights.make_params(cfg, seed, cell.bench_dir)
    prog, ctl = [], []
    for b in range(0, len(picked), rows):
        blk = picked[b:b + rows]
        toks = np.zeros((rows, S), np.int32)
        served = np.zeros((rows, max_out), np.int32)
        nval = np.zeros((rows,), np.int32)
        for r, st in enumerate(blk):
            out = np.asarray(st.tokens, np.int32)
            seq = np.concatenate([st.plan.prompt, out[:-1]])
            toks[r, :len(seq)] = seq
            served[r, :len(out)] = out
            nval[r] = len(out)
        tok_d = jnp.asarray(toks)
        lg = ref.logits(cfg, params, tok_d, plen - 1)
        lc = (ref.logits(cfg, params, tok_d, plen - 1, quant="int8")
              if control else lg)
        p, c = _gaps(lg, lc, jnp.asarray(served), jnp.asarray(nval),
                     with_control=control)
        del lg, lc
        prog.append(np.asarray(p)[:len(blk)])
        ctl.append(np.asarray(c)[:len(blk)])
    del params
    n = int(sum(len(s.tokens) for s in picked))
    out = {"requests": len(picked), "tokens": n}
    for name, rows_ in (("", prog), ("control_", ctl)):
        if name and not control:
            continue
        if not n:                     # nothing compared: no reading
            for k in READINGS:
                out[name + k] = float("inf")
            continue
        a = np.concatenate(rows_)
        out[name + "logit_gap"] = float(a[:, 0].max())
        out[name + "mean_gap"] = float(a[:, 1].sum() / n)
        out[name + "mismatch"] = float(a[:, 2].sum() / n)
    return out


READINGS = ("logit_gap", "mean_gap", "mismatch")


def judge(readings: dict, limits: dict, *, unanswered: int = 0,
          malformed: int = 0, prefix: str = "") -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether all hold.

    ``limits`` (the cell file's) names which of the readings a cell
    holds; ``prefix="control_"`` judges the control's readings of the
    same sample by the same limits."""
    checks = {k: {"value": float(readings[prefix + k]),
                  "limit": float(v)} for k, v in limits.items()}
    checks["unanswered"] = {"value": unanswered, "limit": 0}
    checks["malformed"] = {"value": malformed, "limit": 0}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def answer_faults(cell, stamps, vocab: int) -> tuple[int, int]:
    """(unanswered, malformed): requests sent in the window that never
    completed, and completed ones whose answer is not ``max_new``
    tokens inside the vocabulary (random weights emit no EOS, so every
    request runs to its budget)."""
    unanswered = sum(1 for s in stamps if s.last is None)
    bad = 0
    for s in stamps:
        if s.last is None:
            continue
        t = np.asarray(s.tokens)
        if (len(t) != s.plan.max_new or
                (len(t) and (t.min() < 0 or t.max() >= vocab))):
            bad += 1
    return unanswered, bad
