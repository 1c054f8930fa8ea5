"""Continuous batching for LM decode (beyond-paper, vLLM-style).

Fixed pool of B slots over one shared KV cache; every decode step
advances ALL active slots (each at its own absolute position — the
per-row `pos` vector path through the unified transformer), finished
slots are refilled from the queue.  Decode is the serving regime where
energy ∝ occupied-slot-steps, so slot occupancy — not model FLOPs —
sets joules/request; the admission controller (enqueue-time, same
middleware surface as every other path) prunes low-value requests
before they ever occupy a slot.

Invariants this module maintains (who may touch what):

- **Slot ownership.**  A slot belongs to exactly one ``GenRequest``
  from the prefill that seats it until the host sync that harvests its
  completion; only ``DecodeSession`` assigns or clears slots.  Between
  host syncs ALL slot state (KV pool, ``cur_tok``, ``pos``, ``active``,
  ``remaining``) lives on device and nothing outside the fused window
  may write it.
- **Hot path is in-graph.**  One jit'd ``lax.scan`` advances
  ``sync_every`` micro-steps with the KV pool donated
  (``donate_argnums``); the host syncs once per window to harvest
  tokens and refill.  On the paged path the pool is written in place:
  the stacked pool rides the window's and the layer loop's carries,
  each layer scatters its token into it and the paged kernel reads its
  layer of the stack, and the pool's head axis is padded to a lane
  multiple so that its default device layout is the row-major one the
  kernel reads (``attn.pool_head_dim``): no program copies, slices or
  restacks the pool.  Refills prefill up to
  ``n_free`` prompts in ONE bucketed contiguous row cache whose rows
  are scattered straight into pool slots inside the same jit.
- **Block ownership (paged pool, ``cfg.kv_block_size > 0``).**  KV
  rows live in one shared pool of ``kv_pool_blocks`` x
  ``kv_block_size`` rows per layer; a request owns the physical blocks
  listed in its slot's block-table row from allocation at prefill
  until the host sync that completes it.  ``DecodeSession`` is the
  ONLY allocator: blocks are reserved for the request's whole budget
  (``prompt + max_new`` rows, so a window can never run out
  mid-decode), freed at completion, and a queued request WAITS when
  the pool can't cover its budget — it is never dropped.  Block 0 is
  the reserved trash block: retired slots still being stepped inside a
  window write there harmlessly, and are excluded from attention by
  the per-slot ``pos`` validity mask, never by the table itself.
  The contiguous layout (``kv_block_size == 0``) remains the parity
  oracle — byte-identical greedy tokens, enforced by tests and the
  ``continuous_perf`` smoke gate.
- **Legacy loop.**  The pre-fused per-step host loop survives only as
  ``serve(..., legacy=True)`` — the parity baseline and the "before"
  row of ``benchmarks/continuous_perf.py``.  It is contiguous-only and
  refuses paged configs.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.controller import AdmissionController, DraftDepthController
from repro.models import attention as attn
from repro.models import transformer as tfm
from repro.serving import sampling
from repro.serving.sampling import SamplingParams
from repro.telemetry.trace import NULL_TRACER


@dataclass
class GenRequest:
    rid: int
    prompt: np.ndarray               # [S] int32
    max_new: int = 16
    entropy_hint: float = 0.5        # L(x) proxy at enqueue time
    arrival_t: float | None = None   # admission clock (workload arrival_s)
    eos_id: int | None = None        # stop after emitting this token
    sampling: SamplingParams | None = None   # None = engine default

    generated: list = field(default_factory=list)
    done: bool = False
    admitted: bool = True
    slot: int | None = None          # decode slot it occupied (telemetry)


@dataclass
class SlotClock:
    """The virtual-time core of the slot-pool decode model.

    ``n_slots`` independent free-at lines — the modelled analogue of
    :class:`DecodeSession`'s slot bank, where a request occupies one
    decode slot for its whole service and new work lands in the
    earliest-free slot.  The fleet's ``SimContinuousEngine`` wraps this
    instead of re-modelling slot serialisation, so the sim's occupancy
    and pressure semantics mirror the live engine's: ``pressure(now)``
    is how long a NEW arrival would wait for a slot (zero while any
    slot is free), ``busy(now)`` is the live-occupancy count the
    adapter reports as batch fill.  Side-effect-free to poll."""
    n_slots: int = 8
    free_at: list[float] = field(default_factory=list)

    def __post_init__(self):
        if not self.free_at:
            self.free_at = [0.0] * self.n_slots

    def reserve(self, now: float, dur: float) -> tuple[int, float, float]:
        """Seat ``dur`` seconds of decode in the earliest-free slot."""
        i = min(range(self.n_slots), key=lambda s: self.free_at[s])
        start = max(now, self.free_at[i])
        finish = start + dur
        self.free_at[i] = finish
        return i, start, finish

    def pressure(self, now: float) -> float:
        return max(min(self.free_at) - now, 0.0)

    def busy(self, now: float) -> int:
        return sum(f > now for f in self.free_at)

    def reset(self) -> None:
        self.free_at = [0.0] * self.n_slots


# ---------------------------------------------------------------------------
# slot writes: batched rows -> pool slots
# ---------------------------------------------------------------------------

def _leaf_batch_axis(shape_a: tuple, shape_b: tuple) -> int:
    """Batch axis of one cache leaf, from the SAME leaf's shape under
    two different batch sizes.  Returns -1 for leaves that carry no
    batch dimension (per-layer length bookkeeping); raises on layouts
    where the batch axis cannot be identified unambiguously."""
    if len(shape_a) != len(shape_b):
        raise ValueError(
            f"cache leaf rank changed with batch size: {shape_a} vs "
            f"{shape_b} — unknown cache layout")
    if shape_a == shape_b:
        return -1
    diffs = [i for i, (x, y) in enumerate(zip(shape_a, shape_b)) if x != y]
    if len(diffs) != 1:
        raise ValueError(
            f"cache leaf has no unique batch axis: {shape_a} vs "
            f"{shape_b} differ on axes {diffs}")
    return diffs[0]


def cache_batch_axes(cfg: ModelConfig, max_seq: int):
    """Per-leaf batch-axis tree for ``tfm.init_cache``'s layout.

    Derived structurally (``jax.eval_shape`` at two batch sizes — no
    allocation), so stacked [L, B, ...] leaves, per-layer [B, ...]
    lists, MLA/recurrent states and the scalar length bookkeeping are
    all classified exactly instead of by the old guess-the-axis
    heuristic."""
    s2 = jax.eval_shape(
        lambda: tfm.init_cache(cfg, 2, max_seq, layout="contiguous"))
    s3 = jax.eval_shape(
        lambda: tfm.init_cache(cfg, 3, max_seq, layout="contiguous"))
    return jax.tree_util.tree_map(
        lambda a, b: _leaf_batch_axis(a.shape, b.shape), s2, s3)


def slot_write(pool_cache, row_cache, slot_idx, axes):
    """Scatter a batched row cache (batch nb) into pool slots.

    ``slot_idx`` [nb] int32 — target slot per row; out-of-range
    indices (>= n_slots, used for bucket-padding rows) are DROPPED.
    Leaves whose shapes don't match the derived batch axis raise
    instead of silently keeping the stale pool row."""
    def leaf(pool, row, ax):
        if ax < 0:
            return pool              # no batch dim (length bookkeeping)
        if (pool.ndim != row.ndim
                or pool.shape[:ax] != row.shape[:ax]
                or pool.shape[ax + 1:] != row.shape[ax + 1:]):
            raise ValueError(
                f"cache leaf {row.shape} does not fit pool leaf "
                f"{pool.shape} at batch axis {ax} — refusing to drop "
                f"the prefilled row")
        idx = (slice(None),) * ax + (slot_idx,)
        return pool.at[idx].set(row.astype(pool.dtype), mode="drop")

    return jax.tree_util.tree_map(leaf, pool_cache, row_cache, axes)


def _splice(pool_cache, row_cache, slot: int):
    """Insert a batch-1 cache into the pool at batch index ``slot``
    (the LEGACY per-request refill path).

    The batch axis is wherever the pool's extent differs from the
    row's; equal-shaped leaves carry no batch dim (length bookkeeping)
    and pass through.  More than one differing axis means the layout
    is unknown — raise rather than silently dropping the row (the old
    heuristic returned the pool unchanged).  A batch-1 pool is
    indistinguishable from the row (EVERY leaf equal-shaped, so no
    batch axis is ever found) — that case raises too, instead of
    silently returning the pool unchanged: the row IS the pool, so the
    caller must assign it directly rather than splice."""
    spliced = 0

    def leaf_splice(pool, row):
        nonlocal spliced
        if not hasattr(pool, "ndim"):
            return pool
        ax = _leaf_batch_axis(tuple(row.shape), tuple(pool.shape))
        if ax < 0:
            return pool
        spliced += 1
        idx = [slice(None)] * pool.ndim
        idx[ax] = slot
        return pool.at[tuple(idx)].set(
            jnp.squeeze(row, axis=ax).astype(pool.dtype))

    out = jax.tree_util.tree_map(leaf_splice, pool_cache, row_cache)
    if not spliced:
        raise ValueError(
            "_splice found no leaf with a batch axis — the pool is "
            "batch-1 (shape-identical to the row), which a splice "
            "cannot express.  Assign the row cache AS the pool instead "
            "(n_slots == 1 special case).")
    return out


def _bucket(n: int) -> int:
    """Prefill batch bucket: the serving-wide power-of-two buckets,
    never below ``n`` (a dropped prefill row would lose a request)."""
    from repro.serving.engine import bucket_size
    return max(bucket_size(n), n)


# ---------------------------------------------------------------------------
# paged pool: block-granular prefill scatter + sizing helpers
# ---------------------------------------------------------------------------

def paged_slot_write(pool, rows, slot_idx, table_rows, *,
                     block_size: int, n_pref_blocks: int):
    """Scatter a contiguous prefill ROW cache into paged pool blocks.

    ``pool`` is a paged ``tfm.Cache`` (homogeneous all-attn: stacked
    pool-layout KV leaves); ``rows`` a contiguous row cache of batch
    ``nb`` whose first ``n_pref_blocks * block_size`` rows hold the
    prefilled prompt (written with their head axis zero-padded to the
    pool's).  ``table_rows`` [nb, MB] is each row's FULL block-table
    row (prefill + decode-budget blocks, trash-padded); the kv
    scatter is BLOCK-granular — one indexed write per leaf, no
    per-row indirection.  Out-of-range ``slot_idx`` / table entries
    (bucket-padding rows) are dropped.  The per-slot ``pos`` row is
    rewritten wholesale (valid prompt prefix, -1 beyond), which also
    retires any stale validity left by the slot's previous owner."""
    pkv = pool.layers.kv
    rkv = rows.layers.kv
    P = n_pref_blocks * block_size
    tb = table_rows[:, :n_pref_blocks]                  # [nb, npb]

    def blkify(x):   # [L, nb, P, K, hd] -> [L, nb, npb, bs, K, hd_pool]
        x = attn.pad_head(x[:, :, :P], pkv.k.shape[-1])
        return x.reshape(x.shape[0], x.shape[1], n_pref_blocks,
                         block_size, *x.shape[3:])

    k = pkv.k.at[:, tb].set(blkify(rkv.k).astype(pkv.k.dtype),
                            mode="drop")
    v = pkv.v.at[:, tb].set(blkify(rkv.v).astype(pkv.v.dtype),
                            mode="drop")
    C = pkv.pos.shape[-1]
    rpos = jnp.pad(rkv.pos[:, :, :P], ((0, 0), (0, 0), (0, C - P)),
                   constant_values=-1)
    pos = pkv.pos.at[:, slot_idx].set(rpos, mode="drop")
    layers = pool.layers._replace(
        kv=pkv._replace(k=k, v=v, pos=pos))
    table = pool.block_table.at[slot_idx].set(table_rows, mode="drop")
    return pool._replace(layers=layers, block_table=table)


def blocks_for_request(plen: int, max_new: int, max_seq: int,
                       block_size: int) -> int:
    """Physical blocks a request needs for its WHOLE lifetime.

    Rows written = padded prompt rows + one row per decode step, plus
    the frozen-position row a retired slot keeps rewriting inside a
    fused window (hence ``max(max_new, 2)``), clamped by the engine's
    ``pos < max_seq - 1`` stop.  Reserving this up front is what makes
    pool exhaustion a QUEUE-time condition: an admitted request can
    never run out of blocks mid-decode."""
    rows = min(plen + max(max_new, 2), max_seq)
    return -(-rows // block_size)


def pool_hbm_bytes(cfg: ModelConfig, n_slots: int, max_seq: int,
                   dtype=jnp.bfloat16) -> dict:
    """Modelled HBM footprint of the decode cache (no allocation).

    Returns ``kv_bytes`` (the K/V rows themselves — the part paging
    shrinks), ``meta_bytes`` (position/validity vectors, block table,
    length bookkeeping) and their sum.  Layout follows
    ``cfg.kv_block_size``."""
    import numpy as _np
    cache = jax.eval_shape(
        lambda: tfm.init_cache(cfg, n_slots, max_seq, dtype))

    def nbytes(tree) -> int:
        return int(sum(
            _np.prod(l.shape) * jnp.dtype(l.dtype).itemsize
            for l in jax.tree_util.tree_leaves(tree)))

    total = nbytes(cache)
    try:
        kv = nbytes((cache.layers.kv.k, cache.layers.kv.v))
    except AttributeError:      # heterogeneous / recurrent layouts
        kv = total
    return {"kv_bytes": kv, "meta_bytes": total - kv,
            "total_bytes": total}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

@dataclass
class ContinuousBatchingEngine:
    cfg: ModelConfig
    params: dict
    n_slots: int = 8
    max_seq: int = 256
    controller: AdmissionController | None = None
    sync_every: int = 8              # fused micro-steps per host sync
    donate: bool = True              # donate pool buffers into the jit
    # self-speculative decoding: > 0 compiles the window's macro-step
    # variant — each step drafts ``draft_depth`` tokens through the
    # first ``cfg.draft_layers`` layers, then ONE full-model chunk pass
    # verifies them.  The compiled depth is the CEILING; the live depth
    # (``depth_cap``, a traced operand) is the energy lever the
    # spec_controller moves with zero retrace.
    draft_depth: int = 0
    spec_controller: DraftDepthController | None = None

    _decode: Callable = field(init=False, repr=False)
    _prefill1: Callable = field(init=False, repr=False)
    _step_k: Callable = field(init=False, repr=False)
    _prefill_b: dict = field(init=False, repr=False, default_factory=dict)
    _axes: object = field(init=False, repr=False)

    def __post_init__(self):
        cfg = self.cfg
        max_seq = self.max_seq
        k = max(int(self.sync_every), 1)
        self.sync_every = k
        if self.draft_depth < 0:
            raise ValueError(
                f"draft_depth must be >= 0, got {self.draft_depth}")
        if self.draft_depth > 0:
            if cfg.paged_kv:
                raise ValueError(
                    "self-speculative decoding serves the contiguous "
                    "KV layout only (the verify chunk is a multi-row "
                    "scatter the paged pool cannot express); set "
                    "draft_depth=0 for paged engines")
            if cfg.draft_layers <= 0:
                raise ValueError(
                    "draft_depth > 0 needs cfg.draft_layers in "
                    "[1, n_layers) — the draft is a shallow prefix of "
                    "the same stack")
            kinds = set(cfg.block_kinds)
            if not kinds <= {"attn", "local_attn"} \
                    or cfg.family == "encdec":
                raise ValueError(
                    f"self-speculative decoding needs a pure attention "
                    f"stack; got kinds={sorted(kinds)} "
                    f"family={cfg.family}")
            if self.spec_controller is None:
                self.spec_controller = DraftDepthController(
                    max_depth=self.draft_depth,
                    draft_cost=cfg.draft_layers / cfg.n_layers)
        # slot-scatter axes serve the CONTIGUOUS layout only (legacy
        # splice + fused slot_write); the paged pool has its own
        # block-granular scatter, so derive them from the contiguous
        # layout even when the engine itself is paged.
        self._axes = cache_batch_axes(cfg, max_seq)
        self.paged = cfg.paged_kv
        if self.paged:
            (self.blocks_per_slot, self.logical_len,
             self.pool_blocks) = tfm.paged_geometry(cfg, self.n_slots,
                                                    max_seq)

        # legacy per-step path (parity baseline + before/after bench)
        @jax.jit
        def decode(params, token, cache, pos):
            return tfm.decode_step(cfg, params, token, cache, pos)

        @jax.jit
        def prefill1(params, tokens, cache):
            return tfm.prefill(cfg, params, tokens, cache)

        self._decode = decode
        self._prefill1 = prefill1

        # fused k-step window: sampling, emission masks, EOS/max-new
        # done-masks and position bookkeeping all stay on device; ONE
        # host sync per window.  The pool is donated to the window;
        # the paged pool is also written in place inside it (module
        # docstring), the contiguous one is not.  ``eos`` [B] is
        # the per-slot stop token (-1 = none; token ids are >= 0 so it
        # never matches).  The per-slot PRNG key rides the scan carry:
        # the token written at absolute position q is sampled with
        # ``fold_in(slot_key, q)``, so the stream depends only on
        # (seed, rid, position) — never on window boundaries, refill
        # timing, or (speculative) HOW the engine reached q.
        # temp/topk/topp are traced VALUES: changing them never
        # retraces the window.
        self._decode_traces = 0

        def step_k(params, pool, cur_tok, pos, active, remaining, eos,
                   skey, temp, topk, topp):
            self._decode_traces += 1         # trace-time side effect:
                                             # counts (re)compiles
            def body(carry, _):
                pool, tok, pos, act, rem, keyc = carry
                logits, pool = tfm.decode_step(cfg, params, tok, pool,
                                               pos)
                keys = sampling.step_keys(keyc, pos + 1)
                nxt = sampling.sample_token(keys, logits[:, 0], temp,
                                            topk, topp)
                new_pos = jnp.where(act, pos + 1, pos)
                new_rem = jnp.where(act, rem - 1, rem)
                alive = (act & (new_rem > 0) & (new_pos < max_seq - 1)
                         & (nxt != eos))
                new_tok = jnp.where(act, nxt, tok[:, 0])[:, None]
                return (pool, new_tok, new_pos, alive, new_rem,
                        keyc), (nxt, act)

            carry = (pool, cur_tok, pos, active, remaining, skey)
            carry, (toks, emitted) = jax.lax.scan(body, carry, None,
                                                  length=k)
            pool, cur_tok, pos, active, remaining, _ = carry
            return pool, cur_tok, pos, active, remaining, toks, emitted

        # self-speculative macro-step window: each of the k macro-steps
        # drafts D tokens through the first ``draft_layers`` layers
        # (scratch-sliced cache, discarded), then ONE full-model chunk
        # pass verifies [tok, t_1..t_D] and emits the longest accepted
        # prefix PLUS the full model's own next token — every emitted
        # token is the FULL model's sample under the same
        # position-folded key, so the stream byte-matches the
        # non-speculative path by construction.  ``depth_cap`` (traced)
        # caps accepted drafts per macro-step: the controller collapses
        # or widens the live depth with zero retrace.
        D = self.draft_depth
        dl = cfg.draft_layers

        def step_k_spec(params, pool, cur_tok, pos, active, remaining,
                        eos, skey, temp, topk, topp, depth_cap):
            self._decode_traces += 1
            dparams = dict(params)
            dparams["layers"] = jax.tree_util.tree_map(
                lambda x: x[:dl], params["layers"])
            n = D + 1

            def body(carry, _):
                pool, tok, pos, act, rem, keyc = carry
                B = tok.shape[0]
                # draft: D shallow steps on a sliced scratch cache.
                # The slice is a functional copy — verify rewrites the
                # REAL pool's rows (all layers) for every fed position.
                dcache = tfm.Cache(
                    layers=jax.tree_util.tree_map(lambda x: x[:dl],
                                                  pool.layers),
                    cross=pool.cross, length=pool.length,
                    block_table=None)

                def draft_body(dc, _):
                    dcache, dtok, dpos = dc
                    lg, dcache = tfm.decode_step(cfg, dparams, dtok,
                                                 dcache, dpos)
                    keys = sampling.step_keys(keyc, dpos + 1)
                    t = sampling.sample_token(keys, lg[:, 0], temp,
                                              topk, topp)
                    return (dcache, t[:, None], dpos + 1), t

                _, drafts = jax.lax.scan(
                    draft_body, (dcache, tok, pos), None, length=D)
                # drafts [D, B]: proposals for positions pos+1..pos+D
                chunk = jnp.concatenate([tok, drafts.T], axis=1)
                logits, pool = tfm.decode_chunk(cfg, params, chunk,
                                                pool, pos)
                # full-model samples at positions pos+1..pos+D+1 — the
                # SAME keys sequential decode would fold, flattened to
                # one [B*(D+1)] sample_token call (row-independent)
                posm = (pos[:, None] + 1
                        + jnp.arange(n, dtype=jnp.int32)[None])
                keys = sampling.step_keys(
                    jnp.repeat(keyc, n, axis=0), posm.reshape(-1))
                full = sampling.sample_token(
                    keys, logits.reshape(B * n, -1),
                    jnp.repeat(temp, n), jnp.repeat(topk, n),
                    jnp.repeat(topp, n)).reshape(B, n)
                # fold acceptance into the done-mask machinery:
                # emission j is live while every draft before it
                # matched the full model (and j <= depth_cap); retire
                # flags (EOS / budget / seq-end) cut the chain exactly
                # as the per-step window would
                tokc, posc, remc, actc = tok[:, 0], pos, rem, act
                ok = jnp.ones_like(act)
                toks_j, emit_j = [], []
                for j in range(n):
                    cand = full[:, j]
                    if j:
                        ok = (ok & (drafts[j - 1] == full[:, j - 1])
                              & (j <= depth_cap))
                    emit = actc & ok
                    new_pos = jnp.where(emit, posc + 1, posc)
                    new_rem = jnp.where(emit, remc - 1, remc)
                    retire = emit & ((new_rem <= 0)
                                     | (new_pos >= max_seq - 1)
                                     | (cand == eos))
                    tokc = jnp.where(emit, cand, tokc)
                    posc, remc = new_pos, new_rem
                    actc = actc & ~retire
                    toks_j.append(cand)
                    emit_j.append(emit)
                return (pool, tokc[:, None], posc, actc, remc,
                        keyc), (jnp.stack(toks_j), jnp.stack(emit_j))

            carry = (pool, cur_tok, pos, active, remaining, skey)
            carry, (toks, emitted) = jax.lax.scan(body, carry, None,
                                                  length=k)
            pool, cur_tok, pos, active, remaining, _ = carry
            # toks/emitted [k, D+1, B] — chronological when flattened
            return pool, cur_tok, pos, active, remaining, toks, emitted

        self._step_k = jax.jit(
            step_k_spec if D > 0 else step_k,
            donate_argnums=(1,) if self.donate else ())

    # -- jit caches ---------------------------------------------------------
    @property
    def decode_compile_count(self) -> int:
        """How many times the fused decode window has been traced —
        the shape-drift regression guard (must stay 1 across refills).
        Counted by a trace-time side effect in the window body, so it
        needs no private JAX API."""
        return self._decode_traces

    # -- sampling / speculation ---------------------------------------------
    @property
    def default_sampling(self) -> SamplingParams:
        """Engine-level sampling defaults (from the model config);
        a request's own ``SamplingParams`` override them."""
        return SamplingParams(temperature=self.cfg.temperature,
                              top_k=self.cfg.sample_top_k,
                              top_p=self.cfg.sample_top_p,
                              seed=self.cfg.sampling_seed)

    def current_depth(self) -> int:
        """Live speculative depth for the next window: the
        spec_controller's energy-aware choice, clamped into
        [1, draft_depth] (the compiled ceiling)."""
        if self.draft_depth <= 0:
            return 0
        if self.spec_controller is None:
            return self.draft_depth
        if self.controller is not None:
            # brownout / admission pressure couples in: a shrunken
            # admission basin inflates the perceived draft cost
            self.spec_controller.tau_scale = self.controller.tau_scale
        d = self.spec_controller.decide()
        d = max(1, min(int(d), self.draft_depth))
        if self.controller is not None:
            self.controller.draft_depth_norm = d / self.draft_depth
        return d

    def _prefill_bucket(self, nb: int, plen: int) -> Callable:
        """Batched prefill for bucket size ``nb`` at prompt length
        ``plen``: prefill nb prompts in one call, scatter the rows
        straight into the pool slots, and flip the per-slot decode
        state (pos/cur_tok/active/remaining) in the same jit."""
        key = (nb, plen)
        fn = self._prefill_b.get(key)
        if fn is not None:
            return fn
        cfg, max_seq, axes = self.cfg, self.max_seq, self._axes

        def prefill_b(params, tokens, pool, slot_idx, cur_tok, pos,
                      active, remaining, rem_new, eos, eos_new,
                      skey_new, temp_new, topk_new, topp_new):
            rows = tfm.init_cache(cfg, nb, max_seq)
            logits, rows = tfm.prefill(cfg, params, tokens, rows)
            # the first token lands at absolute position plen — the
            # same (request key, position) fold decode will continue
            keys = sampling.step_keys(
                skey_new, jnp.full((nb,), plen, jnp.int32))
            first = sampling.sample_token(keys, logits[:, -1],
                                          temp_new, topk_new, topp_new)
            pool = slot_write(pool, rows, slot_idx, axes)
            cur_tok = cur_tok.at[slot_idx, 0].set(first, mode="drop")
            pos = pos.at[slot_idx].set(
                jnp.full((nb,), plen, jnp.int32), mode="drop")
            # a slot whose PREFILL token already hits EOS never decodes
            active = active.at[slot_idx].set(first != eos_new,
                                             mode="drop")
            remaining = remaining.at[slot_idx].set(rem_new, mode="drop")
            eos = eos.at[slot_idx].set(eos_new, mode="drop")
            return pool, first, cur_tok, pos, active, remaining, eos

        fn = jax.jit(prefill_b,
                     donate_argnums=(2, 4, 5, 6, 7, 9) if self.donate
                     else ())
        self._prefill_b[key] = fn
        return fn

    def _prefill_bucket_paged(self, nb: int, plen: int) -> Callable:
        """Paged twin of :meth:`_prefill_bucket`: prefill ``nb``
        prompts into a contiguous ROW cache sized to the prompt's
        block multiple, then block-scatter rows + block-table rows
        into the pool and flip the per-slot decode state, all in one
        jit.  ``table_rows`` [nb, MB] carries each request's full
        block assignment (host-allocated)."""
        key = ("paged", nb, plen)
        fn = self._prefill_b.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        cfg_bs = cfg.kv_block_size
        npb = -(-plen // cfg_bs)
        row_len = npb * cfg_bs

        def prefill_p(params, tokens, pool, slot_idx, table_rows,
                      cur_tok, pos, active, remaining, rem_new, eos,
                      eos_new, skey_new, temp_new, topk_new, topp_new):
            rows = tfm.init_cache(cfg, nb, row_len,
                                  layout="contiguous")
            logits, rows = tfm.prefill(cfg, params, tokens, rows)
            keys = sampling.step_keys(
                skey_new, jnp.full((nb,), plen, jnp.int32))
            first = sampling.sample_token(keys, logits[:, -1],
                                          temp_new, topk_new, topp_new)
            pool = paged_slot_write(pool, rows, slot_idx, table_rows,
                                    block_size=cfg_bs,
                                    n_pref_blocks=npb)
            cur_tok = cur_tok.at[slot_idx, 0].set(first, mode="drop")
            pos = pos.at[slot_idx].set(
                jnp.full((nb,), plen, jnp.int32), mode="drop")
            active = active.at[slot_idx].set(first != eos_new,
                                             mode="drop")
            remaining = remaining.at[slot_idx].set(rem_new, mode="drop")
            eos = eos.at[slot_idx].set(eos_new, mode="drop")
            return pool, first, cur_tok, pos, active, remaining, eos

        fn = jax.jit(prefill_p,
                     donate_argnums=(2, 5, 6, 7, 8, 10) if self.donate
                     else ())
        self._prefill_b[key] = fn
        return fn

    def _insert_bucket(self) -> Callable:
        """Seat ONE externally prefilled contiguous row cache into a
        pool slot (the disaggregated prefill->insert hand-off).  The
        row cache has the pool's full ``max_seq`` extent, so a single
        jit serves every prompt length — the landing position arrives
        as the ``pos_new`` operand, not as a trace constant."""
        key = "insert"
        fn = self._prefill_b.get(key)
        if fn is not None:
            return fn
        axes = self._axes

        def insert_b(pool, rows, slot_idx, first, pos_new, cur_tok,
                     pos, active, remaining, rem_new, eos, eos_new):
            pool = slot_write(pool, rows, slot_idx, axes)
            cur_tok = cur_tok.at[slot_idx, 0].set(first, mode="drop")
            pos = pos.at[slot_idx].set(pos_new, mode="drop")
            active = active.at[slot_idx].set(first != eos_new,
                                             mode="drop")
            remaining = remaining.at[slot_idx].set(rem_new, mode="drop")
            eos = eos.at[slot_idx].set(eos_new, mode="drop")
            return pool, cur_tok, pos, active, remaining, eos

        fn = jax.jit(insert_b,
                     donate_argnums=(0, 5, 6, 7, 8, 10) if self.donate
                     else ())
        self._prefill_b[key] = fn
        return fn

    def _insert_bucket_paged(self, plen: int) -> Callable:
        """Paged twin of :meth:`_insert_bucket`: the row cache spans
        the prompt's block multiple, so the jit cache is keyed by the
        block count (two prompt lengths inside one block multiple
        share a compile; ``pos_new`` still carries the exact landing
        position)."""
        cfg_bs = self.cfg.kv_block_size
        npb = -(-plen // cfg_bs)
        key = ("insert-paged", npb)
        fn = self._prefill_b.get(key)
        if fn is not None:
            return fn

        def insert_p(pool, rows, slot_idx, table_rows, first, pos_new,
                     cur_tok, pos, active, remaining, rem_new, eos,
                     eos_new):
            pool = paged_slot_write(pool, rows, slot_idx, table_rows,
                                    block_size=cfg_bs,
                                    n_pref_blocks=npb)
            cur_tok = cur_tok.at[slot_idx, 0].set(first, mode="drop")
            pos = pos.at[slot_idx].set(pos_new, mode="drop")
            active = active.at[slot_idx].set(first != eos_new,
                                             mode="drop")
            remaining = remaining.at[slot_idx].set(rem_new, mode="drop")
            eos = eos.at[slot_idx].set(eos_new, mode="drop")
            return pool, cur_tok, pos, active, remaining, eos

        fn = jax.jit(insert_p,
                     donate_argnums=(0, 6, 7, 8, 9, 11) if self.donate
                     else ())
        self._prefill_b[key] = fn
        return fn

    # -- admission ----------------------------------------------------------
    def _admit(self, requests: list[GenRequest]) -> list[GenRequest]:
        """Run the controller over the stream.  Each request is decided
        at its OWN arrival time when the workload supplies one
        (``arrival_t``); the legacy fixed-increment clock is only the
        fallback for hand-built request lists."""
        queue: list[GenRequest] = []
        t = 0.0
        for r in requests:
            if self.controller is not None:
                ta = (float(r.arrival_t) if r.arrival_t is not None
                      else t)
                d = self.controller.decide(r.entropy_hint, ta)
                r.admitted = d.admit
                t = ta + 0.001
            if r.admitted:
                queue.append(r)
            else:
                r.done = True                 # skipped (proxy/cache)
        return queue

    # -- serving ------------------------------------------------------------
    def start_session(self, prompt_len: int | None = None,
                      tracer=NULL_TRACER) -> "DecodeSession":
        return DecodeSession(self, prompt_len=prompt_len, tracer=tracer)

    def serve(self, requests: list[GenRequest], *,
              prompt_len: int | None = None,
              legacy: bool = False) -> dict:
        """Run all requests to completion; returns summary stats.

        Prompts are padded/truncated to one static prefill length so
        each prefill bucket compiles once.  ``legacy=True`` runs the
        old host-driven per-step loop (parity/benchmark baseline)."""
        wall0 = time.perf_counter()
        if legacy and self.paged:
            raise ValueError(
                "legacy=True serves the contiguous layout only; the "
                "paged pool's parity oracle is a contiguous engine "
                "(cfg.kv_block_size == 0)")
        queue = self._admit(list(requests))
        # batch mode pads every prompt to ONE static prefill length
        # (legacy semantics; incremental sessions pad per refill wave)
        plen = prompt_len or max((len(r.prompt) for r in queue),
                                 default=8)
        if legacy:
            stats = self._serve_legacy(queue, plen)
        else:
            session = self.start_session(plen)
            for r in queue:
                session.push(r)
            while not session.idle:
                session.advance()
            stats = session.stats()
        wall = time.perf_counter() - wall0
        stats.update(
            n_requests=len(requests),
            n_admitted=sum(r.admitted for r in requests),
            tokens_generated=sum(len(r.generated) for r in requests),
            wall_s=wall,
            host_s=max(wall - stats["device_s"], 0.0),
            host_sync_frac=(max(wall - stats["device_s"], 0.0)
                            / wall if wall > 0 else 0.0),
            steps_per_s=(stats["decode_steps"] / wall if wall > 0
                         else 0.0),
        )
        return stats

    def _serve_legacy(self, queue: list[GenRequest],
                      plen: int) -> dict:
        """The pre-PR-3 loop: batch-1 prefill + tree splice per refill,
        device→host argmax pull + per-slot Python loop per step."""
        cfg = self.cfg
        B = self.n_slots
        pool = tfm.init_cache(cfg, B, self.max_seq)
        slots: list[GenRequest | None] = [None] * B
        pos = np.zeros(B, np.int32)
        cur_tok = np.zeros((B, 1), np.int32)
        active = np.zeros(B, bool)
        skey_h = np.zeros((B, 2), np.uint32)
        temp_h = np.zeros(B, np.float32)
        topk_h = np.zeros(B, np.int32)
        topp_h = np.ones(B, np.float32)
        steps = 0
        occupied_slot_steps = 0
        prefills = 0
        device_s = 0.0

        def sampling_of(r):
            return (r.sampling if r.sampling is not None
                    else self.default_sampling)

        def refill():
            nonlocal pool, prefills, device_s
            s = 0
            while s < B:
                if active[s] or not queue:
                    s += 1
                    continue
                r = queue.pop(0)
                p = np.asarray(r.prompt[:plen], np.int32)
                if len(p) < plen:
                    p = np.pad(p, (0, plen - len(p)))
                row_cache = tfm.init_cache(cfg, 1, self.max_seq)
                t0 = time.perf_counter()
                logits, row_cache = jax.block_until_ready(
                    self._prefill1(self.params, jnp.asarray(p[None]),
                                   row_cache))
                device_s += time.perf_counter() - t0
                prefills += 1
                # B == 1: pool and row shapes coincide, so axis
                # detection can't see the batch dim — the row IS the
                # pool
                pool = (row_cache if B == 1
                        else _splice(pool, row_cache, s))
                sp = sampling_of(r)
                rkey = sampling.request_key(sp.seed, r.rid)
                first = int(np.asarray(sampling.sample_token(
                    sampling.step_keys(
                        jnp.asarray(rkey[None]),
                        jnp.asarray(np.array([plen], np.int32))),
                    logits[:, -1],
                    jnp.asarray(np.array([sp.temperature],
                                         np.float32)),
                    jnp.asarray(np.array([sp.top_k], np.int32)),
                    jnp.asarray(np.array([sp.top_p],
                                         np.float32))))[0])
                skey_h[s] = rkey
                temp_h[s] = sp.temperature
                topk_h[s] = sp.top_k
                topp_h[s] = sp.top_p
                r.generated.append(first)
                if r.eos_id is not None and first == r.eos_id:
                    r.done = True        # EOS at prefill: slot stays
                    continue             # free — retry it with the
                                         # next queued request
                slots[s] = r
                pos[s] = plen
                cur_tok[s, 0] = first
                active[s] = True
                s += 1

        refill()
        while any(active):
            steps += 1
            occupied_slot_steps += int(active.sum())
            t0 = time.perf_counter()
            logits, pool = jax.block_until_ready(
                self._decode(self.params, jnp.asarray(cur_tok), pool,
                             jnp.asarray(pos)))
            device_s += time.perf_counter() - t0
            nxt = np.asarray(sampling.sample_token(
                sampling.step_keys(jnp.asarray(skey_h),
                                   jnp.asarray(pos) + 1),
                logits[:, 0], jnp.asarray(temp_h),
                jnp.asarray(topk_h), jnp.asarray(topp_h)), np.int32)
            for s in range(B):
                if not active[s]:
                    continue
                r = slots[s]
                r.generated.append(int(nxt[s]))
                pos[s] += 1
                cur_tok[s, 0] = nxt[s]
                if len(r.generated) >= r.max_new \
                        or pos[s] >= self.max_seq - 1 \
                        or (r.eos_id is not None
                            and int(nxt[s]) == r.eos_id):
                    r.done = True
                    active[s] = False
                    slots[s] = None
            refill()

        return {
            "mode": "legacy",
            "sync_every": 1,
            "decode_steps": steps,
            "occupied_slot_steps": occupied_slot_steps,
            "occupancy": (occupied_slot_steps / (steps * B)
                          if steps else 0.0),
            "host_syncs": steps,
            "prefill_calls": prefills,
            "device_s": device_s,
        }


# ---------------------------------------------------------------------------
# incremental session — what the serving adapter drives
# ---------------------------------------------------------------------------

class DecodeSession:
    """One slot-pool decode session over an engine's jit caches.

    ``push`` enqueues at any time (continuous batching — arrivals
    interleave with decoding); ``advance`` refills free slots with one
    bucketed prefill, runs one fused ``sync_every``-step window, and
    returns the requests that completed in that window.  All decode
    state between windows lives on device.

    ``tracer`` scopes the host work of each ``advance``: ``sched.*``
    for the scheduler's own (refill, seating, table upload, harvest)
    and ``step.*`` for the jitted calls from their operand uploads to
    their results on the host (``step.prefill``, ``step.window``)."""

    def __init__(self, engine: ContinuousBatchingEngine,
                 prompt_len: int | None = None, tracer=NULL_TRACER):
        self.engine = engine
        self.prompt_len = prompt_len
        self.tracer = tracer
        B = engine.n_slots
        self.queue: list[GenRequest] = []
        self.slots: list[GenRequest | None] = [None] * B
        self._pool = tfm.init_cache(engine.cfg, B, engine.max_seq)
        self._cur_tok = jnp.zeros((B, 1), jnp.int32)
        self._pos = jnp.zeros((B,), jnp.int32)
        self._active = jnp.zeros((B,), bool)
        self._remaining = jnp.zeros((B,), jnp.int32)
        self._eos = jnp.full((B,), -1, jnp.int32)
        # per-slot sampling state (host mirror; device sees it as
        # traced operands each window).  Keys derive from the REQUEST
        # id at seat time — never the slot index — so a reused slot
        # can never replay its previous occupant's stream.
        self._skey_h = np.zeros((B, 2), np.uint32)
        self._temp_h = np.zeros(B, np.float32)
        self._topk_h = np.zeros(B, np.int32)
        self._topp_h = np.ones(B, np.float32)
        self._active_host = np.zeros(B, bool)
        self._prefill_done: list[GenRequest] = []
        # disaggregated hand-off: externally prefilled rows waiting
        # for a free slot.  Each entry is (request, rows, first, plen).
        self._insert_q: list[tuple] = []
        # paged pool: host-side block allocator.  The session is the
        # ONLY allocator; the device only ever sees the table it is
        # handed.  Block 0 is the trash block and never allocated.
        if engine.paged:
            self._free_blocks = list(range(1, engine.pool_blocks))
            self._slot_blocks: dict[int, list[int]] = {}
            self._table_h = np.zeros((B, engine.blocks_per_slot),
                                     np.int32)
            self._table_dirty = False
        # counters
        self.decode_steps = 0
        self.occupied_slot_steps = 0
        self.host_syncs = 0
        self.prefill_calls = 0
        self.insert_calls = 0
        self.device_s = 0.0
        self.blocks_allocated = 0
        self.blocks_freed = 0
        self.peak_blocks_in_use = 0
        # speculative decode telemetry
        self.spec_proposed = 0       # drafted tokens offered to verify
        self.spec_accepted = 0       # drafts the full model confirmed
        self.spec_draft_slot_steps = 0   # shallow passes (energy model)
        self.last_depth = engine.draft_depth

    # -- state --------------------------------------------------------------
    @property
    def idle(self) -> bool:
        return (not self.queue and not self._insert_q
                and not self._active_host.any())

    @property
    def n_active(self) -> int:
        return int(self._active_host.sum())

    @property
    def n_queued(self) -> int:
        return len(self.queue)

    def push(self, r: GenRequest) -> None:
        self.queue.append(r)

    # -- sampling -----------------------------------------------------------
    def _sampling_of(self, r: GenRequest) -> SamplingParams:
        return (r.sampling if r.sampling is not None
                else self.engine.default_sampling)

    def _seat_sampling(self, s: int, r: GenRequest) -> None:
        """Mirror one request's sampling state into its slot row."""
        sp = self._sampling_of(r)
        self._skey_h[s] = sampling.request_key(sp.seed, r.rid)
        self._temp_h[s] = sp.temperature
        self._topk_h[s] = sp.top_k
        self._topp_h[s] = sp.top_p

    def _sampling_rows(self, reqs, nb: int):
        """Per-row sampling operands for one prefill wave (pad rows
        beyond ``len(reqs)`` stay greedy/zero-key — their slot index is
        OOB so every write is dropped anyway)."""
        skey = np.zeros((nb, 2), np.uint32)
        temp = np.zeros(nb, np.float32)
        topk = np.zeros(nb, np.int32)
        topp = np.ones(nb, np.float32)
        for j, r in enumerate(reqs):
            sp = self._sampling_of(r)
            skey[j] = sampling.request_key(sp.seed, r.rid)
            temp[j] = sp.temperature
            topk[j] = sp.top_k
            topp[j] = sp.top_p
        return skey, temp, topk, topp

    # -- disaggregated insert -----------------------------------------------
    def insert_prefilled(self, r: GenRequest, rows, first: int,
                         plen: int) -> None:
        """Accept an EXTERNALLY prefilled request (disaggregated
        serving): ``rows`` is a batch-1 contiguous row cache holding
        the prompt's KV, ``first`` the greedy token the prefill pass
        emitted, ``plen`` the padded prompt length the rows were built
        at.  The request is seated into a free slot on the next
        ``advance`` — or waits in FIFO order if none is free."""
        self._insert_q.append((r, rows, first, plen))

    def _drain_inserts(self) -> None:
        """Seat queued externally-prefilled rows into free slots (the
        ``insert`` step of the prefill->insert->generate split).  FIFO:
        the head waits when no slot (or, paged, no block budget) is
        free; EOS-at-prefill completes host-side and never occupies a
        slot."""
        eng = self.engine
        B = eng.n_slots
        bs = eng.cfg.kv_block_size if eng.paged else 0
        while self._insert_q:
            r, rows, first, plen = self._insert_q[0]
            if r.eos_id is not None and first == r.eos_id:
                # EOS straight out of prefill: complete without ever
                # touching the pool
                self._insert_q.pop(0)
                r.generated.append(int(first))
                r.done = True
                self._prefill_done.append(r)
                continue
            free = [s for s in range(B) if not self._active_host[s]]
            if not free:
                return                       # all slots busy: wait
            s = free[0]
            if eng.paged:
                allocatable = eng.pool_blocks - 1
                need = blocks_for_request(plen, r.max_new, eng.max_seq,
                                          bs)
                if need > allocatable:
                    raise ValueError(
                        f"request rid={r.rid} needs {need} KV blocks "
                        f"(prompt {plen} + max_new {r.max_new} rows at "
                        f"block_size {bs}) but the pool has only "
                        f"{allocatable} allocatable blocks — it can "
                        f"never be inserted; raise kv_pool_blocks or "
                        f"shrink the request budget")
                if need > len(self._free_blocks):
                    return                   # pool exhausted: wait
                assigned = [self._free_blocks.pop()
                            for _ in range(need)]
                mb = eng.blocks_per_slot
                row = np.zeros((mb,), np.int32)
                row[:need] = assigned
                self.blocks_allocated += need
                self.peak_blocks_in_use = max(
                    self.peak_blocks_in_use,
                    allocatable - len(self._free_blocks))
                fn = eng._insert_bucket_paged(plen)
            else:
                fn = eng._insert_bucket()
            self._insert_q.pop(0)
            slot_idx = jnp.asarray(np.array([s], np.int32))
            first_a = jnp.asarray(np.array([first], np.int32))
            pos_new = jnp.asarray(np.array([plen], np.int32))
            rem_new = jnp.asarray(
                np.array([max(r.max_new - 1, 1)], np.int32))
            eos_new = jnp.asarray(np.array(
                [-1 if r.eos_id is None else int(r.eos_id)], np.int32))
            t0 = time.perf_counter()
            if eng.paged:
                table_rows = jnp.asarray(row[None, :])
                (self._pool, self._cur_tok, self._pos, self._active,
                 self._remaining, self._eos) = fn(
                    self._pool, rows, slot_idx, table_rows, first_a,
                    pos_new, self._cur_tok, self._pos, self._active,
                    self._remaining, rem_new, self._eos, eos_new)
                self._table_h[s] = row
                self._slot_blocks[s] = assigned
                self._table_dirty = True
            else:
                (self._pool, self._cur_tok, self._pos, self._active,
                 self._remaining, self._eos) = fn(
                    self._pool, rows, slot_idx, first_a, pos_new,
                    self._cur_tok, self._pos, self._active,
                    self._remaining, rem_new, self._eos, eos_new)
            jax.block_until_ready(self._cur_tok)
            self.device_s += time.perf_counter() - t0
            self.insert_calls += 1
            r.generated.append(int(first))
            r.slot = s
            self._seat_sampling(s, r)
            self.slots[s] = r
            self._active_host[s] = True

    # -- refill -------------------------------------------------------------
    def _refill(self) -> None:
        eng = self.engine
        B = eng.n_slots
        free = [s for s in range(B) if not self._active_host[s]]
        take = min(len(free), len(self.queue))
        if take == 0:
            return
        if eng.paged:
            self._refill_paged(free, take)
            return
        tracer = self.tracer
        with tracer.scope("sched.refill"):
            reqs = [self.queue.pop(0) for _ in range(take)]
            # a fixed prompt_len pins ONE prefill shape (compile-once);
            # without it each wave pads to its own longest prompt —
            # bucketed to a power of two so the per-(nb, plen) jit cache
            # stays logarithmic — and a long prompt arriving mid-stream
            # is never silently truncated to an earlier wave's length
            plen = self.prompt_len or min(
                _bucket(max(max(len(r.prompt) for r in reqs), 1)),
                eng.max_seq - 1)
            nb = _bucket(take)
            toks = np.zeros((nb, plen), np.int32)
            slot_idx = np.full((nb,), B, np.int32)   # OOB pad rows: dropped
            rem_new = np.ones((nb,), np.int32)
            eos_new = np.full((nb,), -1, np.int32)
            skey_new, temp_new, topk_new, topp_new = \
                self._sampling_rows(reqs, nb)
            for j, r in enumerate(reqs):
                p = np.asarray(r.prompt[:plen], np.int32)
                toks[j, :len(p)] = p
                slot_idx[j] = free[j]
                rem_new[j] = max(r.max_new - 1, 1)
                if r.eos_id is not None:
                    eos_new[j] = int(r.eos_id)
            fn = eng._prefill_bucket(nb, plen)
        with tracer.scope("step.prefill", nb=nb, plen=plen):
            t0 = time.perf_counter()
            (self._pool, first, self._cur_tok, self._pos, self._active,
             self._remaining, self._eos) = fn(
                eng.params, jnp.asarray(toks), self._pool,
                jnp.asarray(slot_idx), self._cur_tok, self._pos,
                self._active, self._remaining, jnp.asarray(rem_new),
                self._eos, jnp.asarray(eos_new), jnp.asarray(skey_new),
                jnp.asarray(temp_new), jnp.asarray(topk_new),
                jnp.asarray(topp_new))
            first_h = np.asarray(jax.block_until_ready(first))
            self.device_s += time.perf_counter() - t0
        self.prefill_calls += 1
        with tracer.scope("sched.seat"):
            self._seat_prefilled(reqs, slot_idx, first_h)

    def _seat_prefilled(self, reqs, slots_for, first_h, *,
                        on_prefill_eos=None) -> None:
        """Shared post-prefill seating (both layouts): append each
        request's first token, seat it in its slot — or, when that
        token IS its EOS, complete it straight away (``on_prefill_eos``
        lets the paged layout free the never-used blocks)."""
        for j, r in enumerate(reqs):
            s = slots_for[j]
            r.generated.append(int(first_h[j]))
            if r.eos_id is not None and first_h[j] == r.eos_id:
                r.done = True            # EOS straight out of prefill
                self._prefill_done.append(r)
                if on_prefill_eos is not None:
                    on_prefill_eos(s)
                continue
            r.slot = s
            self._seat_sampling(s, r)
            self.slots[s] = r
            self._active_host[s] = True

    def _free_slot_blocks(self, s: int) -> None:
        """Return slot ``s``'s blocks to the pool and retire its table
        row to the trash block (applied to the device table before the
        next fused window runs)."""
        blocks = self._slot_blocks.pop(s, [])
        self._free_blocks.extend(blocks)
        self.blocks_freed += len(blocks)
        self._table_h[s] = 0
        self._table_dirty = True

    def _refill_paged(self, free: list[int], take: int) -> None:
        """Paged refill: reserve each request's WHOLE block budget
        before seating it.  FIFO — the head of the queue waits (is
        never dropped or overtaken) when the pool can't cover its
        budget yet; frees from completing requests unblock it.

        The wave (and its shared padded prompt length) is decided as a
        PURE computation first; blocks are popped only once the wave
        is final, so an error path can never strand a popped block.
        The wave's plen grows only with members actually taken — a
        long prompt deeper in the queue can defer its own admission
        but never inflates an earlier request's budget past the pool
        (the hard can-never-be-served error is judged at the request's
        OWN minimal padding, not the wave's)."""
        eng = self.engine
        B = eng.n_slots
        bs = eng.cfg.kv_block_size
        allocatable = eng.pool_blocks - 1           # block 0 = trash
        tracer = self.tracer
        with tracer.scope("sched.refill"):
            wave: list[GenRequest] = []
            needs: list[int] = []
            plen_wave = self.prompt_len or 0
            for r in self.queue[:take]:
                solo_plen = self.prompt_len or min(
                    _bucket(max(len(r.prompt), 1)), eng.max_seq - 1)
                solo_need = blocks_for_request(solo_plen, r.max_new,
                                               eng.max_seq, bs)
                if solo_need > allocatable:
                    raise ValueError(
                        f"request rid={r.rid} needs {solo_need} KV blocks "
                        f"(prompt {solo_plen} + max_new {r.max_new} rows "
                        f"at block_size {bs}) but the pool has only "
                        f"{allocatable} allocatable blocks — it can never "
                        f"be served; raise kv_pool_blocks or shrink the "
                        f"request budget")
                new_plen = max(plen_wave, solo_plen)
                # a longer prompt re-pads the whole wave: re-budget every
                # member at the grown plen before committing to it
                new_needs = [blocks_for_request(new_plen, x.max_new,
                                                eng.max_seq, bs)
                             for x in wave] + [
                    blocks_for_request(new_plen, r.max_new, eng.max_seq,
                                       bs)]
                if sum(new_needs) > len(self._free_blocks):
                    break                    # pool exhausted: queue waits
                wave.append(r)
                needs = new_needs
                plen_wave = new_plen
            if not wave:
                return
            plen = plen_wave
            assigned = [[self._free_blocks.pop() for _ in range(n)]
                        for n in needs]
            reqs = [self.queue.pop(0) for _ in wave]
            nb = _bucket(len(reqs))
            mb = eng.blocks_per_slot
            toks = np.zeros((nb, plen), np.int32)
            slot_idx = np.full((nb,), B, np.int32)       # OOB pad: dropped
            # pad rows' table entries are OOB too, so their kv-scatter rows
            # are dropped; real rows are trash-padded past their budget
            table_rows = np.full((nb, mb), eng.pool_blocks, np.int32)
            rem_new = np.ones((nb,), np.int32)
            eos_new = np.full((nb,), -1, np.int32)
            skey_new, temp_new, topk_new, topp_new = \
                self._sampling_rows(reqs, nb)
            for j, r in enumerate(reqs):
                p = np.asarray(r.prompt[:plen], np.int32)
                toks[j, :len(p)] = p
                slot_idx[j] = free[j]
                row = np.zeros((mb,), np.int32)
                row[:len(assigned[j])] = assigned[j]
                table_rows[j] = row
                rem_new[j] = max(r.max_new - 1, 1)
                if r.eos_id is not None:
                    eos_new[j] = int(r.eos_id)
            self.blocks_allocated += sum(len(a) for a in assigned)
            self.peak_blocks_in_use = max(
                self.peak_blocks_in_use,
                allocatable - len(self._free_blocks))
            fn = eng._prefill_bucket_paged(nb, plen)
        with tracer.scope("step.prefill", nb=nb, plen=plen):
            t0 = time.perf_counter()
            (self._pool, first, self._cur_tok, self._pos, self._active,
             self._remaining, self._eos) = fn(
                eng.params, jnp.asarray(toks), self._pool,
                jnp.asarray(slot_idx), jnp.asarray(table_rows),
                self._cur_tok, self._pos, self._active, self._remaining,
                jnp.asarray(rem_new), self._eos, jnp.asarray(eos_new),
                jnp.asarray(skey_new), jnp.asarray(temp_new),
                jnp.asarray(topk_new), jnp.asarray(topp_new))
            first_h = np.asarray(jax.block_until_ready(first))
            self.device_s += time.perf_counter() - t0
        self.prefill_calls += 1
        with tracer.scope("sched.seat"):
            for j in range(len(reqs)):
                self._table_h[free[j]] = table_rows[j]
                self._slot_blocks[free[j]] = assigned[j]
            self._seat_prefilled(reqs, free, first_h,
                                 on_prefill_eos=self._free_slot_blocks)

    # -- advance ------------------------------------------------------------
    def advance(self) -> list[GenRequest]:
        """Refill free slots, run one fused k-step window, harvest.
        Returns the requests COMPLETED by this window."""
        eng = self.engine
        tracer = self.tracer
        with tracer.scope("sched.advance"):
            if self._insert_q:
                with tracer.scope("sched.inserts"):
                    self._drain_inserts()
            self._refill()
            done_at_prefill, self._prefill_done = self._prefill_done, []
            if not self._active_host.any():
                return done_at_prefill
            if eng.paged and self._table_dirty:
                # retired slots' rows now point at the trash block; the
                # window must never write a freed (possibly
                # reallocated) block, so the mirror is applied BEFORE
                # every window
                with tracer.scope("sched.table"):
                    self._pool = self._pool._replace(
                        block_table=jnp.asarray(self._table_h))
                self._table_dirty = False
            if eng.draft_depth > 0:
                self.last_depth = eng.current_depth()
            with tracer.scope("step.window"):
                t0 = time.perf_counter()
                (self._pool, self._cur_tok, self._pos, self._active,
                 self._remaining, toks, emitted) = eng._step_k(
                    *self._window_args())
                jax.block_until_ready(toks)
                self.device_s += time.perf_counter() - t0
            with tracer.scope("sched.harvest"):
                completed = self._harvest(toks, emitted)
        return done_at_prefill + completed

    def _harvest(self, toks, emitted) -> list[GenRequest]:
        """Pull one window's tokens to the host, update the counters,
        extend each seated request and retire the finished ones (their
        slots and blocks freed).  Returns the requests completed."""
        eng = self.engine
        B = eng.n_slots
        spec = eng.draft_depth > 0
        depth = self.last_depth
        # ONE host sync per window: token/emission pulls — [k,B], or
        # [k,D+1,B] for the speculative macro-step window
        toks_h = np.asarray(toks)
        emit_h = np.asarray(emitted)
        active_h = np.array(self._active)        # writable host copy
        self.host_syncs += 1
        if spec:
            # macro-slot accounting: emission row 0 marks the slots
            # that were live for the macro-step (one FULL verify pass
            # each); rows 1.. are accepted drafts
            macro_live = emit_h[:, 0, :]                     # [k, B]
            self.decode_steps += int(macro_live.any(axis=1).sum())
            self.occupied_slot_steps += int(macro_live.sum())
            self.spec_accepted += int(emit_h[:, 1:, :].sum())
            self.spec_proposed += int(macro_live.sum()) * depth
            self.spec_draft_slot_steps += int(macro_live.sum()) * depth
            if eng.spec_controller is not None:
                eng.spec_controller.observe(
                    accepted=int(emit_h[:, 1:, :].sum()),
                    proposed=int(macro_live.sum()) * depth)
            k_, n_, B_ = toks_h.shape
            toks_h = toks_h.reshape(k_ * n_, B_)   # chronological
            emit_h = emit_h.reshape(k_ * n_, B_)
        else:
            self.decode_steps += int(emit_h.any(axis=1).sum())
            self.occupied_slot_steps += int(emit_h.sum())
        completed: list[GenRequest] = []
        for s in range(B):
            r = self.slots[s]
            if r is None:
                continue
            r.generated.extend(int(x) for x in toks_h[emit_h[:, s], s])
            if not active_h[s]:
                r.done = True
                completed.append(r)
                self.slots[s] = None
                if eng.paged:
                    self._free_slot_blocks(s)
        self._active_host = active_h
        return completed

    def _window_args(self) -> tuple:
        """Operands of the fused decode window for the current state."""
        eng = self.engine
        args = (eng.params, self._pool, self._cur_tok, self._pos,
                self._active, self._remaining, self._eos,
                jnp.asarray(self._skey_h), jnp.asarray(self._temp_h),
                jnp.asarray(self._topk_h), jnp.asarray(self._topp_h))
        if eng.draft_depth > 0:
            args += (jnp.asarray(self.last_depth, jnp.int32),)
        return args

    def window_hlo(self) -> str:
        """The fused decode window as compiled for the current backend
        (optimised HLO text) — shows which kernels the window runs."""
        return self.engine._step_k.lower(
            *self._window_args()).compile().as_text()

    # -- reporting ----------------------------------------------------------
    def stats(self) -> dict:
        eng = self.engine
        B = eng.n_slots
        out = {
            "mode": "paged" if eng.paged else "fused",
            "sync_every": eng.sync_every,
            "decode_steps": self.decode_steps,
            "occupied_slot_steps": self.occupied_slot_steps,
            "occupancy": (self.occupied_slot_steps
                          / (self.decode_steps * B)
                          if self.decode_steps else 0.0),
            "host_syncs": self.host_syncs,
            "prefill_calls": self.prefill_calls,
            "insert_calls": self.insert_calls,
            "device_s": self.device_s,
        }
        if eng.paged:
            out.update(
                kv_block_size=eng.cfg.kv_block_size,
                pool_blocks=eng.pool_blocks,
                blocks_allocated=self.blocks_allocated,
                blocks_freed=self.blocks_freed,
                peak_blocks_in_use=self.peak_blocks_in_use,
                free_blocks=len(self._free_blocks))
        if eng.draft_depth > 0:
            emitted = self.occupied_slot_steps + self.spec_accepted
            # modelled energy (bandwidth-bound step cost): one unit
            # per full-stack slot pass, draft_layers/n_layers per
            # shallow draft pass, over tokens actually emitted —
            # greedy decode is exactly 1.0 on this scale
            c = eng.cfg.draft_layers / eng.cfg.n_layers
            cost = (self.occupied_slot_steps
                    + self.spec_draft_slot_steps * c)
            out.update(
                mode="spec",
                draft_depth=eng.draft_depth,
                draft_depth_live=self.last_depth,
                draft_layers=eng.cfg.draft_layers,
                spec_proposed=self.spec_proposed,
                spec_accepted=self.spec_accepted,
                acceptance_rate=(self.spec_accepted
                                 / max(self.spec_proposed, 1)),
                accepted_per_step=(emitted
                                   / max(self.occupied_slot_steps, 1)),
                energy_per_token_model=(cost / max(emitted, 1)))
        return out
