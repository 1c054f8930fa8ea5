"""Chip smoke test: the closed-loop server on one TPU, through its
normal entry points, at stablelm-3b's published widths.

    python chip_smoke.py [--seed N]

Phases, in order; the first failure exits non-zero with no result line:

  1. device   — JAX must find a TPU.  Any other platform stops the run
                here, before a model is built; nothing falls back.
  2. generate — ``repro.launch.serve.serve_generate`` with the published
                stablelm-3b config (32 layers, d_model 2560, 32 MHA
                heads of 80, bf16; random weights from ``--seed``) on a
                paged KV pool (block 16, 8 slots, max_seq 512), greedy,
                no speculation: 8 requests of 128 seeded prompt tokens
                and 32 new tokens.  Each is answered exactly once with
                32 tokens inside the vocabulary.
  3. kernels  — the engine's compiled decode window holds the Pallas
                kernel (``tpu_custom_call``); the paged flash-decode
                kernel and the entropy kernel at published widths agree
                with the jnp reference (tolerances below).
  4. gated    — ``serve_classifier`` on ``gated-in-graph`` (the in-graph
                gate with the entropy kernel) under the ``bio``
                controller: 64 requests, each answered exactly once.
                The classifier is the repo's small DistilBERT, so this
                checks the path, not a width.

Compile and wall seconds are printed as set-up time of a smoke run;
they are not metrics.  The last line of stdout is the verdict,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "stablelm-3b"
KV_BLOCK, SLOTS, MAX_SEQ = 16, 8, 512
N_GEN, PROMPT_LEN, NEW_TOKENS = 8, 128, 32
N_GATED = 64
# bf16 inputs, f32 accumulation in both; the reference runs at full
# f32 matmul precision
PAGED_MAX_ABS_ERR = 2e-2
ENTROPY_MAX_ABS_ERR = 1e-2          # nats


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_phase():
    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SmokeFailure(
            f"JAX found platform {d0.platform!r} ({d0.device_kind}), not "
            f"a TPU; this smoke runs on the chip only")
    print(f"device: {d0.device_kind} x{len(devs)}", flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def generate_phase(cfg, seed: int):
    """Serve ``N_GEN`` generate requests through ``serve_generate``;
    returns the server after checking every answer."""
    import numpy as np

    from repro.launch import serve
    from repro.telemetry import CompileWatcher

    args = serve.build_parser().parse_args([
        "--mode", "generate", "--arch", cfg.arch_id,
        "--requests", str(N_GEN), "--new-tokens", str(NEW_TOKENS),
        "--slots", str(SLOTS), "--kv-block-size", str(KV_BLOCK),
        "--controller", "open", "--draft-depth", "0",
        "--seed", str(seed)])
    watch = CompileWatcher().install()
    t0 = time.perf_counter()
    _, responses, server = serve.serve_generate(
        args, cfg, max_seq=MAX_SEQ, prompt_len=PROMPT_LEN)
    wall = time.perf_counter() - t0
    setup = watch.export()
    print(f"set-up of a smoke run, not a metric: generate phase "
          f"compile_s={setup['compile_seconds']:.1f} wall_s={wall:.1f}",
          flush=True)
    rids = sorted(r.rid for r in responses)
    check(rids == list(range(N_GEN)),
          f"generate: answered rids {rids}, expected each of "
          f"0..{N_GEN - 1} exactly once")
    for r in responses:
        out = np.asarray(r.output)
        check(r.path == "continuous-decode" and out.shape == (NEW_TOKENS,),
              f"generate: rid {r.rid} on path {r.path!r} returned "
              f"{out.shape} tokens, expected ({NEW_TOKENS},)")
        check(bool(((out >= 0) & (out < cfg.vocab)).all()),
              f"generate: rid {r.rid} has tokens outside [0, {cfg.vocab})")
    print(f"generate: {N_GEN} requests x {NEW_TOKENS} tokens answered "
          f"once", flush=True)
    return server


def kernel_phase(cfg, server, seed: int):
    """The decode window runs the Pallas kernel, and the kernels agree
    with the jnp reference at the config's widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as kops

    hlo = server.engine.session.window_hlo()
    check("tpu_custom_call" in hlo,
          "kernels: the compiled decode window holds no Pallas kernel "
          "(tpu_custom_call)")

    B, H, K, hd = SLOTS, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    mb = MAX_SEQ // KV_BLOCK
    nb = 1 + B * mb                               # block 0 = trash
    kq, kk, kv, kt, kx = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(kq, (B, H, hd), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (nb, KV_BLOCK, K, hd), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (nb, KV_BLOCK, K, hd), jnp.bfloat16)
    table = (1 + jax.random.permutation(kt, nb - 1)[:B * mb]).reshape(
        B, mb).astype(jnp.int32)
    lens = np.random.default_rng(seed).integers(1, mb * KV_BLOCK + 1, B)
    cols = np.arange(mb * KV_BLOCK)
    pos = jnp.asarray(np.where(cols < lens[:, None], cols, -1), jnp.int32)
    cur = jnp.asarray(lens - 1, jnp.int32)
    out = kops.paged_decode_attention(q, k_pool, v_pool, table, pos, cur)
    with jax.default_matmul_precision("highest"):
        ref = kops.paged_decode_attention(q, k_pool, v_pool, table, pos,
                                          cur, impl="ref")
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    check(err <= PAGED_MAX_ABS_ERR,
          f"kernels: paged decode max abs err {err} > {PAGED_MAX_ABS_ERR}")
    print(f"kernels: paged decode [{B},{H},{hd}] pool {nb}x{KV_BLOCK} "
          f"max abs err {err} <= {PAGED_MAX_ABS_ERR}", flush=True)

    logits = jax.random.normal(kx, (32, cfg.vocab), jnp.float32) * 4
    h, _, a = kops.entropy_stats(logits)
    hr, _, ar = kops.entropy_stats(logits, impl="ref")
    herr = float(jnp.max(jnp.abs(h - hr)))
    check(herr <= ENTROPY_MAX_ABS_ERR and bool(jnp.all(a == ar)),
          f"kernels: entropy max abs err {herr} (limit "
          f"{ENTROPY_MAX_ABS_ERR}), argmax equal {bool(jnp.all(a == ar))}")
    print(f"kernels: entropy [32,{cfg.vocab}] max abs err {herr} <= "
          f"{ENTROPY_MAX_ABS_ERR}, argmax identical", flush=True)


def gated_phase(seed: int):
    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--path", "gated-in-graph", "--controller", "bio",
        "--requests", str(N_GATED), "--qps", "150", "--seed", str(seed)])
    t0 = time.perf_counter()
    summary, responses = serve.serve_classifier(args)
    print(f"set-up of a smoke run, not a metric: gated phase "
          f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    rids = sorted(r.rid for r in responses)
    check(rids == list(range(N_GATED)),
          f"gated: answered rids {rids}, expected each of "
          f"0..{N_GATED - 1} exactly once")
    print(f"gated: {N_GATED} requests answered once, admission rate "
          f"{summary['admission_rate']}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        device = device_phase()
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compilation_cache
        print(f"compilation cache: {enable_compilation_cache()}",
              flush=True)
        cfg = get_config(ARCH)
        server = generate_phase(cfg, args.seed)
        kernel_phase(cfg, server, args.seed)
        del server
        gated_phase(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
