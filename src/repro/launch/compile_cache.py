"""Persistent JAX compilation cache — cold-start hardening.

A serving replica's cold start is dominated by XLA compiles of the
fused decode window (seconds to minutes at real model sizes, once per
(shape, flags) key).  Pointing JAX's persistent compilation cache at a
directory that survives restarts turns every compile after the first
deploy into a disk read.

``enable_compilation_cache`` is called by every entry point
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``)
before its first compile.  The directory is placed from outside: when
``JAX_COMPILATION_CACHE_DIR`` is set it is used as given, and nothing
in code names another; when it is unset the cache lives at one fixed
path inside the checkout (``<repo>/.jax_cache``, git-ignored).  The
path is part of the cache key, so it is never a temporary, per-process
or per-run name.  The min-time / min-size floors are zeroed so small
programs cache too.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``DEFAULT_DIR``, and
    return the directory (created if missing).  Idempotent; safe to
    call before or after the first jax import triggers backend init."""
    import jax

    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # cache small programs too: the default floors (1s compile,
    # small-entry skip) would exclude the kernels and the smoke models
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
