#!/usr/bin/env bash
# Perf-preset launcher environment (HomebrewNLP / olmax / maxtext
# run.sh idiom): source it, or use it as a command prefix —
#
#   source scripts/perf_env.sh
#   PYTHONPATH=src python -m repro.launch.serve --mode generate ...
#
#   scripts/perf_env.sh python -m repro.launch.serve ...   # prefix form
#
# Everything is opt-out: set the variable first and the preset leaves
# it alone.

# faster malloc for the host-side arena (prefill staging, numpy
# buffers); skip silently when tcmalloc isn't installed
if [ -z "${LD_PRELOAD:-}" ]; then
    for _tcm in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
                /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4; do
        if [ -e "$_tcm" ]; then
            export LD_PRELOAD="$_tcm"
            break
        fi
    done
    unset _tcm
fi
# no large-alloc warnings from numpy buffers riding tcmalloc
export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="${TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD:-60000000000}"

# quiet the TF/XLA C++ log spam that dominates cold-start stderr
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# the persistent compilation cache is not set here: the launchers use
# $JAX_COMPILATION_CACHE_DIR when the caller sets it, else
# <repo>/.jax_cache (repro.launch.compile_cache)

# keep the fused decode window as ONE outer-while step for profilers
# (olmax: 0 = entry, 1 = outer while)
export XLA_FLAGS="${XLA_FLAGS:---xla_step_marker_location=1}"

# sane float defaults: no silent fp64 promotion on host staging code
export JAX_DEFAULT_DTYPE_BITS="${JAX_DEFAULT_DTYPE_BITS:-32}"

# prefix form: exec the wrapped command with the preset applied
if [ "$#" -gt 0 ]; then
    exec "$@"
fi
