"""prefill.ms_per_req (model step layer): device milliseconds of the
bucketed paged prefill programs (``jit_prefill_p``) per request they
prefilled in the window.  Moves tpot_p90_ms: a prefill wave runs
between decode windows and stalls every request in flight."""
from harness import trace

PROGRAMS = ("jit_prefill_p",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.program_time_s(ctx.trace, PROGRAMS)
    n = ctx.prefilled()
    if t <= 0 or n == 0:
        return None
    return 1000.0 * t / n
