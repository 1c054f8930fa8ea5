"""decode.step_ms (model step layer): device milliseconds per decode
micro-step — the device time of the fused decode window programs
(``jit_step_k``) over the micro-steps they ran (runs x sync_every).
Moves tpot_p50_ms."""
from harness import trace

PROGRAMS = ("jit_step_k",)


def read(ctx):
    if ctx.trace is None:
        return None
    runs = trace.program_runs(ctx.trace, PROGRAMS)
    t = trace.program_time_s(ctx.trace, PROGRAMS)
    if not runs or t <= 0:
        return None
    return 1000.0 * t / (len(runs) * ctx.sync_every)
