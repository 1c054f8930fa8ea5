"""mfu.decode (model step layer): the decode work the served tokens
needed (every layer's linear maps, attention over each token's valid
context at the logical head dim, and the head) over the device time of
the decode window programs times the chip's peak, in percent.
Moves tpot_p50_ms."""
from harness import flops, trace

PROGRAMS = ("jit_step_k",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.program_time_s(ctx.trace, PROGRAMS)
    ctxs = ctx.decode_contexts()
    if t <= 0 or len(ctxs) == 0:
        return None
    work = float(flops.decode_token_flops(ctx.cfg, ctxs).sum())
    return 100.0 * work / (t * ctx.peaks["flops_per_s"])
