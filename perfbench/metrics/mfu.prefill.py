"""mfu.prefill (model step layer): the prefill work the window's
requests needed (every prompt position through every layer, causal
attention, the head at the last position) over the device time of the
prefill programs times the chip's peak, in percent.  Padding rows of
a bucket count as time, not as work.  Moves tpot_p90_ms (a prefill
wave stalls the decode windows of the requests in flight)."""
from harness import flops, trace

PROGRAMS = ("jit_prefill_p",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.program_time_s(ctx.trace, PROGRAMS)
    n = ctx.prefilled()
    if t <= 0 or n == 0:
        return None
    work = n * flops.prefill_request_flops(ctx.cfg, ctx.plen,
                                         ctx.reference)
    return 100.0 * work / (t * ctx.peaks["flops_per_s"])
