"""paged_decode_roofline (kernels layer): the least time the paged
decode attention kernel could take for the served tokens (valid K/V
rows at the logical head dim, read once per layer per step) over its
device time, in percent.  Moves tpot_p50_ms."""
from harness import flops, trace

KERNELS = ("paged_decode_attention",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.op_time_s(ctx.trace, KERNELS)
    ctxs = ctx.decode_contexts()
    if t <= 0 or len(ctxs) == 0:
        return None
    cfg = ctx.cfg
    least = sum(flops.roofline_s(*flops.paged_decode_kernel(cfg, int(c)),
                                 ctx.peaks) for c in ctxs)
    return 100.0 * cfg["n_layers"] * least / t
