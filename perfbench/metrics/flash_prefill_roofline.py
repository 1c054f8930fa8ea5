"""flash_prefill_roofline (kernels layer): the least time the flash
attention kernel could take over the window's prompts (causal pairs
at the logical head dim; q, k, v read and o written once) over its
device time, in percent.  Moves tpot_p90_ms (a prefill wave stalls
the decode windows of the requests in flight)."""
from harness import flops, trace

KERNELS = ("flash_attention",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = trace.op_time_s(ctx.trace, KERNELS)
    n = ctx.prefilled()
    if t <= 0 or n == 0:
        return None
    cfg = ctx.cfg
    least = flops.roofline_s(*flops.flash_prefill_kernel(cfg, ctx.plen),
                             ctx.peaks)
    return 100.0 * n * cfg["n_layers"] * least / t
