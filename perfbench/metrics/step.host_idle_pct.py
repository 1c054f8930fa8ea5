"""step.host_idle_pct (model step layer): the share of the traced
span, in percent, in which the chip ran nothing and the host's
innermost program span was a model-step call (``step.*``: a decode
window or a prefill wave, from its operand uploads to its results on
the host).  Moves tpot_p50_ms."""
from harness import spans


def read(ctx):
    if ctx.trace is None:
        return None
    return spans.host_idle_pct(ctx.trace, ctx.trace.spans, "step.")
