"""sched.host_idle_pct (scheduler layer): the share of the traced
span, in percent, in which the chip ran nothing and the host's
innermost program span was one of the decode session's scheduling
(``sched.*``: advance, inserts, refill, seat, table, harvest).  Moves
tpot_p50_ms."""
from harness import spans


def read(ctx):
    if ctx.trace is None:
        return None
    return spans.host_idle_pct(ctx.trace, ctx.trace.spans, "sched.")
