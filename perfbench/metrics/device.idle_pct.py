"""device.idle_pct (device layer): the share of the traced span in
which no operation ran on the chip (1 - union of the operations'
intervals / span), in percent: host gaps between calls stretch the
time between tokens.  Moves tpot_p50_ms."""
from harness import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    w = ctx.trace.window_s
    if w <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / w)
