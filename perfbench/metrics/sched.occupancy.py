"""sched.occupancy (scheduler layer): the share of decode slot-steps
that carried a live request over the traced span, Δ``occupied_slot_steps``
/ (Δ``decode_steps`` x slots) from the decode session's counters, in
percent.  Moves out_tok_s."""


def read(ctx):
    steps = ctx.counter_delta("decode_steps")
    if not steps:
        return None
    return 100.0 * ctx.counter_delta("occupied_slot_steps") / (
        steps * ctx.slots)
