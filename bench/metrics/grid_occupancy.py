"""Scheduler (``serving/scheduler.py``): share of the (M, B) decode grid's
lane-steps that emitted a token, ``decode_tokens / (decode_steps * M * B)``
from the engine's ``ServerMetrics`` over the traced window, in %."""


def read(ctx):
    c = ctx.counters
    if c["decode_steps"] <= 0:
        return None
    lanes = ctx.model.instances * ctx.slots
    return 100.0 * c["decode_tokens"] / (c["decode_steps"] * lanes)
