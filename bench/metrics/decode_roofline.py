"""Model step (``models/dense.py``): the least time the traced window's
decode steps need, over the device time of the decode-block programs, in %.
Each step's least time is the larger of its required FLOPs over peak FLOP/s
and its required bytes over peak bandwidth (``bench/flops.py``)."""
from bench import flops


def read(ctx):
    t = ctx.program_s.get("decode_block")
    if not t or not ctx.decode_steps:
        return None
    least = 0.0
    for inst, lanes, ctx_sum in ctx.decode_steps:
        f, b = flops.decode_step(ctx.model, instances=inst, lanes=lanes,
                                 ctx_sum=ctx_sum)
        least += flops.least_seconds(f, b, ctx.peaks)[0]
    return 100.0 * least / t
