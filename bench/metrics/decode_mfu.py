"""Model step (``models/dense.py``): FLOPs the traced window's decode steps
require, over the device time of the decode-block programs at the chip's
peak FLOP/s, in %: the whole decode step's share of the peak, which bounds
what any kernel inside it can claim."""


def read(ctx):
    t = ctx.program_s.get("decode_block")
    if not t or not ctx.decode_steps:
        return None
    return 100.0 * ctx.decode_flops() / (t * ctx.peaks.flops_bf16)
