"""Model step (``models/dense.py``): FLOPs of every token prefilled and
decoded in the traced window, over the window at the chips' peak FLOP/s,
in %.  A prefilled token needs no logits; a decoded one does."""


def read(ctx):
    work = ctx.prefill_flops() + ctx.decode_flops()
    if work <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * work / (ctx.window_s * ctx.chips * ctx.peaks.flops_bf16)
