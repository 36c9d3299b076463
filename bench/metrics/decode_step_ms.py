"""Engine decode block (``serving/engine.py``): device time of the fused
decode-block programs over the traced window, per scan step."""


def read(ctx):
    t, n = ctx.program_s.get("decode_block"), ctx.counters["decode_steps"]
    if not t or n <= 0:
        return None
    return 1e3 * t / n
