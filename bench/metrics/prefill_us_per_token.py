"""Engine prefill (``serving/prefill.py``): device time of the chunk-prefill
programs over the traced window, per prompt token prefilled (padding not
counted)."""


def read(ctx):
    t, n = ctx.program_s.get("prefill_chunk"), ctx.counters["prefill_tokens"]
    if not t or n <= 0:
        return None
    return 1e6 * t / n
