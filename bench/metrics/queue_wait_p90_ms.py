"""Scheduler (``serving/scheduler.py``): 90th percentile of the wait from a
request's due time to its admission into a prefill lane, over the requests
admitted in the traced window."""
import numpy as np


def read(ctx):
    if not ctx.queue_waits_s:
        return None
    return 1e3 * float(np.percentile(ctx.queue_waits_s, 90))
