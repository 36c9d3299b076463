"""What the per-layer metric readers (``bench/metrics/<name>.py``) read.

A traced run builds one :class:`LayerContext` from the probe's records and
the reduced device trace, both restricted to the traced window, and asks
each reader of the cell's per-layer metrics for its value.
"""
from __future__ import annotations

import dataclasses
import importlib

from bench import flops
from bench.model import Dense
from bench.peaks import Peaks


@dataclasses.dataclass
class LayerContext:
    model: Dense
    slots: int                      # slots per instance (B)
    peaks: Peaks
    chips: int
    window_s: float                 # traced window, on the device's clock
    busy_s: float                   # device busy within it
    program_s: dict[str, float]     # device time by program name
    counters: dict[str, int]        # ServerMetrics deltas over the window
    queue_waits_s: list[float]      # due -> admission, admitted in window
    decode_steps: list[tuple[int, int, int]]    # (instances, lanes, ctx sum)
    prefill: list[tuple[int, int]]  # per advance: (tokens, ctx sum)

    def decode_flops(self) -> float:
        return sum(flops.decode_step(self.model, instances=i, lanes=n,
                                     ctx_sum=c)[0]
                   for i, n, c in self.decode_steps)

    def prefill_flops(self) -> float:
        d = self.model
        return sum(2 * flops.layer_matmul_params(d) * t + flops.attn_flops(d, c)
                   for t, c in self.prefill)


def read(names: list[str], ctx: LayerContext) -> dict[str, float]:
    """Each named metric's value; a reader that returns None is left out."""
    out = {}
    for name in names:
        value = importlib.import_module(f"bench.metrics.{name}").read(ctx)
        if value is not None:
            out[name] = float(value)
    return out
