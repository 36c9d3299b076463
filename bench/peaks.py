"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip.  (Copied from the
program's ``launch/mesh.PEAKS``, which cites the same source.)  A kind that
is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s
    hbm_bytes: float        # bytes/s


_V5E = Peaks(flops_bf16=197e12, hbm_bytes=819e9)
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
