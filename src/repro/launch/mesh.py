"""Production meshes and per-chip peaks.

Target: TPU v5e pods; single pod = 16x16 (256 chips), multi-pod = 2 pods
= 512 chips with a leading "pod" axis.  A FUNCTION (not a module-level
constant) so importing never touches jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax
init; everything else sees 1 CPU device.
"""
from __future__ import annotations

import dataclasses

from repro.launch.compat import make_host_mesh


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float      # FLOP/s
    hbm_bw: float          # bytes/s
    ici_bw: float          # bytes/s per link


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s ICI per chip (four links of 50 GB/s).
_V5E = ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9)
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of this kind; a kind not in ``PEAKS`` is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_host_mesh(shape, axes)


def num_chips(mesh) -> int:
    n = 1
    for s in mesh.shape.values():
        n *= s
    return n
