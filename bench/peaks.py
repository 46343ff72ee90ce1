"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s.  JAX reports a v5e chip as
"TPU v5 lite".  The distance dots of this system run in float32 at
``Precision.HIGHEST`` (six bf16 passes on the MXU), so a compute-bound
share against the bf16 peak tops out well under 100%.

A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip


PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9)}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None
