"""What a per-layer metric reader (``bench/metrics/<name>.py``) is given.

``read(ctx)`` returns a number, or None where the traced window holds
nothing to read; the harness then leaves the metric out of the line.  A
roofline share is never returned as 0 for want of a reading.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from bench import peaks as bpeaks
from bench import work as bwork


@dataclass
class Context:
    reduction: object                 # bench.trace.Reduction
    counts: Dict[str, int]            # calls, rounds, epochs, batches ...
    work: Dict[str, Tuple[float, float]]  # kernel -> (flops, bytes)
    device_kind: str
    notes: Dict[str, dict] = field(default_factory=dict)

    def kernel_s(self, kernel: str) -> float:
        """Device seconds of the ops under ``repro.kernels.<kernel>``."""
        return self.reduction.kernel_s.get(kernel, 0.0)


def roofline(ctx: Context, metric: str, kernel: str) -> Optional[float]:
    """A kernel's share of its roofline, in %: the least time the chip
    could take for the algorithm's work over the kernel's device time."""
    seconds = ctx.kernel_s(kernel)
    if kernel not in ctx.work or seconds <= 0.0:
        return None
    flops, nbytes = ctx.work[kernel]
    share, bound = bwork.roofline_share(flops, nbytes, seconds,
                                        bpeaks.peaks(ctx.device_kind))
    ctx.notes[metric] = {"bound": bound}
    return share


def nonkernel_ms(ctx: Context, per: str) -> Optional[float]:
    """Device ms outside every kernel scope, per ``per`` (a count)."""
    n = ctx.counts.get(per, 0)
    if n <= 0 or ctx.reduction.busy_s <= 0.0:
        return None
    return 1e3 * ctx.reduction.nonkernel_s / n


def device_idle(ctx: Context) -> Optional[float]:
    """Share of the traced window in which no op ran on the device, %."""
    r = ctx.reduction
    if r.window_s <= 0.0 or r.busy_s <= 0.0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
