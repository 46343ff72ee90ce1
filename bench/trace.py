"""From a profiler trace of the window to device busy time, kernel time
and the breakdown.

* Busy time is the union of the intervals in which an op ran on the
  device, clipped to the window; idle share is 1 minus busy over window.
* Kernel time is the self time of the HLO ops whose framework op name
  puts them under a ``repro.kernels.<name>`` named scope (``kernels/ops.py``
  opens one around every kernel call, whatever implements it).  A kernel
  that runs as a program of its own (a jitted kernel called outside any
  trace, as ``index.search`` calls the scan) loses that scope: its Pallas
  call carries the kernel's name as its HLO op name, and is counted under
  it.  A TPU trace's op events carry no metadata, so these come from the
  per-HLO-op table that xprof derives from the same trace (``hlo_stats``);
  a trace without that table is an error, never a reading of no kernel
  time.
* Everything else in that table is non-kernel time.
* Each idle gap is labelled by the innermost host span open at its middle
  (the harness marks ``bench.window`` and each ``bench.call``; JAX adds its
  own dispatch spans).

``Tracer`` takes the trace with ``jax.profiler`` and reads the
``.xplane.pb`` with ``jax.profiler.ProfileData``; ``reduce`` works on plain
tuples so that it can be checked on a synthetic trace.
"""
from __future__ import annotations

import glob
import json
import os
import re
import shutil
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

SCOPE = re.compile(r"repro\.kernels\.([A-Za-z0-9_]+)")
WINDOW = "bench.window"
TOP = 10


class Op(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


class Span(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float


@dataclass
class Reduction:
    busy_s: float
    window_s: float
    kernel_s: Dict[str, float]
    nonkernel_s: float
    ops: List[Tuple[str, float]] = field(default_factory=list)
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def scope_of(texts: Iterable[str]) -> Optional[str]:
    """The innermost ``repro.kernels.<name>`` scope named in an op's
    metadata strings, or None."""
    found = None
    for t in texts:
        for m in SCOPE.finditer(str(t)):
            found = m.group(1)
    return found


def kernel_of(row: "HloRow") -> Optional[str]:
    """The kernel an HLO op belongs to: its innermost ``repro.kernels``
    scope, else, for a Pallas call outside every scope, the kernel its HLO
    op is named after; None for every other op."""
    k = scope_of([row.tf_op])
    if k is None and "pallas_call" in row.tf_op:
        k = _base(row.hlo_op)
    return k


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals inside [lo, hi]."""
    tot, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(_clip(s, e, lo, hi) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def gaps_ns(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(_clip(s, e, lo, hi) for s, e in intervals):
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _base(name: str) -> str:
    """An op's name without its HLO text and numeric suffix."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\-_]?\d+$", "", name)


def _short(tf_op: str) -> str:
    """A framework op name without the jit(...) wrappers of its path."""
    parts = [p for p in tf_op.split("/") if p and not p.startswith("jit(")
             and p not in ("while", "body", "cond", "closed_call")]
    return "/".join(parts[-3:])


class HloRow(NamedTuple):
    """One HLO op of the profile's per-op table: its framework op name
    (the ``op_name`` metadata, with JAX's named scopes) and self time."""
    tf_op: str
    hlo_op: str
    self_s: float


def reduce(ops: List[Op], spans: List[Span],
           hlo: List[HloRow]) -> Reduction:
    """Reduce one device's ops, the host spans and the per-HLO-op table
    to the window's numbers.  The window is the ``bench.window`` span
    (else the ops' extent); busy time and gaps come from the op events,
    kernel and non-kernel time from the table's self times and scopes."""
    win = [s for s in spans if s.name == WINDOW]
    if win:
        lo, hi = win[0].start_ns, win[0].start_ns + win[0].dur_ns
    elif ops:
        lo = min(o.start_ns for o in ops)
        hi = max(o.start_ns + o.dur_ns for o in ops)
    else:
        return Reduction(0.0, 0.0, {}, 0.0)
    iv = [(o.start_ns, o.start_ns + o.dur_ns) for o in ops]
    busy = union_ns(iv, lo, hi)
    if busy > 0.0 and not hlo:
        raise ValueError("device ops in the window but no per-HLO-op table: "
                         "kernel time cannot be attributed")
    kernel: Dict[str, float] = {}
    groups: Dict[str, float] = {}
    nonkernel = 0.0
    for r in hlo:
        k = kernel_of(r)
        if k is None:
            nonkernel += r.self_s
            g = _short(r.tf_op) or _base(r.hlo_op)
        else:
            kernel[k] = kernel.get(k, 0.0) + r.self_s
            g = "repro.kernels." + k
        groups[g] = groups.get(g, 0.0) + r.self_s
    inner = [s for s in spans if s.name != WINDOW]
    gaps = []
    for s, e in gaps_ns(iv, lo, hi):
        mid = 0.5 * (s + e)
        around = [h for h in inner if h.start_ns <= mid <= h.start_ns +
                  h.dur_ns]
        label = min(around, key=lambda h: h.dur_ns).name if around else \
            "(no host span)"
        gaps.append((label, (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Reduction(busy_s=busy * 1e-9, window_s=(hi - lo) * 1e-9,
                     kernel_s=kernel, nonkernel_s=nonkernel,
                     ops=sorted(groups.items(), key=lambda g: -g[1]),
                     gaps=gaps)


# ---------------------------------------------------------------------------
# reading an xplane.pb
# ---------------------------------------------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"


def read_xspace(path: str, device: int = 0) -> Tuple[List[Op], List[Span]]:
    """Ops on one TPU's op line and the spans of the host's threads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: List[Op] = []
    spans: List[Span] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device:
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for e in line.events:
                    ops.append(Op(e.name, e.start_ns, e.duration_ns))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        spans.append(Span(e.name, e.start_ns, e.duration_ns))
    return ops, spans


def read_hlo_stats(path: str) -> List[HloRow]:
    """The profile's per-HLO-op table (xprof's ``hlo_stats`` tool): self
    time and framework op name of every op that ran on the device (empty
    where the profile holds none).  xprof is required."""
    from xprof.convert import raw_to_tool_data

    out = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    data = out[0] if isinstance(out, tuple) else out
    if not data:
        return []
    table = json.loads(data)
    cols = [c["id"] for c in table.get("cols", [])]
    rows = []
    for row in table.get("rows", []):
        v = dict(zip(cols, [c.get("v") if c else None for c in row["c"]]))
        rows.append(HloRow(str(v.get("tf_op_name") or ""),
                           str(v.get("hlo_op_name") or ""),
                           float(v.get("total_self_time") or 0.0) * 1e-6))
    return rows


class Tracer:
    """Profiler trace of the window, reduced when it stops."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._win = None

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(self.log_dir)
        self._win = jax.profiler.TraceAnnotation(WINDOW)
        self._win.__enter__()

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def stop(self, device: int = 0) -> Reduction:
        import jax

        self._win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            paths = sorted(glob.glob(os.path.join(
                self.log_dir, "**", "*.xplane.pb"), recursive=True))
            if not paths:
                raise RuntimeError(f"no trace written under {self.log_dir}")
            ops, spans = read_xspace(paths[-1], device)
            hlo = read_hlo_stats(paths[-1])
        finally:
            shutil.rmtree(self.log_dir, ignore_errors=True)
        return reduce(ops, spans, hlo)
