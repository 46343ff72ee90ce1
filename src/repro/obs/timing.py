"""Host spans, and named scopes that mark device work in the trace.

``span(name)`` is the program's one span recorder.  It times a block on
the host, ``block_until_ready``-ing whatever the block assigns to
``sp.result`` so that async dispatch cannot leak out of the measurement
(the classic JAX timing bug); without a ``.result`` it times dispatch
only.  Each span also opens a ``jax.profiler.TraceAnnotation`` of its name,
so in a profiler trace it sits on the host plane beside the device ops.
Every finished span goes into a bounded in-memory ring (``RING_SIZE``) as
a ``SpanRecord``: name, start and end in ns (``time.perf_counter_ns``),
the enclosing span's name, self time (its duration less that of the spans
opened inside it) and the counts the block filed with ``sp.count(...)``.
``recent(name, n)`` reads the last ``n`` records of a name.

``kernel_scope(name)`` wraps every Pallas kernel call site in
``kernels/ops.py`` with a ``jax.named_scope`` (``repro.kernels.<name>``),
and ``layer_scope(layer, part)`` marks a layer's own device work
(``repro.<layer>.<part>``, e.g. ``repro.graph.tree``).  The names land in
the HLO metadata and in ``jax.profiler`` traces, so a profile attributes
device time to them (``named_scope`` rather than ``TraceAnnotation``
because these run INSIDE enclosing jit traces, where only trace-time
scoping survives).  A kernel scope inside a layer scope stays innermost:
a kernel's time is the kernel's, whichever layer called it.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

import jax

SCOPE_PREFIX = "repro.kernels"
RING_SIZE = 8192


class SpanRecord(NamedTuple):
    """One finished span, as the ring keeps it."""

    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]
    self_ns: int
    counts: Dict[str, int]


class Span:
    """One timed block; set ``.result`` to what must finish on device."""

    def __init__(self, name: str, parent: Optional["Span"]) -> None:
        self.name = name
        self.parent = parent
        self.result = None
        self.seconds: Optional[float] = None
        self.counts: Dict[str, int] = {}
        self.child_ns = 0

    def count(self, **n: int) -> None:
        """Add to the span's named counts."""
        for k, v in n.items():
            self.counts[k] = self.counts.get(k, 0) + int(v)


_RING: "collections.deque[SpanRecord]" = collections.deque(maxlen=RING_SIZE)
_OPEN = threading.local()


@contextlib.contextmanager
def span(name: str) -> Iterator[Span]:
    """Time and record a block: ``with span("run") as sp: sp.result = f(x)``.

    On exit, blocks until ``sp.result`` is ready (if set), sets
    ``sp.seconds`` and appends the span's record to the ring.
    """
    stack = _OPEN.__dict__.setdefault("stack", [])
    sp = Span(name, stack[-1] if stack else None)
    stack.append(sp)
    try:
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter_ns()
            yield sp
            if sp.result is not None:
                jax.block_until_ready(sp.result)
            t1 = time.perf_counter_ns()
    finally:
        stack.pop()
    sp.seconds = (t1 - t0) * 1e-9
    if sp.parent is not None:
        sp.parent.child_ns += t1 - t0
    _RING.append(SpanRecord(name, t0, t1, sp.parent and sp.parent.name,
                            t1 - t0 - sp.child_ns, dict(sp.counts)))


def recent(name: str, n: int) -> List[SpanRecord]:
    """The last ``n`` recorded spans called ``name``, oldest first (fewer
    where the ring holds fewer)."""
    if n <= 0:
        return []
    out = []
    for rec in reversed(list(_RING)):
        if rec.name == name:
            out.append(rec)
            if len(out) == n:
                break
    return out[::-1]


def clear() -> None:
    """Empty the ring."""
    _RING.clear()


def kernel_scope(name: str):
    """Named scope for a kernel dispatch site (profiler/HLO attribution)."""
    return jax.named_scope(f"{SCOPE_PREFIX}.{name}")


def layer_scope(layer: str, part: str):
    """Named scope ``repro.<layer>.<part>`` round one part of a layer's own
    device work (profiler/HLO attribution); never a kernel's."""
    if f"repro.{layer}" == SCOPE_PREFIX:
        raise ValueError("repro.kernels.* scopes are kernel_scope's")
    return jax.named_scope(f"repro.{layer}.{part}")
