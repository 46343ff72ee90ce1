"""Roofline-term extraction from compiled dry-run artifacts.

  compute   = FLOPs_per_chip / peak_FLOPs
  memory    = HBM_bytes_per_chip / HBM_bw
  collective= collective_bytes_per_chip / link_bw

cost_analysis() of an SPMD-partitioned executable reports the PER-PARTITION
program, so its flops/bytes are already per-chip (verified empirically in
tests/test_roofline.py).  Collective bytes are not in cost_analysis — we parse
the optimized HLO and sum operand sizes of every collective op.

This module owns the HARDWARE/KERNEL side of the launch tooling: the chip
constants, the Pallas ``KERNEL_INVENTORY``, and the HLO-derived roofline
terms.  The analytic LLM-template cost models (transformer/SSM/MoE
FLOP/HBM/param estimators) live in ``launch.llm_cost`` — they model language
models, not the clustering kernels, and nothing here depends on them.
"""
from __future__ import annotations

import re
from typing import Dict, NamedTuple


class Peaks(NamedTuple):
    flops: float         # bf16 FLOP/s per chip
    hbm_bw: float        # HBM bytes/s per chip
    ici_bw: float        # chip-to-chip bytes/s per link


# Published per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s of interchip interconnect over 4 links (50 GB/s each).
# JAX reports a v5e chip as "TPU v5 lite".
PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9)}


def peaks(device_kind: str) -> Peaks:
    """Published peaks of one chip; a kind not in ``PEAKS`` is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None

# ---------------------------------------------------------------------------
# Pallas kernel inventory — analytic per-call FLOP / HBM-byte models for the
# custom kernels (src/repro/kernels/).  `flops`/`hbm_bytes` take the call
# shape and return per-call totals; benchmarks divide by measured time for
# roofline fractions (``launch.obs_report`` joins this inventory against
# BENCH_kernels.json to print achieved vs roofline).
#
# Row-tiled kernels (`tunable=True`) take a row-tile size chosen per
# (kernel, backend, shape) from the checked-in ``kernels/autotune_table.json``
# — every tile is bitwise-identical, so the table is pure performance config.
# BENCH_kernels.json entries for these kernels carry the dispatched ``tile``
# and, in --quick runs, ``us_rowwise`` (the legacy per-row oracle the tiled
# path must beat).  Refresh the table with:
#
#   PYTHONPATH=src python benchmarks/kernels_bench.py --autotune --quick
# ---------------------------------------------------------------------------

KERNEL_INVENTORY = {
    "pairwise_sq": dict(
        tunable=True,
        desc="batched (B, m, m) within-cluster distance matrices (Alg. 3 "
             "refinement hot-spot), one MXU matmul per cluster tile",
        flops=lambda B, m, d: 2.0 * B * m * m * d,
        hbm_bytes=lambda B, m, d: 4.0 * (B * m * d + B * m * m),
    ),
    "assign_centroids": dict(
        desc="flash-argmin nearest-centroid assignment: centroid tiles "
             "stream through VMEM, O(n*d + k*d + n) HBM traffic",
        flops=lambda n, k, d: 2.0 * n * k * d,
        hbm_bytes=lambda n, k, d: 4.0 * (n * d + k * d + 2 * n),
    ),
    "probe_centroids": dict(
        desc="top-p generalisation of the flash-argmin (IVF coarse probe / "
             "engine probe candidates)",
        flops=lambda n, k, d, p: 2.0 * n * k * d,
        hbm_bytes=lambda n, k, d, p: 4.0 * (n * d + k * d + 2 * n * p),
    ),
    "ivf_scan": dict(
        tunable=True,
        desc="scalar-prefetch inverted-list tile streaming with running "
             "top-k; HBM traffic is only the probed fraction",
        flops=lambda q, rows, d, topk: 2.0 * q * rows * d,
        hbm_bytes=lambda q, rows, d, topk: 4.0 * (q * d + q * rows * d
                                                  + 2 * q * topk),
    ),
    "ivf_scan_adc": dict(
        tunable=True,
        desc="asymmetric-distance scan of compressed lists: per-query "
             "(M, W) LUT stays VMEM-resident while u8 codes stream — "
             "(M + 4) HBM bytes per candidate row instead of 4d (W=256 "
             "pq one-hot MXU path, W=1 int8 direct dot)",
        flops=lambda q, rows, m, w, topk: 2.0 * q * rows * m * w,
        hbm_bytes=lambda q, rows, m, w, topk: (4.0 * q * m * w
                                               + q * rows * (m + 4.0)
                                               + 4.0 * 3 * q * topk),
    ),
    "ivf_scan_grouped": dict(
        desc="query-grouped inverted-list scan: G probe-local queries share "
             "each streamed list tile, so tile HBM traffic amortizes by the "
             "group's probe overlap (per-call: q queries, `rows` deduped "
             "union rows per group of G)",
        flops=lambda q, rows, d, topk, G: 2.0 * q * rows * d,
        hbm_bytes=lambda q, rows, d, topk, G: 4.0 * (q * d
                                                     + (q / G) * rows * d
                                                     + 2 * q * topk),
    ),
    "gather_score": dict(
        tunable=True,
        desc="fused candidate-row gather + ΔI/distance scoring in VMEM "
             "(engine move step); the (B, C, d) gathered tensor never "
             "reaches HBM",
        flops=lambda B, C, d: 6.0 * B * (C + 1) * d,
        hbm_bytes=lambda B, C, d: 4.0 * (B * d + B * (C + 1) * (d + 1)
                                         + B * C),
    ),
    "refine_merge": dict(
        tunable=True,
        desc="fused candidate-distance + top-κ merge (graph-build "
             "refinement hot path): candidate rows stream HBM→VMEM by "
             "scalar-prefetch indexing, the merge runs in-register — "
             "neither the (B, C, d) gather nor the (B, C) distance "
             "matrix reaches HBM",
        flops=lambda B, C, d, kappa: (3.0 * B * C * d
                                      + 4.0 * B * kappa * (kappa + C)),
        hbm_bytes=lambda B, C, d, kappa: 4.0 * (B * d + B * C * d + B * C
                                                + 4.0 * B * kappa),
    ),
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]\w*?)\[([\d,]*)\]")


def _nbytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def peak_memory_bytes(mem) -> int:
    """Peak HBM bytes from `compiled.memory_analysis()` across jax versions.

    Newer jaxlibs dropped `peak_memory_in_bytes`; argument + output + temp
    is the same upper bound XLA reported there.
    """
    peak = getattr(mem, "peak_memory_in_bytes", None)
    if peak is None:
        peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    return peak


def cost_analysis(compiled) -> Dict[str, float]:
    """`compiled.cost_analysis()` as a dict across jax versions.

    Older jaxlibs return a one-element list of dicts, newer ones the dict
    itself; normalize so callers can `.get("flops")` either way.
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


# ---------------------------------------------------------------------------
# while-aware HLO traversal
#
# XLA's cost_analysis() and a naive text scan both count a while (scan) body
# ONCE, not multiplied by its trip count (verified in tests/test_roofline.py).
# Every layer loop / kv-chunk loop / loss-chunk loop in this codebase is a
# scan, so loop-resident collectives must be scaled by the loop nest's trip
# counts.  We parse computations, read each while condition's bound constant,
# and propagate multiplicities down the while-nest.
# ---------------------------------------------------------------------------

_COMP_START = re.compile(r"^(?:ENTRY\s+)?(%[\w\.\-]+)\s*\(")
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=(%?[\w\.\-]+), body=(%?[\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CALLS_RE = re.compile(r"calls=(%[\w\.\-]+)")


def _computations(hlo_text: str):
    """name -> list of body lines; also returns the entry computation name.

    Headers may contain nested parens and wrap across lines; a computation
    body runs from its opening '{' to a line that is exactly '}'.
    """
    comps, entry = {}, None
    cur = None
    pending_name, pending_entry = None, False
    for line in hlo_text.splitlines():
        s = line.strip()
        if cur is None:
            if pending_name is None:
                m = _COMP_START.match(s)
                if m:
                    pending_name = m.group(1)
                    pending_entry = s.startswith("ENTRY")
            if pending_name is not None and s.endswith("{"):
                cur = pending_name
                comps[cur] = []
                if pending_entry:
                    entry = cur
                pending_name, pending_entry = None, False
            continue
        if s == "}":
            cur = None
            continue
        comps[cur].append(line)
    return comps, entry


def _multiplicities(hlo_text: str):
    """comp name -> times executed (product of enclosing while trip counts)."""
    comps, entry = _computations(hlo_text)
    whiles = {}  # comp -> list[(cond, body)]
    for name, lines in comps.items():
        lst = []
        for ln in lines:
            m = _WHILE_RE.search(ln)
            if m:
                lst.append((m.group(1), m.group(2)))
        whiles[name] = lst

    def trip(cond_name: str) -> int:
        # The bound is usually a literal in the condition body; post-fusion
        # HLO (e.g. XLA:CPU's "wide" loop transform) moves the compare into a
        # called fusion, so if the body has no constant, descend into calls=.
        text = "\n".join(comps.get(cond_name, []))
        seen = {cond_name}
        while True:
            ints = [int(x) for x in _CONST_RE.findall(text)]
            if ints:
                return max(ints)
            callees = [c for c in _CALLS_RE.findall(text)
                       if c in comps and c not in seen]
            if not callees:
                return 1
            seen.update(callees)
            text = "\n".join("\n".join(comps[c]) for c in callees)

    mult = {name: 1.0 for name in comps}
    if entry:
        # BFS from entry, accumulating multiplicity into while bodies/conds
        from collections import deque
        seen_depth = {entry: 1.0}
        q = deque([entry])
        while q:
            c = q.popleft()
            m = seen_depth[c]
            mult[c] = m
            for cond, body in whiles.get(c, []):
                t = trip(cond)
                for sub in (body, cond):
                    nm = m * t if sub == body else m
                    if seen_depth.get(sub, 0) < nm:
                        seen_depth[sub] = nm
                        q.append(sub)
    return mult, comps


_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_V1_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def collective_bytes_corrected(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Like collective_bytes, but multiplies each collective by the trip-count
    product of its enclosing while (scan) nest — the physically-executed
    traffic."""
    mult, comps = _multiplicities(hlo_text)
    out: Dict[str, Dict[str, float]] = {
        c: {"bytes": 0.0, "wire_bytes": 0.0, "count": 0}
        for c in _COLLECTIVES}
    for name, lines in comps.items():
        m_comp = mult.get(name, 1.0)
        for ln in lines:
            _accumulate_collective(ln.strip(), out, m_comp)
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    out["total_wire_bytes"] = sum(v["wire_bytes"] for v in out.values()
                                  if isinstance(v, dict))
    return out


def _accumulate_collective(stripped: str, out, weight: float) -> None:
    m = re.search(r"=\s*(\([^)]*\)|\S+)\s+([\w-]+)\(", stripped)
    if not m:
        return
    op = m.group(2)
    kind = None
    for c in _COLLECTIVES:
        if op == c or op.startswith(c + "-") or \
                (op.startswith(c) and op[len(c):len(c) + 1] == "."):
            kind = c
            break
    if kind is None or op.endswith("-done"):
        return
    shapes = _SHAPE_RE.findall(m.group(1))
    result = sum(_nbytes(d, s) for d, s in shapes)
    g = _group_size(stripped)
    if kind == "all-gather":
        operand, wire = result / g, result * (g - 1) / g
    elif kind == "reduce-scatter":
        operand, wire = result * g, result * (g - 1)
    elif kind == "all-reduce":
        operand, wire = result, 2.0 * result * (g - 1) / g
    elif kind == "all-to-all":
        operand, wire = result, result * (g - 1) / g
    else:
        operand, wire = result, result
    out[kind]["bytes"] += operand * weight
    out[kind]["wire_bytes"] += wire * weight
    out[kind]["count"] += weight


def collective_bytes(hlo_text: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-type byte totals from optimized HLO text.

    HLO prints operands as plain %refs, so sizes are derived from the RESULT
    shape + replica group size g:
      operand bytes : all-gather = result/g; reduce-scatter = result*g;
                      all-reduce / all-to-all / permute = result.
      wire bytes    : bytes physically moved per device (ring algorithms):
                      all-gather / reduce-scatter / all-to-all =
                      full_buffer*(g-1)/g; all-reduce = 2*buffer*(g-1)/g;
                      collective-permute = result.
    The collective roofline term uses wire bytes.
    """
    out: Dict[str, Dict[str, float]] = {
        c: {"bytes": 0.0, "wire_bytes": 0.0, "count": 0}
        for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*(\([^)]*\)|\S+)\s+([\w-]+)\(", stripped)
        if not m:
            continue
        op = m.group(2)
        kind = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-") or \
                    (op.startswith(c) and op[len(c):len(c) + 1] == "."):
                kind = c
                break
        if kind is None or op.endswith("-done"):
            continue
        shapes = _SHAPE_RE.findall(m.group(1))
        result = sum(_nbytes(d, s) for d, s in shapes)
        g = _group_size(stripped)
        if kind == "all-gather":
            operand = result / g
            wire = result * (g - 1) / g
        elif kind == "reduce-scatter":
            operand = result * g
            wire = result * (g - 1)
        elif kind == "all-reduce":
            operand = result
            wire = 2.0 * result * (g - 1) / g
        elif kind == "all-to-all":
            operand = result
            wire = result * (g - 1) / g
        else:  # collective-permute
            operand = result
            wire = result
        out[kind]["bytes"] += operand
        out[kind]["wire_bytes"] += wire
        out[kind]["count"] += 1
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    out["total_wire_bytes"] = sum(v["wire_bytes"] for v in out.values()
                                  if isinstance(v, dict))
    return out


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float, *,
                   device_kind: str, links: int = 3) -> Dict[str, float]:
    """All three terms in seconds (per chip of ``device_kind``).
    `links`: ICI links engaged."""
    pk = peaks(device_kind)
    t_c = flops / pk.flops
    t_m = hbm_bytes / pk.hbm_bw
    t_x = coll_bytes / (pk.ici_bw * links)
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])
    total = max(t_c, t_m, t_x)
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "bottleneck": dom[0],
            "roofline_fraction": (t_c / total if total > 0 else 0.0)}
