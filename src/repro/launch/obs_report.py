"""Observability report: measured BENCH records vs the analytic roofline.

Joins the unified ``repro.bench.v1`` run records (``repro.obs.emit``) that
the benchmarks write against ``launch.roofline.KERNEL_INVENTORY``:

  * kernel table — each measured kernel's microseconds vs the analytic
    roofline bound for its recorded shape (compute vs HBM term, whichever
    binds), with the achieved fraction;
  * per-phase breakdown — the per-epoch / per-round / per-batch telemetry
    rows that rode each device-resident run's single host sync (engine
    epochs, graph-build rounds, sharded-IVF scan counters).

This doubles as the CI schema gate: any ``BENCH_*.json`` that drifted from
the schema, any timed kernel missing from ``KERNEL_INVENTORY``, and any
name in ``--require`` that is absent all exit nonzero.  A ``--require``
token matches either a whole record (``BENCH_<name>.json``) or a single
measured kernel inside the ``kernels`` record — so CI can insist that e.g.
``ivf_scan`` and ``ivf_scan_grouped`` stay on the bench.

Row-tiled kernels report the autotuned ``tile`` the dispatch used (from
``kernels/autotune_table.json``; "-" for untiled kernels) and, when the
bench measured it, ``rowwise_x`` — the speedup over the legacy per-row
oracle.

CLI::

    python -m repro.launch.obs_report [--dir .] \
        [--require kernels engine ivf_scan]
"""
from __future__ import annotations

import argparse
import sys
from typing import Any, Dict, List

from repro.launch.roofline import KERNEL_INVENTORY, PEAKS, roofline_terms
from repro.obs import emit


class ReportError(RuntimeError):
    """Schema drift / inventory gap — the CI-failing condition."""


def _fmt_table(header: List[str], rows: List[List[str]]) -> str:
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    def line(cells):
        return "  ".join(str(c).ljust(w) for c, w in zip(cells, widths))
    rule = "  ".join("-" * w for w in widths)
    return "\n".join([line(header), rule] + [line(r) for r in rows])


def kernel_table(rec: Dict[str, Any]) -> str:
    """Measured-vs-analytic roofline table from a ``kernels`` record,
    against the peaks of the device the record was measured on.  A device
    without published peaks (a CPU run) gets its timings with "-" in the
    roofline columns: no chip's peaks are assumed for it."""
    entries = rec["metrics"].get("kernels", [])
    if not entries:
        raise ReportError("kernels record has no metrics['kernels'] entries")
    kind = rec["env"].get("device_kind")
    rows = []
    for e in entries:
        name = e["kernel"]
        inv = KERNEL_INVENTORY.get(name)
        if inv is None:
            raise ReportError(
                f"measured kernel {name!r} has no KERNEL_INVENTORY entry")
        meas_us = float(e["us"])
        bound, frac, bottleneck = "-", "-", "-"
        if kind in PEAKS:
            shape = e["shape"]
            flops = inv["flops"](*shape.values())
            hbm = inv["hbm_bytes"](*shape.values())
            terms = roofline_terms(flops, hbm, 0.0, device_kind=kind)
            bound_us = max(terms["compute_s"], terms["memory_s"]) * 1e6
            bound, bottleneck = f"{bound_us:.2f}", terms["bottleneck"]
            frac = f"{bound_us / meas_us if meas_us > 0 else 0.0:.4f}"
        dims = ",".join(f"{k}={v}" for k, v in e["shape"].items())
        tile = str(e["tile"]) if "tile" in e else "-"
        roww = (f"{float(e['us_rowwise']) / meas_us:.2f}x"
                if e.get("us_rowwise") and meas_us > 0 else "-")
        rows.append([name, dims, f"{meas_us:.1f}", bound, bottleneck, frac,
                     tile, roww])
    return _fmt_table(
        ["kernel", "shape", "measured_us", "roofline_us", "bound",
         "achieved_frac", "tile", "rowwise_x"], rows)


def phase_table(rec: Dict[str, Any]) -> str:
    """Per-row telemetry breakdown of one record (epoch/round/batch)."""
    tel = rec.get("telemetry") or {}
    slots = [s for s, vals in tel.items() if vals]
    if not slots:
        return "(no telemetry section)"
    n_rows = len(tel[slots[0]])
    rows = []
    for t in range(n_rows):
        cells = [str(t)]
        for s in slots:
            v = tel[s][t]
            cells.append(f"{v:.4f}" if isinstance(v, float) else str(v))
        rows.append(cells)
    return _fmt_table(["row"] + slots, rows)


def render(recs: Dict[str, Dict[str, Any]]) -> str:
    out = []
    if "kernels" in recs:
        kind = recs["kernels"]["env"].get("device_kind")
        out.append(f"== kernel roofline (measured vs analytic, {kind}) ==")
        if kind not in PEAKS:
            out.append(f"(no published peaks for {kind!r}: roofline not "
                       f"computed)")
        out.append(kernel_table(recs["kernels"]))
        out.append("")
    for name, rec in sorted(recs.items()):
        if name == "kernels":
            continue
        out.append(f"== {name} [{rec['git_rev']} "
                   f"{rec['env'].get('backend')}x"
                   f"{rec['env'].get('devices')}] ==")
        m = rec["metrics"]
        flat = [k for k, v in m.items() if isinstance(v, (int, float, bool))]
        for k in flat:
            out.append(f"  {k} = {m[k]}")
        tele = phase_table(rec)
        if tele != "(no telemetry section)":
            out.append("  per-phase telemetry:")
            out.append("\n".join("    " + ln for ln in tele.splitlines()))
        out.append("")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=".",
                    help="directory holding BENCH_*.json run records")
    ap.add_argument("--require", nargs="*", default=[],
                    help="record names — or measured kernel names inside the "
                         "kernels record — that must be present (CI gate)")
    args = ap.parse_args(argv)

    try:
        recs = emit.load_dir(args.dir)
    except ValueError as e:                 # schema drift
        print(f"obs_report: schema error: {e}", file=sys.stderr)
        return 1
    timed_kernels = {e["kernel"]
                     for e in (recs.get("kernels", {})
                               .get("metrics", {}).get("kernels", []))}
    missing = [r for r in args.require
               if r not in recs and r not in timed_kernels]
    if missing:
        print(f"obs_report: required records missing: {missing} "
              f"(have records {sorted(recs)}, kernels "
              f"{sorted(timed_kernels)})", file=sys.stderr)
        return 1
    if not recs:
        print(f"obs_report: no BENCH_*.json records in {args.dir!r}",
              file=sys.stderr)
        return 1
    try:
        print(render(recs))
    except ReportError as e:
        print(f"obs_report: {e}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
