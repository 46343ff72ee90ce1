"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  --full switches the paper-scale
sizes on (hours on CPU; the quick sizes preserve every ratio being tested).
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import emit

SUITES = ["fig1_cooccurrence", "fig2_tau", "fig4_config", "fig5_quality",
          "fig6_scalability", "table2_large_k", "anns_recall",
          "anns_ivf_bench", "engine_bench", "graph_build_bench",
          "kernels_bench", "kv_cluster_bench", "ablation_guided",
          "roofline_report"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (very slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()
    from repro.launch import runtime
    runtime.init()

    suites = args.only.split(",") if args.only else SUITES
    print("name,us_per_call,derived")
    ok = True
    for name in suites:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            rows = mod.run(quick=not args.full)
            emit(rows)
            print(f"# {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            ok = False
            print(f"{name}/FAILED,0.0,{type(e).__name__}:{e}")
    if not ok:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
