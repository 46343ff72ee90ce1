"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data on the device from ``--seed``, builds what
the traffic needs and warms every program the window runs; the window
then runs whole timed calls until ``--seconds`` have elapsed; afterwards
the outputs of the timed calls are compared with the plain references in
``bench/reference.py``.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared beside its limit, which the last lines of standard error repeat).
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.

The run fails, and prints no result, where JAX finds no TPU or fewer chips
than the cell asks for.  ``--control precision`` runs the lower-precision
control (``bench/control.py``) in the program's place, and ``--control
dots`` its float32 dots alone; they are for setting limits, not for the
benchmark's own runs.

JAX's persistent compilation cache lives at ``.jax_cache/`` in the
checkout (``JAX_COMPILATION_CACHE_DIR`` where that is set).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("none", "precision", "dots"),
                    default="none")
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if args.control != "none":
        # the control's bf16 round trips must stay in the program: XLA may
        # otherwise drop a convert pair as excess precision
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_allow_excess_precision=false")
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness

    try:
        cell = harness.resolve(harness.load_spec(ROOT), args.workload)
        import jax
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               control=args.control)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
