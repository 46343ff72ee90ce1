"""Shared benchmark utilities."""
from __future__ import annotations

import time
from typing import Callable, List, Tuple

import jax

Row = Tuple[str, float, str]  # (name, us_per_call, derived)


def timed_stats(fn: Callable, *args, warmup: int = 1, iters: int = 5) -> dict:
    """Steady-state timing of fn(*args): the warm-up calls (jit compile +
    first dispatch) are timed separately and NEVER pollute the reported
    median.  Returns {"us": median steady-state wall-us, "compile_us": first
    warm-up call wall-us, "iters": iters}."""
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    compile_us = (time.perf_counter() - t0) * 1e6
    for _ in range(warmup - 1):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return {"us": ts[len(ts) // 2] * 1e6, "compile_us": compile_us,
            "iters": iters}


def timed(fn: Callable, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median steady-state wall time (us) of fn(*args); warm-up discarded."""
    return timed_stats(fn, *args, warmup=warmup, iters=iters)["us"]


def emit(rows: List[Row]) -> None:
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


def merge_scale_record(path: str, prefix: str, shapes: dict, config: dict,
                       metrics: dict) -> None:
    """Merge one bench's section into the shared ``BENCH_scale.json``.

    engine_bench and graph_build_bench both contribute to ONE ``scale`` run
    record (the large-k wire-cost figures belong side by side).  Each bench
    owns the keys under its ``<prefix>.`` namespace: existing keys from the
    OTHER bench survive, this bench's stale keys are dropped before its
    fresh ones merge, so the file is valid ``repro.bench.v1`` after either
    bench runs in either order.
    """
    import os
    from repro.obs import load_records, run_record, write_json
    sh: dict = {}
    cf: dict = {}
    mt: dict = {}
    if os.path.exists(path):
        try:
            rec = load_records(path)[0]
            if rec["name"] == "scale":
                sh, cf, mt = rec["shapes"], rec["config"], rec["metrics"]
        except Exception:
            pass                      # drifted file: rebuild from scratch
    tag = prefix + "."

    def _merge(old: dict, new: dict) -> dict:
        kept = {k: v for k, v in old.items() if not k.startswith(tag)}
        kept.update({tag + k: v for k, v in new.items()})
        return kept

    write_json(path, run_record("scale", shapes=_merge(sh, shapes),
                                config=_merge(cf, config),
                                metrics=_merge(mt, metrics)))


def run_forced_host_child(bench_file: str, quick: bool, devices: int,
                          timeout: int = 3600,
                          extra: Tuple[str, ...] = ()) -> None:
    """Re-run `bench_file --child` under R forced host CPU devices.

    A CPU rehearsal of a sharded mode: the child sees ``devices`` virtual
    CPU devices (``--xla_force_host_platform_device_count``).  It runs only
    where JAX is pinned to the CPU (``JAX_PLATFORMS=cpu``): on a machine
    with an accelerator the sharded modes run in-process on the real
    devices (``run_sharded_mode``), since a child would measure CPU devices
    — or, given the chip, find it held by the parent.
    """
    import os
    import subprocess
    import sys
    if not cpu_pinned():
        raise RuntimeError(
            "run_forced_host_child is a CPU rehearsal and needs "
            "JAX_PLATFORMS=cpu; on an accelerator run the sharded mode "
            "in-process on the real devices")
    here = os.path.dirname(os.path.abspath(bench_file))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    cmd = [sys.executable, os.path.abspath(bench_file), "--child",
           "--quick" if quick else "--full", *extra]
    subprocess.run(cmd, check=True, env=env, timeout=timeout)


def cpu_pinned() -> bool:
    """Whether JAX is pinned to the CPU — read from the environment, so the
    answer initialises no backend."""
    import os
    return os.environ.get("JAX_PLATFORMS", "") == "cpu"


def run_sharded_mode(bench_file: str, body: Callable[[bool], None],
                     quick: bool, devices: int,
                     extra: Tuple[str, ...] = ()) -> None:
    """Run a sharded bench mode's ``body(quick)``: in-process on the real
    devices, or — where JAX is pinned to the CPU — rehearsed on ``devices``
    forced host devices in a child (``run_forced_host_child``)."""
    if cpu_pinned():
        run_forced_host_child(bench_file, quick, devices, extra=extra)
    else:
        body(quick)
