"""The harness: finds a cell's files by name, runs its set-up, its
measured window and its check, and prints the result line.

Nothing here belongs to one configuration, traffic mix or metric:

* ``BENCHMARK.json`` names each cell's config and traffic;
* ``bench/configs/<config>.json`` holds the deployment's sizes;
* ``bench/traffic/<traffic>.json`` holds the mix, and its ``"phase"``
  names the general runner in ``bench/phases/<phase>.py`` that reads it;
* ``bench/limits/<cell>.json`` holds the limit of each number compared;
* ``bench/metrics/<metric>.py`` reads one per-layer metric (``read(ctx)``).

A later PR adds a cell, a mix or a metric by adding such files and
entries, and edits none.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(Exception):
    """A cell that cannot be run as asked (files, chips, names)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_spec(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    return load_json(path)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = BENCH_DIR
    # kernel dispatch handed to the program (None: its own choice; the
    # tests pass 'interpret' to run the Pallas bodies on the CPU)
    force: Optional[str] = None
    # the lower-precision control: "none", "precision" (the float32 dots
    # at HIGH and the program's own lower-precision paths) or "dots" (the
    # dots alone)
    control: str = "none"

    @property
    def phase(self) -> str:
        return self.traffic["phase"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(spec: dict, name: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell called ``name``, its config, traffic, limits and metrics."""
    wl = {w["name"]: w for w in spec["workloads"]}
    if name not in wl:
        raise BenchError(f"no workload {name!r} (known: {sorted(wl)})")
    w = wl[name]
    cfgs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = cfgs[w["config"]]
    root = os.path.dirname(bench_dir)
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    limits = load_json(os.path.join(bench_dir, "limits", name + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
                bench_dir=bench_dir)


def phase_module(cell: Cell):
    """The general runner ``bench/phases/<phase>.py`` the traffic names."""
    path = os.path.join(cell.bench_dir, "phases", cell.phase + ".py")
    return _load_file(f"bench_phase_{cell.phase}", path)


def metric_reader(cell: Cell, metric: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = os.path.join(cell.bench_dir, "metrics", metric + ".py")
    mod = _load_file("bench_metric_" + metric.replace(".", "_").replace(
        "-", "_"), path)
    return mod.read


def _load_file(modname: str, path: str):
    if not os.path.exists(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

@dataclass
class Window:
    calls: int = 0
    elapsed: float = 0.0
    latencies: List[float] = field(default_factory=list)
    t_start: float = 0.0
    compiles: int = 0           # programs compiled inside the window


class CompileCounter:
    """Counts XLA compilations from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


def run_window(call: Callable[[int], Any], seconds: float,
               annotate: Optional[Callable[[str], Any]] = None) -> Window:
    """Whole calls, back to back, until ``seconds`` have elapsed.  Each
    call ends in ``block_until_ready``; its latency is its own wall time,
    and the window is the wall time of all of them."""
    import jax

    counter = CompileCounter()
    w = Window(t_start=time.perf_counter())
    while True:
        ts = time.perf_counter()
        if annotate is None:
            jax.block_until_ready(call(w.calls))
        else:
            with annotate("bench.call"):
                jax.block_until_ready(call(w.calls))
        te = time.perf_counter()
        w.latencies.append(te - ts)
        w.calls += 1
        w.elapsed = te - w.t_start
        if w.elapsed >= seconds:
            w.compiles = counter.count
            return w


# ---------------------------------------------------------------------------
# one run of a cell
# ---------------------------------------------------------------------------

def device_info(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


def judge(values: Dict[str, float], limits: Dict[str, float]):
    """(correct, checks): every number compared at or under its limit."""
    missing = sorted(set(limits) - set(values))
    if missing:
        raise BenchError(f"check gave no reading for {missing}")
    checks = {k: {"value": float(values[k]), "limit": float(limits[k])}
              for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, control: str = "none",
             require_tpu: bool = True, log=None) -> dict:
    """Set up, measure, check.  Returns the result line as a dict."""
    import jax

    from bench import control as ctl
    from bench import data as bdata

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    devices = jax.devices()[:cell.chips]
    if require_tpu:
        if devices[0].platform != "tpu":
            raise BenchError(f"JAX finds no TPU (platform "
                             f"{devices[0].platform!r})")
        if len(jax.devices()) < cell.chips:
            raise BenchError(f"{cell.chips} chips asked, "
                             f"{len(jax.devices())} found")
    cell.control = control
    if control != "none":
        ctl.lowered()
    phase = phase_module(cell)
    key = bdata.seed_key(seed)
    state = phase.setup(cell, key, seed, log)
    # set-up leaves some 10^5 Python objects (JAX's caches and traced
    # programs); a full collection over them stalls the host for ~0.1 s,
    # once or twice a window, so they are kept out of the collector's view
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_process
    log(f"[setup] {setup_s:.3f}s")

    tracer = None
    if trace:
        from bench import trace as btrace
        tracer = btrace.Tracer(tempfile.mkdtemp(prefix="bench_trace_"))
        tracer.start()
    win = run_window(lambda i: phase.call(state, i), seconds,
                     annotate=tracer.annotate if tracer else None)
    reduction = tracer.stop() if tracer else None
    gc.unfreeze()
    peak = memory_peak(devices)
    log(f"[window] {win.calls} calls in {win.elapsed:.3f}s, "
        f"{win.compiles} compiled inside; slowest call "
        f"{max(win.latencies):.4f}s")

    metrics: Dict[str, dict] = {}
    if not trace:
        got = phase.end_to_end(state, win)
        got["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] not in got:
                raise BenchError(f"phase {cell.phase!r} gave no "
                                 f"{m['name']!r}")
            metrics[m["name"]] = {"value": got[m["name"]], "unit": m["unit"]}
    else:
        from bench.layer import Context
        ctx = Context(reduction=reduction,
                      counts=phase.counts(state, win),
                      work=phase.work(state, win),
                      device_kind=devices[0].device_kind)
        for m in cell.per_layer:
            val = metric_reader(cell, m["name"])(ctx)
            if val is None:
                continue
            entry = {"value": float(val), "unit": m["unit"]}
            entry.update(ctx.notes.get(m["name"], {}))
            metrics[m["name"]] = entry

    if control != "none":
        ctl.restore()
    values = phase.check(state, win, seed, log)
    correct, checks = judge(values, cell.limits)
    for k, c in checks.items():
        log(f"[check] {k} {c['value']!r} limit {c['limit']!r}")
    attempted, failed = phase.attempted(state, win)
    dev = device_info(devices)
    dev["memory_peak_bytes"] = peak
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": dev}
    if reduction is not None:
        dev["busy_s"] = reduction.busy_s
        dev["window_s"] = reduction.window_s
        out["breakdown"] = reduction.breakdown()
    out["checks"] = checks
    return out
