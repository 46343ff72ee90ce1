"""Unified observability layer: device-half telemetry + host-half tooling.

Device half (`obs.telemetry`): a fixed-shape `Telemetry` pytree accumulated
INSIDE the existing single-sync traces (engine while_loop, graph-build scan,
sharded-IVF shard_map) so per-epoch metrics ride the same host sync as the
results.  Host half: `span()`, the one span recorder (host time, a
profiler annotation and counts, kept in a bounded ring that `recent()`
reads), and the named scopes that mark device work in a trace
(`kernel_scope`, `layer_scope`), all in `obs.timing`; the reusable
transfer-guard `sync_counter()` (`obs.syncs`), and the one structured
run-record schema behind every BENCH_*.json (`obs.emit`).
`launch/obs_report.py` joins the emitted records against the analytic
roofline models.
"""
from repro.obs import telemetry
from repro.obs.emit import (SCHEMA, append_jsonl, load_dir, load_records,
                            run_record, validate_record, write_json)
from repro.obs.syncs import SyncCounter, sync_counter
from repro.obs.telemetry import Telemetry
from repro.obs.timing import Span, kernel_scope, layer_scope, recent, span

__all__ = [
    "telemetry", "Telemetry",
    "SyncCounter", "sync_counter",
    "Span", "span", "recent", "kernel_scope", "layer_scope",
    "SCHEMA", "run_record", "write_json", "append_jsonl", "load_records",
    "load_dir", "validate_record",
]
