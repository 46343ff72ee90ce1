"""Checked-in tile-size autotune table for the row-tiled kernels.

``benchmarks/kernels_bench.py --autotune`` sweeps every tunable kernel over a
small grid of row-tile sizes at the bench shapes, asserts the winner is no
slower than the default config, and records the winners into
``autotune_table.json`` (next to this module, checked in).  ``kernels.ops``
consults the table at dispatch (trace) time — shapes are static under jit,
so the lookup costs nothing at runtime — and an explicit ``tile=`` argument
always overrides it.

Tile semantics are identical on every backend because the tiled arithmetic
is bitwise tile-invariant (see ``ref.batched_gather_dots``): on TPU the tile
is the Pallas kernel's ``bB`` row-tile (VMEM working-set size), on CPU it is
the ``lax.map`` chunk of the reference's gathered working set (cache
blocking).  ``tile=0`` means "one tile for the whole batch" — capped, in
the TPU kernels, by their VMEM budget (``row_tile``).  Only rows of the
running backend are ever read: the checked-in rows are all ``cpu``, so on
a TPU every kernel runs at its VMEM-budget tile until a chip sweep records
``tpu`` rows.

Table schema (``repro.autotune.v1``)::

    {"schema": "repro.autotune.v1",
     "entries": [{"kernel": "gather_score", "backend": "cpu",
                  "shape": {"B": 8192, "C": 16, "d": 128},
                  "tile": 2048, "us": 712.4, "us_default": 761.0}, ...]}

Lookups match on (kernel, backend); among entries the one whose batch size
is nearest in log-space wins (exact shape matches have distance 0), so the
engine's B=1024 epoch batches reuse the B=8192 bench winner rather than
falling back to the untuned default.
"""
from __future__ import annotations

import functools
import json
import math
import os
from typing import Any, Dict, List, Optional

SCHEMA = "repro.autotune.v1"
TABLE_FILE = os.path.join(os.path.dirname(__file__), "autotune_table.json")

# tile used when the table has no entry for (kernel, backend); 0 = untiled
# (ivf_scan_adc defaults tiled: its ref one-hot-expands pq codes, so the
# chunk bounds the expanded working set even before any table exists)
DEFAULT_TILE = {"gather_score": 0, "refine_merge": 0, "pairwise_sq": 0,
                "ivf_scan": 0, "ivf_scan_adc": 64}

# sweep grids per kernel (candidate tiles; 0 = whole batch, the default)
SWEEP_TILES = {
    "gather_score": (0, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096),
    "refine_merge": (0, 128, 256, 512, 1024, 2048),
    "pairwise_sq": (0, 8, 32, 128),
    "ivf_scan": (0, 16, 64, 256),
    "ivf_scan_adc": (0, 8, 32, 128),
}

# the batch-like dim used for nearest-shape matching, per kernel
_BATCH_DIM = ("B", "n", "q")


@functools.lru_cache(maxsize=1)
def load_table(path: Optional[str] = None) -> tuple:
    """Parsed table entries (cached; ``save`` clears the cache).

    ``path=None`` reads the module-level ``TABLE_FILE`` at call time, so
    tests can repoint the table by patching that attribute.
    """
    if path is None:
        path = TABLE_FILE
    if not os.path.exists(path):
        return ()
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != SCHEMA:
        raise ValueError(f"{path}: expected schema {SCHEMA!r}, "
                         f"got {doc.get('schema')!r}")
    return tuple(doc.get("entries", ()))


def save(entries: List[Dict[str, Any]], path: str = TABLE_FILE) -> None:
    """Write the table (sorted for stable diffs) and drop the lookup cache."""
    key = lambda e: (e["kernel"], e["backend"],
                     sorted(e["shape"].items()))
    doc = {"schema": SCHEMA, "entries": sorted(entries, key=key)}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    load_table.cache_clear()


def record(entries: List[Dict[str, Any]], kernel: str, backend: str,
           shape: Dict[str, int], tile: int, us: float,
           us_default: float) -> None:
    """Insert/replace one sweep winner in an entry list (same-shape dedupe)."""
    entries[:] = [e for e in entries
                  if not (e["kernel"] == kernel and e["backend"] == backend
                          and e["shape"] == shape)]
    entries.append({"kernel": kernel, "backend": backend, "shape": shape,
                    "tile": int(tile), "us": float(us),
                    "us_default": float(us_default)})


def _batch_of(shape: Dict[str, Any]) -> Optional[int]:
    for k in _BATCH_DIM:
        if k in shape:
            return int(shape[k])
    return None


def best_tile(kernel: str, backend: str, shape: Dict[str, int]) -> int:
    """Tuned tile for the nearest recorded shape, else the kernel default."""
    entries = [e for e in load_table()
               if e["kernel"] == kernel and e["backend"] == backend]
    if not entries:
        return DEFAULT_TILE.get(kernel, 0)
    b = _batch_of(shape)

    def dist(e):
        if e["shape"] == dict(shape):
            return -1.0                        # exact shape match wins
        eb = _batch_of(e["shape"])
        if b is None or eb is None or b <= 0 or eb <= 0:
            return math.inf
        return abs(math.log(b / eb))

    return int(min(entries, key=dist)["tile"])


def resolve(kernel: str, backend: str, shape: Dict[str, int],
            tile: Optional[int]) -> int:
    """Dispatch-time tile: the explicit override if given, else the table."""
    if tile is not None:
        return int(tile)
    return best_tile(kernel, backend, shape)
