"""Jit'd public wrappers for the Pallas kernels.

On TPU the Pallas kernels run compiled; on the CPU the hot path dispatches
to the pure-jnp references (XLA:CPU).  ``force='interpret' | 'ref' |
'pallas'`` overrides the dispatch for tests, which run the Pallas bodies in
interpret mode against the same references.

Every dispatcher runs under ``obs.timing.kernel_scope`` — a
``jax.named_scope("repro.kernels.<name>")`` that tags the emitted ops in HLO
metadata and profiler traces, so a ``jax.profiler`` capture of any enclosing
trace attributes time per kernel with no runtime cost.
"""
from __future__ import annotations

import jax

from repro.kernels import autotune as _at
from repro.kernels import centroid_assign as _ca
from repro.kernels import gather_score as _gs
from repro.kernels import ivf_scan as _ivf
from repro.kernels import ivf_scan_adc as _adc
from repro.kernels import pairwise_topk as _pt
from repro.kernels import ref as _ref
from repro.kernels import refine_merge as _rm
from repro.obs.timing import kernel_scope


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tile(kernel: str, shape: dict, tile: int | None) -> int:
    """Row-tile for this call: explicit ``tile=`` override, else the
    checked-in autotune table's rows for this backend (see
    ``kernels.autotune``).  0 is the kernel's own default: the whole batch
    on the CPU reference, the VMEM-budget tile (``row_tile``) of the TPU
    kernel.  Resolved at trace time — shapes are static under jit, so this
    is free at runtime."""
    return _at.resolve(kernel, jax.default_backend(), shape, tile)


def pairwise_sq(Xb: jax.Array, *, force: str | None = None,
                tile: int | None = None) -> jax.Array:
    """Batched (B, m, d) -> (B, m, m) squared L2. force: None|'pallas'|'ref'|'interpret'."""
    with kernel_scope("pairwise_sq"):
        B, m, d = Xb.shape
        t = _tile("pairwise_sq", {"B": B, "m": m, "d": d}, tile)
        if force == "pallas" or (force is None and _on_tpu()):
            return _pt.pairwise_sq(Xb, bB=t)
        if force == "interpret":
            return _pt.pairwise_sq(Xb, bB=t, interpret=True)
        return _ref.pairwise_sq(Xb, tile=t)


def assign_centroids(X: jax.Array, C: jax.Array, *, force: str | None = None,
                     bn: int = 1024, bk: int = 512):
    """(n, d) x (k, d) -> nearest-centroid (assign, d2); pads to tile shapes."""
    with kernel_scope("assign_centroids"):
        if force == "ref" or (force is None and not _on_tpu()):
            return _ref.assign_centroids(X, C)
        return _ca.assign_centroids_padded(X, C, bn=bn, bk=bk,
                                           interpret=(force == "interpret"))


def probe_centroids(X: jax.Array, C: jax.Array, p: int, *,
                    force: str | None = None, bn: int = 1024, bk: int = 512):
    """(n, d) x (k, d) -> top-p nearest centroids (ids, d2); pads to tiles."""
    with kernel_scope("probe_centroids"):
        if force == "ref" or (force is None and not _on_tpu()):
            return _ref.probe_centroids(X, C, p)
        return _ca.probe_centroids_padded(X, C, p, bn=bn, bk=bk,
                                          interpret=(force == "interpret"))


def gather_score(x: jax.Array, u: jax.Array, cand: jax.Array, D: jax.Array,
                 cnt: jax.Array, *, mode: str = "bkm",
                 force: str | None = None,
                 tile: int | None = None) -> jax.Array:
    """(B, d) x (B, C) candidate ids -> (B, C) move scores, gather fused.

    ``tile`` is the row-tile size (None = autotune table; 0 = whole batch);
    every tile produces bitwise-identical scores, so it is purely a
    performance knob.
    """
    with kernel_scope("gather_score"):
        B, d = x.shape
        t = _tile("gather_score", {"B": B, "C": cand.shape[1], "d": d}, tile)
        if force == "ref" or (force is None and not _on_tpu()):
            return _ref.gather_score(x, u, cand, D, cnt, mode=mode, tile=t)
        return _gs.gather_score(x, u, cand, D, cnt, mode=mode, bB=t,
                                interpret=(force == "interpret"))


def refine_merge(x: jax.Array, rows: jax.Array, cand_ids: jax.Array,
                 old_ids: jax.Array, old_d: jax.Array, Xsrc: jax.Array, *,
                 force: str | None = None, tile: int | None = None):
    """(B, C) candidate rows merged into (B, κ) top-κ lists, gather fused.

    ``tile`` as in ``gather_score`` — a bitwise-neutral performance knob.
    """
    with kernel_scope("refine_merge"):
        B, d = x.shape
        t = _tile("refine_merge",
                  {"B": B, "C": rows.shape[1], "d": d,
                   "kappa": old_ids.shape[1]}, tile)
        if force == "ref" or (force is None and not _on_tpu()):
            return _ref.refine_merge(x, rows, cand_ids, old_ids, old_d, Xsrc,
                                     tile=t)
        return _rm.refine_merge(x, rows, cand_ids, old_ids, old_d, Xsrc,
                                bB=t, interpret=(force == "interpret"))


def ivf_scan(Q: jax.Array, vecs: jax.Array, pids: jax.Array,
             tile_map: jax.Array, *, block_rows: int, topk: int = 10,
             force: str | None = None, raw: bool = False,
             tile: int | None = None):
    """Per-query scan of probed packed-list tiles -> (ids, d2) top-k.

    ``tile`` chunks the query axis, bitwise-neutral: the reference's for
    cache blocking (see ``ref.ivf_scan``), the Pallas kernel's to cap the
    queries its VMEM holds (0 = its VMEM budget, ``ivf_scan.q_tile``).
    """
    with kernel_scope("ivf_scan"):
        nq, d = Q.shape
        t = _tile("ivf_scan",
                  {"q": nq, "rows": tile_map.shape[1] * block_rows, "d": d,
                   "topk": topk}, tile)
        if force == "ref" or (force is None and not _on_tpu()):
            return _ref.ivf_scan(Q, vecs, pids, tile_map,
                                 block_rows=block_rows, topk=topk, raw=raw,
                                 tile=t)
        return _ivf.ivf_scan(Q, vecs, pids, tile_map, block_rows=block_rows,
                             topk=topk, interpret=(force == "interpret"),
                             raw=raw, bq=t)


def ivf_scan_adc(lut: jax.Array, qconst: jax.Array, vnorm: jax.Array,
                 codes: jax.Array, pids: jax.Array, tile_map: jax.Array, *,
                 block_rows: int, topk: int = 10, force: str | None = None,
                 tile: int | None = None):
    """Asymmetric-distance scan of compressed lists via a per-query LUT.

    (lut (q, M, W), qconst (q,)) from ``index.quantize.build_lut`` (W=256
    pq, W=1 int8); codes/vnorm are the packed u8 slab and reconstruction
    norms.  Returns (ids, packed-row pos, RAW partials) — callers finalize
    or exact-rerank.  ``tile`` chunks the query axis as in ``ivf_scan``
    (bitwise-neutral); the Pallas grid is per-query and keeps the (1, M, W)
    LUT block VMEM-resident.
    """
    with kernel_scope("ivf_scan_adc"):
        nq, m, w = lut.shape
        t = _tile("ivf_scan_adc",
                  {"q": nq, "rows": tile_map.shape[1] * block_rows, "m": m,
                   "w": w, "topk": topk}, tile)
        if force == "ref" or (force is None and not _on_tpu()):
            return _ref.ivf_scan_adc(lut, qconst, vnorm, codes, pids,
                                     tile_map, block_rows=block_rows,
                                     topk=topk, tile=t)
        return _adc.ivf_scan_adc(lut, qconst, vnorm, codes, pids, tile_map,
                                 block_rows=block_rows, topk=topk,
                                 interpret=(force == "interpret"), bq=t)


def ivf_scan_grouped(Qg: jax.Array, vecs: jax.Array, pids: jax.Array,
                     union_tiles: jax.Array, qmask: jax.Array, *,
                     block_rows: int, topk: int = 10,
                     force: str | None = None, raw: bool = False):
    """Query-grouped list scan: each union tile streamed once per group.

    ``raw=True`` returns partial distances (``||v||² − 2q·v``, +inf at
    invalid slots) for cross-shard merges, like ``ivf_scan``.
    """
    with kernel_scope("ivf_scan_grouped"):
        if force == "ref" or (force is None and not _on_tpu()):
            return _ref.ivf_scan_grouped(Qg, vecs, pids, union_tiles, qmask,
                                         block_rows=block_rows, topk=topk,
                                         raw=raw)
        return _ivf.ivf_scan_grouped(Qg, vecs, pids, union_tiles, qmask,
                                     block_rows=block_rows, topk=topk,
                                     interpret=(force == "interpret"),
                                     raw=raw)
