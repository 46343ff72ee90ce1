"""Pallas TPU kernel: fused IVF inverted-list scan with a running top-k.

Queries probe p coarse cells; each cell's posting list lives in a tile-aligned
packed layout (`repro.index.ivf`), so the work per query is a sequence of
(block_rows, d) tiles of the packed database.  The probe path turns the CSR
offsets into a per-query *tile map* (q, T) of packed-tile indices (padded with
a dedicated all-invalid tile), and this kernel streams exactly those tiles
from HBM through VMEM via scalar-prefetch-driven block indexing — the same
revisiting pattern as `centroid_assign`, with the revisited output block
carrying a running per-query top-k instead of a single argmin.

HBM traffic per query is O(scanned_rows * d) — the point of IVF: only the
probed fraction of the database is ever touched.
"""
# autotune: exempt(ivf_scan_grouped): the block_rows tile shape is an
#   index-format constant chosen at pack time, and the group size G is a
#   recall/locality knob owned by the caller, not a dispatch-time tile.
#   (ivf_scan itself IS swept: its `tile` chunks the query axis — the
#   reference's for cache blocking, the kernel's under its VMEM budget;
#   bitwise-neutral — resolved from autotune_table.json.)
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.centroid_assign import _select_topk

# VMEM the whole-array query-axis blocks of one call may take, double-
# buffered (the (bq, d) queries and the two (bq, topk) outputs), leaving the
# streamed list tiles room under the 16 MiB scoped-VMEM limit of a TPU core.
QUERY_BYTES = 4 << 20
# SMEM the scalar-prefetched tile map of one call may take (a TPU core has
# 1 MiB of SMEM; the map is passed flat, as a 2-D SMEM array pads its rows
# to 128 words).
TILE_MAP_BYTES = 256 << 10


def _lanes(w: int) -> int:
    return -(-w // 128) * 128


def q_tile(nq: int, width: int, topk: int, T: int) -> int:
    """Largest 8-aligned query tile whose whole-array blocks — ``width`` f32
    lanes of query input per query (0 for none) and two (·, topk) outputs,
    double-buffered — fit ``QUERY_BYTES``, and whose T-tile map fits
    ``TILE_MAP_BYTES`` (never more than the 8-padded batch)."""
    per_q = 2 * 4 * ((_lanes(width) if width else 0) + 2 * _lanes(topk))
    fit = min(QUERY_BYTES // per_q, TILE_MAP_BYTES // (4 * T))
    return min(max(fit // 8 * 8, 8), -(-nq // 8) * 8)


def map_query_tiles(fn, bq: int, *arrays):
    """``fn(*arrays)`` over consecutive tiles of ``bq`` queries (the arrays
    share their leading query axis), one ``lax.map`` step per tile, so the
    kernel never holds more than ``bq`` queries in VMEM.  Queries are
    independent, so the tiling is bitwise-neutral; a ragged tail is padded
    with zero queries scanning tile 0 and sliced off."""
    nq = arrays[0].shape[0]
    nt = -(-nq // bq)
    if nt == 1:
        return fn(*arrays)
    pad = nt * bq - nq
    tiles = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
             .reshape(nt, bq, *a.shape[1:]) for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), tiles)
    return jax.tree.map(lambda o: o.reshape(nt * bq, *o.shape[2:])[:nq], out)


def tile_scores(q, v_ref, id_ref):
    """Partial distances ``||v||^2 - 2 q.v`` of queries q (g, d) against one
    packed tile, +inf at padding rows -> (part (g, bl), ids (1, bl))."""
    v = v_ref[...].astype(jnp.float32)          # (bl, d)
    ids = id_ref[...]                           # (1, bl) int32, -1 = padding
    dots = jax.lax.dot_general(
        q, v, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # (g, bl)
    vsq = jnp.sum(v * v, axis=-1)               # (bl,)
    part = vsq[None, :] - 2.0 * dots            # d2 minus ||q||^2
    return jnp.where(ids < 0, jnp.inf, part), ids


def running_topk(od_ref, oid_ref, rows, first, part, ids, topk: int):
    """Fold one tile's (part, ids) into the running top-k held in
    ``od_ref[rows]`` / ``oid_ref[rows]`` (overwritten on the first tile)."""
    @pl.when(first)
    def _init():
        d0, i0 = _select_topk(part, ids, topk)
        od_ref[rows, :] = d0
        oid_ref[rows, :] = i0

    @pl.when(jnp.logical_not(first))
    def _update():
        d = jnp.concatenate([od_ref[rows, :], part], axis=-1)
        i = jnp.concatenate([oid_ref[rows, :], ids], axis=-1)
        d1, i1 = _select_topk(d, i, topk)
        od_ref[rows, :] = d1
        oid_ref[rows, :] = i1


def _kernel(tile_map_ref, q_ref, v_ref, id_ref, oid_ref, od_ref, *,
            topk: int):
    i = pl.program_id(0)
    t = pl.program_id(1)
    row = pl.ds(i, 1)
    q = q_ref[row, :].astype(jnp.float32)       # (1, d)
    part, ids = tile_scores(q, v_ref, id_ref)   # (1, bl)
    running_topk(od_ref, oid_ref, row, t == 0, part, ids, topk)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "topk", "interpret", "raw",
                                    "bq"))
def ivf_scan(Q: jax.Array, vecs: jax.Array, pids: jax.Array,
             tile_map: jax.Array, *, block_rows: int, topk: int = 10,
             interpret: bool = False, raw: bool = False, bq: int = 0):
    """Scan each query's probed tiles of the packed database.

    Q: (q, d) queries; vecs: (n_pad, d) packed vectors (n_pad a multiple of
    block_rows); pids: (n_pad,) int32 original ids, -1 at padding rows;
    tile_map: (q, T) int32 packed-tile indices per query (repeats of an
    all-padding tile are harmless).

    Returns (ids (q, topk) int32 with -1 beyond the candidate count,
    d2 (q, topk) float32 ascending, +inf beyond the candidate count).
    ``raw=True`` skips the final ``+ ||q||^2`` / clamp and returns the
    kernel's partial distances (+inf at invalid slots) — mesh shards merge
    on these so cross-shard selection is bit-identical to a single scan.
    ``bq`` is the query tile (0 = the whole batch), rounded up to a multiple
    of 8 and capped by ``q_tile``'s VMEM and SMEM budgets; larger batches
    run one kernel call per tile (``map_query_tiles``).
    """
    nq, d = Q.shape
    assert vecs.shape[0] % block_rows == 0, (vecs.shape, block_rows)
    assert tile_map.shape[0] == nq
    T = tile_map.shape[1]
    bq = min(-(-(bq or nq) // 8) * 8, q_tile(nq, d, topk, T))

    def scan(q, tm):                            # one tile of queries
        whole = lambda shape: pl.BlockSpec(shape, lambda i, t, tm: (0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(q.shape[0], T),
            in_specs=[
                whole(q.shape),
                pl.BlockSpec((block_rows, d),
                             lambda i, t, tm: (tm[i * T + t], 0)),
                pl.BlockSpec((1, block_rows),
                             lambda i, t, tm: (0, tm[i * T + t])),
            ],
            out_specs=[whole((q.shape[0], topk))] * 2,
        )
        return pl.pallas_call(
            functools.partial(_kernel, topk=topk),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((q.shape[0], topk), jnp.int32),
                jax.ShapeDtypeStruct((q.shape[0], topk), jnp.float32),
            ],
            interpret=interpret,
        )(tm.reshape(-1), q, vecs, pids.astype(jnp.int32)[None, :])

    oid, od = map_query_tiles(scan, bq, Q, tile_map.astype(jnp.int32))
    if raw:
        return oid, jnp.where(oid < 0, jnp.inf, od)
    return _ref.finalize_d2(oid, od, Q)


def _grouped_kernel(union_ref, qg_ref, v_ref, id_ref, m_ref, oid_ref, od_ref,
                    *, topk: int):
    s = pl.program_id(1)
    qg = qg_ref[...].astype(jnp.float32)        # (G, d)
    part, ids = tile_scores(qg, v_ref, id_ref)  # (G, bl), (1, bl)
    # membership of union slot s: the group's (G, U) mask block reduced at
    # lane s (a (G, 1) block breaks the TPU's (8, 128) block tiling)
    m = m_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    probed = jnp.sum(jnp.where(lane == s, m, 0), axis=-1, keepdims=True)
    # a query only sees this tile's rows if it probed the tile; padding rows
    # and unprobed tiles become id=-1/inf so the select treats them as holes
    idsb = jnp.where((probed > 0) & (ids >= 0), ids, -1)
    part = jnp.where(idsb < 0, jnp.inf, part)
    running_topk(od_ref, oid_ref, slice(None), s == 0, part, idsb, topk)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "topk", "interpret",
                                    "raw"))
def ivf_scan_grouped(Qg: jax.Array, vecs: jax.Array, pids: jax.Array,
                     union_tiles: jax.Array, qmask: jax.Array, *,
                     block_rows: int, topk: int = 10,
                     interpret: bool = False, raw: bool = False):
    """Query-grouped scan: stream each probed tile once per query GROUP.

    The per-query grid re-fetches a hot list tile for every query that
    probes it; this grid batches G probe-local queries per group and walks
    the group's deduped union tile list instead, so a tile shared by the
    whole group is loaded once (and the trailing null-tile padding slots,
    sorted to be consecutive, are not re-fetched between steps).

    Qg: (ngroups * G, d) queries permuted into groups (`index.probe.
    build_group_map` produces the layout); union_tiles: (ngroups, U) int32
    deduped tile indices (null-tile padded); qmask: (ngroups * G, U) int32
    nonzero where the query probed that union slot.

    Returns (ids, d2) of shape (ngroups * G, topk) in the grouped order —
    same output convention as `ivf_scan` (``raw=True`` returns partial
    distances, +inf at invalid slots, for cross-shard merges).
    """
    nqg, d = Qg.shape
    ngroups, U = union_tiles.shape
    assert nqg % ngroups == 0, (nqg, ngroups)
    G = nqg // ngroups
    assert qmask.shape == (nqg, U), (qmask.shape, nqg, U)
    assert vecs.shape[0] % block_rows == 0, (vecs.shape, block_rows)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ngroups, U),
        in_specs=[
            pl.BlockSpec((G, d), lambda g, s, ut: (g, 0)),
            pl.BlockSpec((block_rows, d), lambda g, s, ut: (ut[g, s], 0)),
            pl.BlockSpec((1, block_rows), lambda g, s, ut: (0, ut[g, s])),
            pl.BlockSpec((G, U), lambda g, s, ut: (g, 0)),
        ],
        out_specs=[
            pl.BlockSpec((G, topk), lambda g, s, ut: (g, 0)),
            pl.BlockSpec((G, topk), lambda g, s, ut: (g, 0)),
        ],
    )
    oid, od = pl.pallas_call(
        functools.partial(_grouped_kernel, topk=topk),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nqg, topk), jnp.int32),
            jax.ShapeDtypeStruct((nqg, topk), jnp.float32),
        ],
        interpret=interpret,
    )(union_tiles.astype(jnp.int32), Qg, vecs,
      pids.astype(jnp.int32)[None, :], qmask.astype(jnp.int32))
    if raw:
        return oid, jnp.where(oid < 0, jnp.inf, od)
    return _ref.finalize_d2(oid, od, Qg)
