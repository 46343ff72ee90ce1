"""Batched IVF query path: coarse top-p probe -> fused inverted-list scan.

The recall/latency knob is `nprobe` (cluster-closure-style multi-probe): each
query scans the `nprobe` nearest cells' lists instead of just the nearest,
trading a linear increase in scanned rows for recall.

Two scan layouts share the same probe front-end:

  * per-query (default): one grid row per query streams that query's probed
    tiles — simplest, and the layout the mesh-sharded path
    (`core.distributed.ShardedIvf`) runs per shard;
  * query-grouped (`qgroup=G`): queries are permuted into probe-locality
    groups of G and each group walks its deduped union tile list, so a list
    tile probed by several queries of the group is streamed from HBM once
    instead of once per query (`build_group_map` + `kops.ivf_scan_grouped`).
    Returns the same neighbour ids as per-query whenever distances are
    distinct; candidates at EXACTLY equal distance resolve in ascending
    tile order here vs probe order there.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.index.ivf import IvfIndex
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.obs.timing import span


@functools.partial(jax.jit, static_argnames=("max_tiles", "block_rows",
                                             "null_tile"))
def build_tile_map(cids: jax.Array, starts: jax.Array, caps: jax.Array,
                   *, max_tiles: int, block_rows: int, null_tile: int):
    """Probed cells -> per-query packed-tile indices.

    cids: (q, p) cell ids; returns (q, p * max_tiles) int32, with slots past
    a list's end pointing at the all-hole null tile.
    """
    first = starts[cids] // block_rows                     # (q, p)
    ntiles = caps[cids] // block_rows                      # (q, p)
    ar = jnp.arange(max_tiles, dtype=jnp.int32)
    tiles = first[..., None] + ar                          # (q, p, max_tiles)
    tiles = jnp.where(ar < ntiles[..., None], tiles, null_tile)
    q = cids.shape[0]
    return tiles.reshape(q, -1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("group", "null_tile"))
def build_group_map(tile_map: jax.Array, *, group: int, null_tile: int):
    """Per-query tile map -> probe-locality query groups with union tiles.

    Sorts queries by their first probed tile (nearest cell), takes groups of
    `group` consecutive queries, and dedupes each group's probed tiles into
    one sorted union list (real tiles ascending, null-tile padding trailing,
    so repeated padding slots cost no re-fetch in the grouped kernel).

    Returns (order (ngroups*group,) int32 — original query index per grouped
    row, q (out of range, so scatters drop it — negative sentinels would
    wrap) at ragged-tail padding rows; union (ngroups, group*T) int32;
    qmask (ngroups*group, group*T) int32 membership, 0 on padding rows).
    """
    q, T = tile_map.shape
    G = group
    npad = (-q) % G
    order = jnp.argsort(tile_map[:, 0], stable=True).astype(jnp.int32)
    valid = jnp.ones((q,), bool)
    if npad:
        order = jnp.concatenate(
            [order, jnp.full((npad,), q, jnp.int32)])
        valid = jnp.concatenate([valid, jnp.zeros((npad,), bool)])
    ngroups = (q + npad) // G
    U = G * T

    tq = tile_map[jnp.clip(order, 0, q - 1)]               # (qg, T)
    tq = jnp.where(valid[:, None], tq, null_tile)          # padding rows
    tqg = tq.reshape(ngroups, G, T)

    # dedupe each group's tiles: null sorts (and dupes get re-marked) last
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    s = jnp.sort(jnp.where(tqg.reshape(ngroups, U) == null_tile, big,
                           tqg.reshape(ngroups, U)), axis=-1)
    dup = jnp.concatenate([jnp.zeros_like(s[:, :1], bool),
                           s[:, 1:] == s[:, :-1]], axis=-1)
    s = jnp.sort(jnp.where(dup, big, s), axis=-1)
    union = jnp.where(s == big, null_tile, s).astype(jnp.int32)

    # membership by searchsorted into the sorted union (O(U log U) per group,
    # replacing the old O(G * U * T) pairwise compare): every REAL tile of
    # the group appears in its own union by construction, so the left-insert
    # slot IS its (unique, deduped) union position — scatter a 1 there.
    # Null-tile entries never join the mask, exactly as before.
    tq_flat = tqg.reshape(ngroups, U)
    slot = jax.vmap(jnp.searchsorted)(union, tq_flat)      # (ngroups, U)
    real = (tq_flat != null_tile).astype(jnp.int32)
    g_ix = jnp.arange(ngroups, dtype=jnp.int32)[:, None]
    m_ix = (jnp.arange(U, dtype=jnp.int32) // T)[None, :]  # member per slot
    memb = jnp.zeros((ngroups, G, U), jnp.int32)
    memb = memb.at[g_ix, m_ix, jnp.clip(slot, 0, U - 1)].max(real)
    return order, union, memb.reshape(ngroups * G, U)


def _no_candidates(q: int, topk: int):
    """The empty-index result: zero-width scans can't run (and a 0-tile grid
    would return unwritten kernel buffers), so short-circuit to -1/+inf."""
    return (jnp.full((q, topk), -1, jnp.int32),
            jnp.full((q, topk), jnp.inf, jnp.float32))


@functools.partial(jax.jit, static_argnames=("topk",))
def exact_rerank(Q: jax.Array, vecs: jax.Array, pids: jax.Array,
                 pos: jax.Array, *, topk: int):
    """Decode-free exact re-score of ADC survivors (the rerank tail).

    Q: (q, d); vecs: (n_pad, d) the residual-kept f32 originals; pids:
    (n_pad,) int32; pos: (q, R) packed-row positions from `ivf_scan_adc`
    (-1 = empty).  Gathers the ORIGINAL rows by position — no decode — and
    re-scores them with the f32 scan's exact arithmetic, selecting topk with
    the same stable tie-break.  Returns (ids (q, topk), raw partials
    (``||v||² - 2 q.v``, +inf at empty)) for `finalize_d2` — so reranked
    distances are exact, and recall is honest against brute force.

    Jitted standalone for the same cross-topology fusion-rounding reason as
    `probe_centroids`: the sharded path runs this per shard inside its one
    trace, and the merged partials must round identically here.
    """
    qf = Q.astype(jnp.float32)
    safe = jnp.clip(pos, 0)
    cv = vecs[safe].astype(jnp.float32)                    # (q, R, d)
    vsq = jnp.sum(cv * cv, axis=-1)                        # (q, R)
    dots = jnp.einsum("qd,qrd->qr", qf, cv, precision=kref.HIGHEST)
    cids = jnp.where(pos < 0, -1, pids.astype(jnp.int32)[safe])
    part = jnp.where(cids < 0, jnp.inf, vsq - 2.0 * dots)
    d, ids = kref.stable_topk(part, cids, topk)
    return ids, jnp.where(ids < 0, jnp.inf, d)


@jax.jit
def _finalize(ids: jax.Array, part: jax.Array, Q: jax.Array):
    """`finalize_d2` under jit — the codec exit paths apply the final
    monotone transform inside a trace like every other scan exit (see
    `probe_centroids` on why eager op-by-op rounds differently)."""
    return kref.finalize_d2(ids, part, Q)


def _rerank_depth(topk: int, rerank: Optional[int]) -> int:
    """Candidate depth of the ADC pass: 0 disables the rerank tail."""
    if rerank is None:
        return 4 * topk
    if rerank == 0:
        return 0
    return max(rerank, topk)


def _search_grouped(index: IvfIndex, Q: jax.Array, tm: jax.Array, *,
                    topk: int, qgroup: int, force: Optional[str]):
    order, union, qmask = build_group_map(tm, group=qgroup,
                                          null_tile=index.null_tile)
    Qg = Q[jnp.clip(order, 0, Q.shape[0] - 1)]
    gi, gd = kops.ivf_scan_grouped(Qg, index.vecs, index.ids, union, qmask,
                                   block_rows=index.block_rows, topk=topk,
                                   force=force)
    # scatter back to the original query order; out-of-range padding drops
    ids = jnp.full((Q.shape[0], topk), -1, jnp.int32)
    d2 = jnp.full((Q.shape[0], topk), jnp.inf, jnp.float32)
    return (ids.at[order].set(gi, mode="drop"),
            d2.at[order].set(gd, mode="drop"))


def search(index: IvfIndex, Q: jax.Array, *, topk: int = 10,
           nprobe: int = 8, force: Optional[str] = None,
           qgroup: Optional[int] = None, codec: str = "f32",
           rerank: Optional[int] = None):
    """Top-k search. Q: (q, d) -> (ids (q, topk) int32, d2 (q, topk) f32).

    ids are the original vector ids (-1 past the candidate count); d2 is
    exact squared L2 to the returned vectors.  `force` follows the kernel
    dispatch convention (None | 'pallas' | 'ref' | 'interpret').  `nprobe`
    clamps to the cell count (probing more cells than exist is exhaustive).
    `qgroup=G` runs the query-grouped scan layout (see module docstring).

    `codec="pq"|"int8"` scans the attached compressed payload through
    `ivf_scan_adc` instead of the f32 slab, then exact-reranks the top
    `rerank` ADC candidates against the f32 originals (default 4 * topk;
    `rerank=0` disables the tail and returns distances to the codec
    reconstructions).  With rerank on, returned d2 is exact squared L2
    again — the codec only decides WHICH candidates survive to the tail.

    Each call runs under one ``repro.search`` span (``obs.span``: host
    dispatch, no sync) that counts ``grid_rows``, the rows the scan's grid
    streams, and ``grid_flops``, the operations it spends on them.
    """
    with span("repro.search") as sp:
        assert nprobe >= 1, nprobe
        nprobe = min(nprobe, index.k)
        if index.max_list_tiles == 0:     # every list empty: nothing to scan
            return _no_candidates(Q.shape[0], topk)
        cids, _ = kops.probe_centroids(Q, index.centroids, nprobe,
                                       force=force)
        tm = build_tile_map(cids, index.starts, index.caps,
                            max_tiles=index.max_list_tiles,
                            block_rows=index.block_rows,
                            null_tile=index.null_tile)
        # rows the scan's grid streams: every query walks nprobe lists of
        # the longest list's tiles (a group of qgroup queries walks their
        # union, qgroup * nprobe lists' worth); each row scored against a
        # query costs a multiply and an add per code column
        q = Q.shape[0]
        per_q = nprobe * index.max_list_tiles * index.block_rows
        if codec != "f32":
            assert qgroup is None, "codec scan is per-query only (no qgroup)"
            assert index.codec is not None and index.codec.kind == codec, \
                (codec, index.codec_kind)
            from repro.index import quantize as _q

            sp.count(grid_rows=q * per_q, grid_flops=2 * q * per_q *
                     _q.code_width(index.codec, index.dim))
            depth = _rerank_depth(topk, rerank)
            lut, qc = _q.build_lut(index.codec, Q)
            ids, pos, part = kops.ivf_scan_adc(
                lut, qc, index.vnorm, index.codes, index.ids, tm,
                block_rows=index.block_rows, topk=(depth or topk),
                force=force)
            if not depth:
                return _finalize(ids, part, Q)
            rid, rpart = exact_rerank(Q, index.vecs, index.ids, pos,
                                      topk=topk)
            return _finalize(rid, rpart, Q)
        if qgroup is not None and qgroup > 1:
            qg = -(-q // qgroup) * qgroup
            sp.count(grid_rows=qg * per_q,
                     grid_flops=2 * qg * per_q * qgroup * index.dim)
            return _search_grouped(index, Q, tm, topk=topk, qgroup=qgroup,
                                   force=force)
        sp.count(grid_rows=q * per_q, grid_flops=2 * q * per_q * index.dim)
        return kops.ivf_scan(Q, index.vecs, index.ids, tm,
                             block_rows=index.block_rows, topk=topk,
                             force=force)


def merge_shard_topk(ids: jax.Array, part: jax.Array, topk: int):
    """Merge per-shard local top-k lists into the global top-k.

    ids/part: (R, q, t) all-gathered shard results, `part` the RAW partial
    distances (`ivf_scan(..., raw=True)`, +inf at invalid slots).  Packed
    rows live on exactly one shard, so no id-dedupe is needed; the selection
    is `kernels.ref.stable_topk` — the same first-minimum tie-break the scan
    kernels use, over candidates in shard order.  Returns (ids (q, topk),
    part (q, topk)) still in raw form.
    """
    R, q, t = ids.shape
    ent_i = ids.transpose(1, 0, 2).reshape(q, R * t)
    ent_d = part.transpose(1, 0, 2).reshape(q, R * t)
    d, i = kref.stable_topk(ent_d, ent_i, topk)
    return i, d


def merge_probe_cells(gd: jax.Array, gi: jax.Array, p: int):
    """Merge per-shard coarse-probe partials into the global top-p cells.

    gd/gi: (L, q) all-gathered per-shard top-min(p, k_slab) RAW probe
    partials (``||c||² - 2 q·c``, +inf at slab holes) and global cell ids,
    L = R * p_loc in shard-major order.  Stays in the transposed (L, q)
    layout end-to-end — the merged working set never materialises a
    replicated q-leading 2-D operand wider than p — and selects with the
    same iterative first-minimum the scan kernels use (``jnp.argmin``
    returns the first minimum), so for distinct partials the merged probe
    order is identical to the single-device ``probe_centroids`` ranking.
    Returns cids (q, p) int32.
    """
    q = gd.shape[1]
    col = jnp.arange(q)
    outs = []
    for _ in range(p):
        j = jnp.argmin(gd, axis=0)              # (q,) first-min over L
        outs.append(gi[j, col])
        gd = gd.at[j, col].set(jnp.inf)
    return jnp.stack(outs, axis=1)


def scan_fraction(index: IvfIndex, Q: jax.Array, *, nprobe: int = 8,
                  force: Optional[str] = None) -> float:
    """Mean fraction of packed database rows streamed per query."""
    nprobe = min(nprobe, index.k)
    cids, _ = kops.probe_centroids(Q, index.centroids, nprobe, force=force)
    scanned = jnp.sum(index.caps[cids], axis=-1)           # (q,)
    # lint: boundary(host diagnostic, not on the serving path)
    return float(jnp.mean(scanned) / max(index.capacity_rows, 1))


def exhaustive_search(index: IvfIndex, Q: jax.Array, *, topk: int = 10,
                      force: Optional[str] = None):
    """Ground-truth scan of every packed tile — for recall eval.

    Enumerates the packed buffer's tiles directly instead of routing through
    ``nprobe = k`` (which paid an O(q*k) probe plus a k-wide top-p selection
    just to name every cell, and whose trace grew with k).  The scan itself
    is the same fused kernel, so this also pins the scan's padding handling
    against brute force (tests/test_ivf.py).
    """
    ntiles = index.capacity_rows // index.block_rows
    if ntiles == 0:                       # every list empty: nothing to scan
        return _no_candidates(Q.shape[0], topk)
    tm = jnp.broadcast_to(jnp.arange(ntiles, dtype=jnp.int32),
                          (Q.shape[0], ntiles))
    return kops.ivf_scan(Q, index.vecs, index.ids, tm,
                         block_rows=index.block_rows, topk=topk, force=force)
