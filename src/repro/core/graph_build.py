"""Device-resident, sharded KNN-graph construction: one GraphBuilder core.

Both of this repo's graph builders are the same loop, round after round:

  candidates  which rows might be one of my κ nearest neighbours — my
              co-members in an equal-size 2M-tree partition (paper Alg. 3,
              ``source='partition'``), or my neighbours' neighbours plus
              reverse edges (NN-Descent, Dong et al. WWW 2011 — the paper's
              "KGraph" baseline, ``source='descent'``);
  distances   exact squared L2 from my vector to each candidate;
  merge       fold the candidates into my sorted, id-deduped top-κ list.

This module implements that refinement step ONCE (``_refine_rows``, backed
by the fused ``kernels.refine_merge`` Pallas kernel) and parameterises the
candidate source, mirroring the clustering engine's candidate→score→move
architecture.  The entire tau-round loop — the level-scanned
``two_means_scan`` bisection, the graph-guided ``engine`` pass (the paper's
"intertwined evolving" step), ``members_table`` and the per-row refinement —
runs inside ONE trace per build (a ``lax.scan`` over rounds), so a build is
one dispatch and one host sync instead of 3-4 jitted calls per tau round.

Topology follows the ``ShardedEngine`` conventions (``core.distributed``):
rows and their graph rows are sharded over the mesh's data axes and every
merge is a local update of the owning shard's rows.  X is all-gathered ONCE
per build (candidate vectors may live on any shard, so candidate distances
are computed locally against the replicated copy).  The 2M tree is the
genuinely distributed ``two_means_dist`` bisection: each level psums
per-shard (256, k)-digit projection histograms and splits at the weighted
median, so rows stay sharded and the tree state is O(k) scalars per shard —
no ``lax.sort`` over a replicated (n_pad,) array survives.  The member
table is likewise shard-local (``members_table_local``): each shard tables
its OWN rows' cluster slots plus a deterministic spill list, and the round
exchanges only the transposed (cap_loc, k) slices and (spill,) lists.  The
guided engine pass runs sharded through ``engine.sharded_epoch_body`` (one
assignment all-gather per round).  A sharded build therefore performs O(1)
host syncs (transfer-guard-enforced) and matches the single-device build
bit-exactly when the single-device config emulates the mesh's R-way visit
order (``GraphBuildConfig.shards``), exactly like the engine's
topology-parity contract.

Padding: the partition source pads n up to ``k0 * xi`` with phantom copies
of random rows.  Phantom rows participate as candidate *providers* (mapped
to their real id and deduped) and maintain their own throwaway lists, which
keeps every merge a conflict-free per-row update; rows beyond a cluster's
fixed capacity are absent from the member table for that round (counted in
``BuildDiagnostics.overflow``) but still refine their own list against the
members that are present, and the first ``GraphBuildConfig.spill`` overflow
rows per shard are re-offered to every row as extra candidates (the
deterministic spill list), so capacity pressure degrades recall gracefully.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.knn_graph import KnnGraph, members_table_local, merge_topk
from repro.core.two_means import _seg_sum_T, _TreeTopo, two_means_dist
from repro.kernels import ops as kops
from repro.obs import telemetry as obs_tel
from repro.obs.timing import layer_scope


# beyond this list width the sort-based merge_topk beats the fused kernel's
# O(κ(κ+C)) unrolled selection merge (see _refine_rows)
_WIDE_KAPPA = 64


class BuildDiagnostics(NamedTuple):
    """Per-round observability of a graph build (satellite of Alg. 3).

    overflow: (tau,) int32 — members beyond the fixed member-table capacity
    (``cap_factor * xi``) this round; they were not offered as candidates.
    guided_moves: (tau,) int32 — moves accepted by the graph-guided engine
    pass (0 for ``source='descent'`` or ``guided=False``).
    telemetry: per-round ``obs.telemetry.Telemetry`` (tau rows) when the
    build ran with ``GraphBuildConfig(telemetry=True)`` — the same two
    counters as named slots plus ``graph_updates`` (neighbour-list entries
    changed per round) and ``graph_mean_dist`` (mean finite neighbour
    distance); None otherwise.  Accumulated inside the build's round scan,
    so it arrives in the build's one host sync.
    """

    overflow: jax.Array
    guided_moves: jax.Array
    telemetry: Optional[obs_tel.Telemetry] = None


class GraphBuildConfig(NamedTuple):
    """Static knobs of a graph build (hashable: one trace per config)."""

    kappa: int = 16
    source: str = "partition"   # 'partition' (Alg. 3) | 'descent' (KGraph)
    xi: int = 64                # partition: target cluster size (power of 2)
    tau: int = 8                # rounds (NN-Descent iterations for descent)
    cap_factor: int = 2         # member-table capacity = cap_factor * xi
    bkm_batch: int = 1024       # guided pass batch size (per shard)
    guided: bool = True         # partition: run the intertwined engine pass
    sample: int = 0             # descent: candidate half-width (0 -> 2κ)
    chunk: int = 1024           # refine row-chunk (bounds the ref-path gather)
    shards: int = 1             # single-device emulation of an R-way order
    force: Optional[str] = None  # kernel dispatch override (None|'ref'|...)
    random_init: bool = True    # seed lists with κ random candidates (the
    #                             KNN builders' random init; closure k-means
    #                             turns it off to keep pure leaf-mate lists)
    telemetry: bool = False     # per-round Telemetry in BuildDiagnostics
    spill: int = 8              # per-shard deterministic overflow spill width


def _next_pow2(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p


def _plan(n: int, cfg: GraphBuildConfig) -> Tuple[int, int]:
    """(k0, n_pad) of the padded partition layout (descent never pads).

    Only the cluster COUNT must be a power of two (the 2M tree bisects);
    the cluster size xi is free — n_pad = k0 * xi always divides k0, which
    is what ``two_means_scan`` needs.  Power-of-two xi still gives the best
    TPU tile alignment for the refine step.
    """
    if cfg.source != "partition":
        return 1, n
    assert cfg.xi >= 1, cfg.xi
    k0 = _next_pow2(max((n + cfg.xi - 1) // cfg.xi, 1))
    return k0, k0 * cfg.xi


def _random_ids(key: jax.Array, own_real: jax.Array, n: int,
                width: int) -> jax.Array:
    """(rows, width) random real ids != own_real (all -1 when n == 1)."""
    rows = own_real.shape[0]
    if n <= 1:
        return jnp.full((rows, width), -1, jnp.int32)
    r = jax.random.randint(key, (rows, width), 0, n - 1, dtype=jnp.int32)
    return jnp.where(r >= own_real[:, None], r + 1, r)


def _refine_rows(x_own, rows, cand_ids, g_ids, g_d, Xsrc, chunk, force):
    """The shared refinement step, chunked over rows.

    Per row: exact distances to its C candidates (vectors gathered from the
    replicated Xsrc by padded-row index) merged into its current top-κ list
    — one ``kernels.refine_merge`` call per row chunk, purely local to the
    row's owner in the sharded topology.
    """
    B = x_own.shape[0]
    kappa = g_ids.shape[1]
    chunk = max(1, min(chunk, B))
    nb = -(-B // chunk)
    Bp = nb * chunk
    with layer_scope("graph", "candidates"):
        if Bp != B:
            # pad to a chunk multiple with clamped copies; extras are discarded
            idx = jnp.minimum(jnp.arange(Bp, dtype=jnp.int32), B - 1)
            x_own, rows, cand_ids, g_ids, g_d = (
                x_own[idx], rows[idx], cand_ids[idx], g_ids[idx], g_d[idx])

        if kappa > _WIDE_KAPPA:
            # wide lists (e.g. closure's trees*(leaf-1)): the fused kernel's
            # unrolled selection merge is O(κ(κ+C)) per row — the
            # three-argsort merge_topk wins past ~64; distances stay per-row
            # exact, so the single<->sharded bitwise parity is
            # chunk-invariant as before
            def body(args):
                xo, rw, ci, gi, gd = args
                Y = Xsrc[rw].astype(jnp.float32)
                cd = jnp.sum((Y - xo.astype(jnp.float32)[:, None, :]) ** 2, -1)
                cd = jnp.where(ci < 0, jnp.inf, cd)
                return merge_topk(gi, gd, ci, cd, kappa)
        else:
            def body(args):
                xo, rw, ci, gi, gd = args
                return kops.refine_merge(xo, rw, ci, gi, gd, Xsrc, force=force)

        if nb > 1:
            C = rows.shape[1]
            ids, d = jax.lax.map(body, (
                x_own.reshape(nb, chunk, -1), rows.reshape(nb, chunk, C),
                cand_ids.reshape(nb, chunk, C),
                g_ids.reshape(nb, chunk, kappa),
                g_d.reshape(nb, chunk, kappa)))
            ids, d = ids.reshape(Bp, kappa), d.reshape(Bp, kappa)
        else:
            ids, d = body((x_own, rows, cand_ids, g_ids, g_d))
        return ids[:B], d[:B]


def _guided_stats(X, assign, k0, topo: _TreeTopo):
    """Guided-pass cluster stats, both topologies: transposed (d, k0)
    composite sums — per-shard O(B * d) segment sums (``_seg_sum_T``),
    combined in FIXED shard order (all-gather + ordered sum — bit-exact
    across topologies, unlike an unordered float psum) — plus
    order-invariant int counts."""
    Xf = X.astype(jnp.float32)
    D_T = topo.fsum_blocks(
        lambda xb, ab: _seg_sum_T(xb, ab, k0), Xf, assign)
    cnt = topo.isum(jax.ops.segment_sum(jnp.ones(assign.shape, jnp.int32),
                                        assign, num_segments=k0))
    return D_T, cnt.astype(jnp.float32)


def _partition_round(X_full, X_loc, row_ids, real_id, own_real, g_ids, g_d,
                     key, t, *, cfg, k0, comm, data_axes):
    """One Alg. 3 round: distributed 2M-tree partition (+ guided pass) ->
    shard-local member table -> per-row refinement.

    Rows stay sharded end to end: the tree is the histogram/radix-median
    ``two_means_dist`` (O(k0) replicated state), the guided pass runs with
    cluster-sharded centroid stats, and every shard contributes its own
    (cap/R, k0) member-table slice.  The single-device emulation
    (``cfg.shards=R``) blocks its rows the same way, so builds stay
    bit-exact across topologies.
    """
    k1, k2 = jax.random.split(key)
    B = X_loc.shape[0]
    R = X_full.shape[0] // B if comm is not None else cfg.shards
    topo = _TreeTopo(R, data_axes if comm is not None else None)
    assign = two_means_dist(X_loc, row_ids, k0, k1, shards=R,
                            data_axes=topo.axes)
    moves = jnp.zeros((), jnp.int32)
    if cfg.guided:
        # the intertwined evolving step: one graph-guided engine pass.
        # Neighbour ids are real ids (< n), which are also valid padded rows.
        # Round 0 keeps the pure tree partition (the graph is still near
        # random): BOTH topologies now skip the pass outright via lax.cond
        # — the replicated round index selects the same branch on every
        # shard, so the collective schedule stays SPMD-consistent and the
        # round-0 "run + select-discard" phantom pass is gone.
        source = engine.graph_source(g_ids)
        ecfg = engine.EngineConfig(
            batch_size=cfg.bkm_batch, sparse_updates=True,
            shards=cfg.shards if comm is None else 1, force=cfg.force)
        if comm is None:
            def _guided(a):
                D_T, cnt = _guided_stats(X_loc, a, k0, topo)
                st = engine.BKMState(a.astype(jnp.int32), D_T.T, cnt,
                                     jnp.zeros((), jnp.int32))
                st = engine.epoch_inline(X_full, st, source, k2, ecfg)
                return st.assign, st.moves
        else:
            k0_loc = k0 // R
            coff = (row_ids[0] // B) * k0_loc

            def _guided(a):
                D_T, cnt = _guided_stats(X_loc, a, k0, topo)
                D_loc = jax.lax.dynamic_slice(
                    D_T, (0, coff), (D_T.shape[0], k0_loc)).T
                local, _, _, mv, _ = engine.sharded_epoch_body(
                    X_loc, source, a, D_loc, cnt, k2, cfg=ecfg,
                    data_axes=data_axes, coff=coff)
                return local, mv
        assign, moves = jax.lax.cond(t > 0, _guided,
                                     lambda a: (a, moves), assign)
    cap = cfg.cap_factor * cfg.xi
    spill = cfg.spill
    with layer_scope("graph", "members"):
        if comm is not None:
            tT, sp, ovf = members_table_local(assign, row_ids, k0, cap // R,
                                              spill)
            table_T = engine._all_gather(tT, comm)               # (cap, k0)
            spill_ids = engine._all_gather(sp, comm)             # (R*spill,)
            overflow = engine._psum(ovf, comm)
        else:
            bl = lambda x: x.reshape((R, -1) + x.shape[1:])
            tT, sp, ovf = jax.vmap(
                lambda a, p: members_table_local(a, p, k0, cap // R, spill)
            )(bl(assign), bl(row_ids))
            table_T = tT.reshape(cap, k0)
            spill_ids = sp.reshape(R * spill)
            overflow = jnp.sum(ovf, dtype=jnp.int32)
    with layer_scope("graph", "candidates"):
        cand_rows = jnp.take(table_T, assign, axis=1).T          # (B, cap)
        spill_b = jnp.broadcast_to(spill_ids[None, :],
                                   (B, spill_ids.shape[0]))
        cand_rows = jnp.concatenate([cand_rows, spill_b], axis=1)
        cand_ids = jnp.where(cand_rows >= 0,
                             real_id[jnp.maximum(cand_rows, 0)], -1)
        # mask self and phantoms of self; phantom dupes dedupe in the merge
        cand_ids = jnp.where(cand_ids == own_real[:, None], -1, cand_ids)
        cand_rows = jnp.maximum(cand_rows, 0)
    g_ids, g_d = _refine_rows(X_loc, cand_rows, cand_ids, g_ids, g_d, X_full,
                              cfg.chunk, cfg.force)
    return g_ids, g_d, overflow, moves


def _descent_round(X_full, X_loc, row_ids, own_real, g_ids, g_d, key, *,
                   cfg, n, sample, comm):
    """One NN-Descent round: neighbours-of-neighbours + approximate reverse
    edges (candidate generation replicated, distances + merge local)."""
    G_full = engine._all_gather(g_ids, comm) if comm is not None else g_ids
    ids = jnp.maximum(G_full, 0)                           # (n, κ)
    kappa = ids.shape[1]
    k1, k2, k3 = jax.random.split(key, 3)

    # forward: neighbours of neighbours, subsampled to `sample`
    pick1 = jax.random.randint(k1, (n, sample), 0, kappa)
    pick2 = jax.random.randint(k2, (n, sample), 0, kappa)
    mid = jnp.take_along_axis(ids, pick1, axis=1)          # (n, s)
    fwd = ids[mid, pick2]                                  # (n, s)

    # approximate reverse neighbours: scatter each edge (i -> j) into a
    # random slot of j's reverse list (collisions overwrite — a subsample)
    slot = jax.random.randint(k3, (n, kappa), 0, sample)
    src = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                           (n, kappa))
    rev = jnp.full((n, sample), -1, jnp.int32).at[
        ids.reshape(-1), slot.reshape(-1)].set(src.reshape(-1))

    cand = jnp.concatenate([fwd, rev], axis=1)[row_ids]    # (B, 2s)
    cand = jnp.where(cand == own_real[:, None], -1, cand)
    g_ids, g_d = _refine_rows(X_loc, jnp.maximum(cand, 0), cand, g_ids, g_d,
                              X_full, cfg.chunk, cfg.force)
    return g_ids, g_d


def _build_rounds(X_loc, row_ids, real_id, key, *, cfg, n, k0, comm,
                  data_axes):
    """The whole build — init + tau rounds — as one traceable body.

    X_loc/row_ids (and the returned graph rows) are the local shard slice of
    the padded layout; real_id is replicated.  ``comm=None`` is the
    single-device topology (X_loc == the full padded data).
    """
    X_full = engine._all_gather(X_loc, comm) if comm is not None else X_loc
    own_real = real_id[row_ids]
    B = X_loc.shape[0]
    kinit, kloop = jax.random.split(key)

    # init = the same refinement step against κ random candidates: exact
    # distances, sorted and deduped from the very first merge
    g_ids = jnp.full((B, cfg.kappa), -1, jnp.int32)
    g_d = jnp.full((B, cfg.kappa), jnp.inf, jnp.float32)
    if cfg.random_init:
        with layer_scope("graph", "candidates"):
            cand0 = _random_ids(kinit, real_id, n, cfg.kappa)[row_ids]
        g_ids, g_d = _refine_rows(X_loc, jnp.maximum(cand0, 0), cand0,
                                  g_ids, g_d, X_full, cfg.chunk, cfg.force)

    sample = cfg.sample or 2 * cfg.kappa

    def round_body(carry, t):
        gi0, gd0 = carry
        kt = jax.random.fold_in(kloop, t)
        if cfg.source == "partition":
            gi, gd, ovf, moves = _partition_round(
                X_full, X_loc, row_ids, real_id, own_real, gi0, gd0, kt, t,
                cfg=cfg, k0=k0, comm=comm, data_axes=data_axes)
        else:
            gi, gd = _descent_round(X_full, X_loc, row_ids, own_real, gi0,
                                    gd0, kt, cfg=cfg, n=n, sample=sample,
                                    comm=comm)
            ovf = jnp.zeros((), jnp.int32)
            moves = jnp.zeros((), jnp.int32)
        if not cfg.telemetry:
            return (gi, gd), (ovf, moves)
        # telemetry extras: changed list entries vs round start, and the
        # mean finite neighbour distance (globals psum'd in-trace)
        upd = jnp.sum(gi != gi0, dtype=jnp.int32)
        fin = jnp.isfinite(gd)
        dsum = jnp.sum(jnp.where(fin, gd, 0.0))
        dcnt = jnp.sum(fin, dtype=jnp.float32)
        if comm is not None:
            upd = engine._psum(upd, comm)
            dsum = engine._psum(dsum, comm)
            dcnt = engine._psum(dcnt, comm)
        mdist = dsum / jnp.maximum(dcnt, 1.0)
        return (gi, gd), (ovf, moves, upd, mdist)

    (g_ids, g_d), ys = jax.lax.scan(
        round_body, (g_ids, g_d), jnp.arange(cfg.tau, dtype=jnp.int32))
    if cfg.telemetry:
        overflow, moves, upd, mdist = ys
        tel = obs_tel.record_rows(obs_tel.init(cfg.tau), overflow=overflow,
                                  guided_moves=moves, graph_updates=upd,
                                  graph_mean_dist=mdist)
    else:
        overflow, moves = ys
        tel = None
    return g_ids, g_d, overflow, moves, tel


def _pad_rows(X, key, n_pad):
    """Pad X with phantom copies of random rows; returns (X_pad, real_id)."""
    n = X.shape[0]
    if n_pad > n:
        extra = jax.random.randint(key, (n_pad - n,), 0, n, dtype=jnp.int32)
        real_id = jnp.concatenate([jnp.arange(n, dtype=jnp.int32), extra])
        return X[real_id], real_id
    return X, jnp.arange(n, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnums=(2,))
def _build_single(X, key, cfg: GraphBuildConfig):
    n = X.shape[0]
    k0, n_pad = _plan(n, cfg)
    kpad, kb = jax.random.split(key)
    X_pad, real_id = _pad_rows(X, kpad, n_pad)
    row_ids = jnp.arange(n_pad, dtype=jnp.int32)
    g_ids, g_d, overflow, moves, tel = _build_rounds(
        X_pad, row_ids, real_id, kb, cfg=cfg, n=n, k0=k0, comm=None,
        data_axes=())
    return (KnnGraph(g_ids[:n], g_d[:n]),
            BuildDiagnostics(overflow, moves, tel))


def build_graph(X: jax.Array, key: jax.Array, cfg: GraphBuildConfig
                ) -> Tuple[KnnGraph, BuildDiagnostics]:
    """Single-device device-resident build: ONE dispatch, O(1) host syncs.

    Returns (KnnGraph (n, κ), BuildDiagnostics (tau,)-per-round).  With
    ``cfg.shards=R`` the guided pass emulates an R-way sharded visit order,
    making the result bit-exact against a ``GraphBuilder`` build on an
    R-device mesh (the topology-parity contract of ``core.engine``).
    """
    if cfg.source == "partition" and cfg.shards > 1:
        k0, n_pad = _plan(X.shape[0], cfg)
        assert n_pad % cfg.shards == 0
        assert (cfg.cap_factor * cfg.xi) % cfg.shards == 0
        assert not cfg.guided or k0 % cfg.shards == 0
    return _build_single(X, key, cfg)


class GraphBuilder:
    """Mesh-resident graph builder: the ``ShardedEngine`` of graph builds.

    Holds (cfg, mesh) and exposes ``build(X, key)``: the whole tau-round
    loop inside one jitted ``shard_map`` program — rows and graph rows
    sharded over the data axes, X all-gathered once, candidate distances and
    merges local, O(1) host syncs per build.  ``mesh=None`` falls back to
    the single-device ``build_graph`` program.

    Constraints: the padded row count (``k0 * xi`` for the partition source,
    n for descent) must divide the mesh's data-axis size — powers of two
    always do for the partition layout; truncate descent inputs with
    ``distributed.usable_rows`` otherwise.
    """

    def __init__(self, cfg: GraphBuildConfig, mesh=None,
                 data_axes: Tuple[str, ...] = ("data",)):
        self.cfg = cfg
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self._programs = {}
        if mesh is not None:
            import math
            self.shards = math.prod(mesh.shape[a] for a in self.data_axes)
        else:
            self.shards = 1

    def _make_program(self, n: int):
        cfg = self.cfg
        k0, n_pad = _plan(n, cfg)
        if self.mesh is None:
            return lambda X, key: _build_single(X, key, cfg)
        assert n_pad % self.shards == 0, (
            f"padded rows {n_pad} must divide the {self.shards}-way mesh "
            "(see distributed.usable_rows for the descent source)")
        if cfg.source == "partition":
            cap = cfg.cap_factor * cfg.xi
            assert cap % self.shards == 0, (
                f"member-table capacity {cap} must divide the "
                f"{self.shards}-way mesh (per-shard table slices)")
            assert not cfg.guided or k0 % self.shards == 0, (
                f"k0={k0} must divide the {self.shards}-way mesh for the "
                "cluster-sharded guided pass (raise xi or shrink the mesh)")
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        row, rep = P(self.data_axes), P()
        comm = engine._Comm(self.data_axes)

        def body(X_pad, row_ids, real_id, kb):
            return _build_rounds(X_pad, row_ids, real_id, kb, cfg=cfg, n=n,
                                 k0=k0, comm=comm, data_axes=self.data_axes)

        # trailing rep spec covers the telemetry (None, an empty pytree,
        # when cfg.telemetry is off — one spec list serves both modes)
        sharded = shard_map(body, mesh=self.mesh,
                            in_specs=(row, row, rep, rep),
                            out_specs=(row, row, rep, rep, rep),
                            check_vma=False)

        def program(X, key):
            kpad, kb = jax.random.split(key)
            X_pad, real_id = _pad_rows(X, kpad, n_pad)
            row_ids = jnp.arange(n_pad, dtype=jnp.int32)
            g_ids, g_d, overflow, moves, tel = sharded(X_pad, row_ids,
                                                       real_id, kb)
            return (KnnGraph(g_ids[:n], g_d[:n]),
                    BuildDiagnostics(overflow, moves, tel))

        return jax.jit(program)

    def build(self, X: jax.Array, key: jax.Array
              ) -> Tuple[KnnGraph, BuildDiagnostics]:
        n, d = X.shape
        sig = (n, d, X.dtype)
        fn = self._programs.get(sig)
        if fn is None:
            fn = self._programs[sig] = self._make_program(n)
        return fn(X, key)

    def __repr__(self):
        return (f"GraphBuilder(shards={self.shards}, "
                f"source={self.cfg.source!r}, cfg={self.cfg})")
