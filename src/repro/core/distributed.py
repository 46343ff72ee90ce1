"""Distributed GK-means — shard_map adapters over the unified engine.

Layout (DESIGN.md §4):
  * X and the KNN graph rows are sharded over the data axes (row-parallel);
  * the assignment vector is sharded; a replicated copy for *candidate
    lookup* (neighbour ids are global) is refreshed once per epoch via
    all_gather;
  * the composite vectors D are CLUSTER-sharded (shard s owns the
    contiguous block [s*k/R, (s+1)*k/R)); scoring materialises only the
    batch's candidate rows via the candidate-row exchange
    (``engine._exchange_rows``: all-gather of the id union + a psum of
    owner-masked row contributions, O(R·B·C·d) wire, no (k, d) operand),
    and updates either scatter only owned rows (``sparse_updates``) or psum
    the move deltas in the audit-neutral transposed (d, k) layout.  The 1-D
    ``cnt`` stays replicated so the leaver guard is topology-agnostic.

``ShardedEngine`` is the one entry point: a mesh + ``EngineConfig`` pair
with jitted ``epoch`` / ``run`` / ``distortion`` shard_map programs.  The
bodies live in ``repro.core.engine`` (``sharded_epoch_body`` /
``sharded_run_body``) and are the same candidate->score->move step the
single-device path runs: ``mode='lloyd'``, ``sparse_updates`` and
``payload_bf16`` are engine options in both topologies,
``engine.epoch(..., shards=R)`` reproduces one sharded epoch on one device,
and ``engine.run(..., shards=R)`` reproduces a whole ``ShardedEngine.run``
(the parity tests pin both bit-exactly in sparse mode).  ``run`` keeps the
epoch loop, per-epoch O(k·d) distortion, and the ``min_move_frac`` early
stop inside ONE trace across the mesh — one host sync per run, matching the
single-device ``engine.run``.

Row counts need NOT divide the mesh: ``ShardedEngine`` zero-pads X/G/assign
up to the next multiple of R and passes an in-trace validity mask
(``rows >= n`` contribute nothing to scores, stats, moves, or telemetry),
so ``n % R != 0`` runs natively — no out-of-band truncation or post-hoc
remainder assignment.  ``usable_rows`` remains for callers that want the
old explicit-truncation behaviour.

Graph construction shards with the same conventions:
``sharded_graph_builder(mesh, cfg)`` returns a ``core.graph_build``
``GraphBuilder`` whose whole tau-round build runs inside one shard_map
trace — rows and graph rows sharded, candidate distances and merges local,
O(1) host syncs per build, bit-exact against the single-device build with
``GraphBuildConfig(shards=R)``.

IVF serving shards by CELL rather than by row: ``ShardedIvf`` re-packs an
``IvfIndex``'s inverted lists into equal per-shard slabs
(``index.ivf.shard_lists``), keeps queries replicated, and shards the
coarse quantizer round-robin over cells: each shard probes only its own
centroid slab (ceil(k / R) cells), and the per-shard top-min(nprobe,
k_slab) partials are exchanged and merged with the same first-min selection
(``index.probe.merge_probe_cells``) — the full (k, d) centroid matrix is
never materialised.  Search then runs local list scan -> one all-gather of
per-shard local top-k -> in-trace merge inside ONE shard_map trace per
query batch.  The local scans return RAW partial distances and the merge is
the kernels' own stable first-minimum selection, so the sharded search is
bit-exact with the single-device ``index.probe.search`` (no ``n % R``
constraint: slab padding rows carry id -1 and can never surface).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core.engine import (CandidateSource, EngineConfig, dense_source,
                               graph_source, probe_source,
                               sharded_epoch_body, sharded_run_body)
from repro.kernels.ref import HIGHEST

DATA_AXES = ("data",)


def usable_rows(n: int, shards: int) -> int:
    """Largest row count <= n that the mesh's data axes divide evenly."""
    return (n // shards) * shards


class ShardedEngine:
    """Mesh-resident clustering engine: one API for every sharded caller.

    Holds (mesh, ``EngineConfig``, candidate kind) and exposes three entry
    points over row-sharded X/G/assign, CLUSTER-sharded D, and replicated
    cnt (callers still pass and receive the full (k, d) D — shard_map
    slices/reassembles the contiguous cluster blocks at the boundary):

      ``epoch(X, G, assign, D, cnt, key)``  -> (assign, D, cnt, moves)
          one pass (``engine.sharded_epoch_body``);
      ``run(X, G, assign, D, cnt, key)``    -> (assign, D, cnt, hist, mhist,
          epochs, final, tel) — the whole ``cfg.iters`` epoch loop, per-epoch
          stats distortion and the ``min_move_frac`` early stop inside ONE
          trace (``engine.sharded_run_body``): one host sync per run.
          ``tel`` is a replicated per-epoch ``obs.telemetry.Telemetry`` when
          ``cfg.telemetry`` and None otherwise — it rides the same sync;
      ``distortion(X, assign, D, cnt)``     -> () global mean distortion
          (O(n·d) recompute, for host-driven loops and checks).

    ``kind`` selects the candidate source ('graph' | 'dense' | 'probe'); G
    is the neighbour-id array for 'graph' and ignored otherwise (pass any
    row-sharded int32 array of matching leading dim).

    ``n % R != 0`` is handled natively: the wrapper zero-pads the row
    arrays to the next multiple of R and threads a validity mask into the
    trace (padded rows contribute zero to scores, stats, moves, and
    telemetry); the returned assignment is sliced back to n rows.  k must
    divide R (the cluster blocks are equal).
    """

    def __init__(self, mesh: Mesh, cfg: EngineConfig = EngineConfig(), *,
                 kind: str = "graph", probe_p: int = 8,
                 data_axes: Tuple[str, ...] = DATA_AXES):
        assert kind in ("graph", "dense", "probe"), kind
        self.mesh = mesh
        self.cfg = cfg
        self.kind = kind
        self.probe_p = probe_p
        self.data_axes = tuple(data_axes)
        self.shards = math.prod(mesh.shape[a] for a in self.data_axes)
        row, rep = P(self.data_axes), P()

        def source(G) -> CandidateSource:
            if kind == "graph":
                return graph_source(G)
            if kind == "probe":
                return probe_source(probe_p)
            return dense_source()

        def epoch_fn(X, G, assign, D, cnt, key, cix, rid, n):
            # keep the public epoch API a 4-tuple: drop the telemetry-only
            # `prop` counter (run() is where telemetry surfaces).  cix is a
            # sharded arange(k) — its first element is this shard's cluster
            # offset, derived from data rather than axis_index (XLA:CPU
            # forced-host partitioning hazard); rid/n give the padded-row
            # validity mask.
            out = sharded_epoch_body(X, source(G), assign, D, cnt, key,
                                     cfg=cfg, data_axes=self.data_axes,
                                     coff=cix[0], valid=rid < n)
            return out[:4]

        def run_fn(X, G, assign, D, cnt, key, cix, rid, n):
            return sharded_run_body(X, source(G), assign, D, cnt, key,
                                    cfg=cfg, data_axes=self.data_axes,
                                    coff=cix[0], valid=rid < n)

        def dist_fn(X, assign, D, cnt, cix, rid, n):
            # diagnostics recompute against the sharded D: materialise each
            # local row's OWN centroid via the candidate-row exchange (no
            # (k, d) operand anywhere, O(R·n_loc·d) wire)
            from repro.core.engine import _Comm, _exchange_rows
            comm = _Comm(self.data_axes)
            Xf = X.astype(jnp.float32)
            rows = _exchange_rows(assign[:, None], D, cix[0], comm)[:, 0]
            C_own = rows / jnp.maximum(cnt[assign], 1.0)[:, None]
            vf = (rid < n).astype(jnp.float32)
            diff = (Xf - C_own) * vf[:, None]
            tot = jax.lax.psum(jnp.sum(diff * diff), self.data_axes)
            nn = jax.lax.psum(jnp.sum(vf), self.data_axes)
            return tot / nn

        self._epoch = jax.jit(shard_map(
            epoch_fn, mesh=mesh,
            in_specs=(row, row, row, row, rep, rep, row, row, rep),
            out_specs=(row, row, rep, rep), check_vma=False))
        # trailing rep spec covers `tel` — P() over the disabled path's None
        # (an empty pytree) is a no-op, so one spec list serves both modes
        self._run = jax.jit(shard_map(
            run_fn, mesh=mesh,
            in_specs=(row, row, row, row, rep, rep, row, row, rep),
            out_specs=(row, row, rep, rep, rep, rep, rep, rep),
            check_vma=False))
        self._distortion = jax.jit(shard_map(
            dist_fn, mesh=mesh,
            in_specs=(row, row, row, rep, row, row, rep),
            out_specs=rep, check_vma=False))

    def _pad(self, k: int, X, *rows):
        """Zero-pad row-sharded arrays to n_pad = ceil(n/R)*R; returns the
        padded arrays plus the (cix, rid, n) mask inputs."""
        R = self.shards
        assert k % R == 0, f"k={k} must divide the {R}-way mesh"
        n = X.shape[0]
        n_pad = -(-n // R) * R
        pad = n_pad - n
        if pad:
            X = jnp.concatenate(
                [jnp.asarray(X),
                 jnp.zeros((pad,) + X.shape[1:], jnp.asarray(X).dtype)])
            rows = tuple(
                jnp.concatenate(
                    [jnp.asarray(r),
                     jnp.zeros((pad,) + r.shape[1:], jnp.asarray(r).dtype)])
                for r in rows)
        cix = jnp.arange(k, dtype=jnp.int32)
        rid = jnp.arange(n_pad, dtype=jnp.int32)
        return (X,) + rows + (cix, rid, jnp.int32(n))

    def epoch(self, X, G, assign, D, cnt, key):
        n = X.shape[0]
        Xp, Gp, ap, cix, rid, nn = self._pad(D.shape[0], X, G, assign)
        assign, D, cnt, moves = self._epoch(Xp, Gp, ap, D, cnt, key, cix,
                                            rid, nn)
        return assign[:n], D, cnt, moves

    def run(self, X, G, assign, D, cnt, key):
        n = X.shape[0]
        Xp, Gp, ap, cix, rid, nn = self._pad(D.shape[0], X, G, assign)
        out = self._run(Xp, Gp, ap, D, cnt, key, cix, rid, nn)
        return (out[0][:n],) + tuple(out[1:])

    def distortion(self, X, assign, D, cnt):
        Xp, ap, cix, rid, nn = self._pad(D.shape[0], X, assign)
        return self._distortion(Xp, ap, D, cnt, cix, rid, nn)

    def __repr__(self):
        return (f"ShardedEngine(shards={self.shards}, kind={self.kind!r}, "
                f"cfg={self.cfg})")


def make_sharded_epoch(mesh: Mesh, *, data_axes: Tuple[str, ...] = DATA_AXES,
                       batch_size: int = 1024, eps: float = 0.0,
                       mode: str = "bkm", kind: str = "graph",
                       probe_p: int = 8, sparse_updates: bool = False,
                       payload_bf16: bool = False):
    """Back-compat shim: the ``epoch`` entry point of a ``ShardedEngine``."""
    cfg = EngineConfig(batch_size=batch_size, eps=eps, mode=mode,
                       sparse_updates=sparse_updates,
                       payload_bf16=payload_bf16)
    return ShardedEngine(mesh, cfg, kind=kind, probe_p=probe_p,
                         data_axes=data_axes).epoch


def sharded_distortion(mesh: Mesh, data_axes: Tuple[str, ...] = DATA_AXES):
    """Back-compat shim: the ``distortion`` entry point of a ShardedEngine."""
    return ShardedEngine(mesh, data_axes=data_axes).distortion


class ShardedIvf:
    """Mesh-resident IVF index serving: one shard_map trace per query batch.

    Wraps an ``index.IvfIndex`` for multi-device serving with the engine's
    mesh conventions: the packed inverted lists are sharded by cell over
    ``data_axes`` (``index.ivf.shard_lists`` equal-slab layout), queries and
    the coarse quantizer stay replicated, and ``search`` runs the whole
    probe -> local fused scan -> all-gather(local top-k) -> merge path in
    one jitted shard_map program — one dispatch and one host sync per query
    batch (the caller's ``device_get``).

    Parity: every packed row lives on exactly one shard and local scans
    return raw partial distances, merged with the same stable first-minimum
    selection the scan kernels use, so results are bit-exact with the
    single-device ``index.probe.search(index, Q, ...)`` (tests pin this on 4
    virtual devices under a device->host transfer guard).
    """

    def __init__(self, mesh: Mesh, index, *,
                 data_axes: Tuple[str, ...] = DATA_AXES):
        from repro.index.ivf import shard_lists
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.shards = math.prod(mesh.shape[a] for a in self.data_axes)
        # keep only what serving needs (coarse quantizer + static layout
        # scalars), NOT the unsharded index — holding index.vecs alive would
        # double resident database memory for the replica's lifetime
        self.k = index.k
        self.block_rows = index.block_rows
        self.max_list_tiles = index.max_list_tiles
        self.capacity_rows = index.capacity_rows  # scan_frac denominator
        self.d = index.vecs.shape[1]
        row, rep = (NamedSharding(mesh, P(self.data_axes)),
                    NamedSharding(mesh, P()))
        # the codec (small pytree of scales / codebooks) is replicated:
        # every shard builds the same per-query LUT
        self.codec = (None if index.codec is None
                      else jax.device_put(index.codec, rep))
        # place the slabs on the mesh NOW: leaving them on the default
        # device would make every search() dispatch re-distribute the whole
        # packed database to satisfy the shard_map in_specs
        p = shard_lists(index, self.shards)
        # coarse quantizer sharded round-robin over cells, NOT by list owner:
        # the merged probe result is replicated either way, and the list
        # owner map balances ROWS, so its cell counts skew — the probe's
        # wall-clock is the max slab, and round-robin pins that at
        # ceil(k / R).  k_slab holes carry cell id -1 (probed at +inf, can
        # never surface while real cells remain — and nprobe <= k).
        import numpy as np
        R = self.shards
        cent = np.asarray(index.centroids,  # lint: boundary(one-time setup)
                          np.float32)
        k_slab = max(-(-self.k // R), 1)
        cslab = np.zeros((R * k_slab, self.d), np.float32)
        ccid = np.full((R * k_slab,), -1, np.int32)
        for s in range(R):
            cells = np.arange(s, self.k, R)
            cslab[s * k_slab:s * k_slab + len(cells)] = cent[cells]
            ccid[s * k_slab:s * k_slab + len(cells)] = cells
        self.k_slab = k_slab
        self.cslab = jax.device_put(jnp.asarray(cslab), row)
        self.ccid = jax.device_put(jnp.asarray(ccid), row)
        self.parts = p._replace(
            vecs=jax.device_put(p.vecs, row),
            ids=jax.device_put(p.ids, row),
            starts=jax.device_put(p.starts, row),
            caps=jax.device_put(p.caps, row),
            codes=None if p.codes is None else jax.device_put(p.codes, row),
            vnorm=None if p.vnorm is None else jax.device_put(p.vnorm, row))
        self._progs = {}

    def search(self, Q: jax.Array, *, topk: int = 10, nprobe: int = 8,
               qgroup=None, telemetry: bool = False, codec: str = "f32",
               rerank=None):
        """Top-k over the sharded lists -> (ids (q, topk), d2 (q, topk)).

        ``qgroup=G`` runs the query-grouped scan layout per shard (each
        shard groups by ITS local tile locality; results are scattered back
        to original query order before the cross-shard merge, so the merged
        output is replicated and matches per-query ids whenever distances
        are distinct).  ``telemetry=True`` appends a 1-row
        ``obs.telemetry.Telemetry`` third output (scanned_rows,
        scanned_rows_max_shard, scan_frac, scanned_bytes) accumulated
        in-trace — it rides the same single host sync as the ids.

        ``codec="pq"|"int8"`` scans the sharded COMPRESSED slabs through
        `ivf_scan_adc` (the replicated per-query LUT is built inside the
        trace; only codes + norms stream from each shard's HBM), then each
        shard exact-reranks its own top-``rerank`` ADC survivors against its
        f32 slab before the one all-gather — same single-sync collective
        schedule as the f32 path, with ``bytes_per_row(codec)`` per scanned
        row instead of ``4 d``.  ``rerank`` follows
        ``index.probe.search`` (None -> 4 * topk; 0 disables the tail, and
        that path is bit-exact with the single-device codec search).
        """
        assert nprobe >= 1, nprobe
        nprobe = min(nprobe, self.k)
        if self.max_list_tiles == 0:      # every list empty: nothing to scan
            from repro.index.probe import _no_candidates
            from repro.obs import telemetry as obs_tel
            out = _no_candidates(Q.shape[0], topk)
            return out + (obs_tel.init(1),) if telemetry else out
        p = self.parts
        if codec != "f32":
            assert qgroup is None, "codec scan is per-query only (no qgroup)"
            assert self.codec is not None and self.codec.kind == codec, \
                (codec, None if self.codec is None else self.codec.kind)
            prog = self._prog(topk, nprobe, qgroup, telemetry, codec, rerank)
            return prog(Q, p.vecs, p.ids, p.starts, p.caps, self.cslab,
                        self.ccid, p.codes, p.vnorm, self.codec)
        prog = self._prog(topk, nprobe, qgroup, telemetry, "f32", None)
        return prog(Q, p.vecs, p.ids, p.starts, p.caps, self.cslab,
                    self.ccid)

    def _prog(self, topk: int, nprobe: int, qgroup, telemetry: bool,
              codec: str, rerank):
        key = (topk, nprobe, qgroup, telemetry, codec, rerank)
        if key in self._progs:
            return self._progs[key]
        from repro.index import quantize as _q
        from repro.index.probe import (_rerank_depth, build_group_map,
                                       build_tile_map, exact_rerank,
                                       merge_probe_cells, merge_shard_topk)
        from repro.kernels import ops as kops
        from repro.kernels.ref import finalize_d2, stable_topk
        from repro.obs import telemetry as obs_tel
        bl = self.block_rows
        max_tiles = self.max_list_tiles
        null_loc = self.parts.rows_loc // bl - 1    # last local tile: holes
        axes = self.data_axes
        R = self.shards
        k_slab = self.k_slab
        cap = max(self.capacity_rows, 1)
        grouped = qgroup is not None and qgroup > 1
        depth = _rerank_depth(topk, rerank) if codec != "f32" else 0
        bpr = (4 * self.d if codec == "f32"
               else _q.bytes_per_row(self.codec, self.d))

        def probe_cells(Q, cslab_l, ccid_l):
            """Sharded coarse probe: rank owned cells, exchange, merge.

            Each shard scores only its k_slab = ceil(k / R) slab centroids
            on the RAW probe partials (bitwise equal to the full scan's
            entries for those cells), the per-shard top-min(nprobe, k_slab)
            lists ride
            one (L, q)-layout all-gather, and ``merge_probe_cells`` keeps
            the kernels' first-min tie-break — so the merged cell set (and,
            for distinct partials, its order) matches the single-device
            ``kops.probe_centroids`` exactly, without a (k, d) operand.
            """
            Qf = Q.astype(jnp.float32)
            Cf = cslab_l.astype(jnp.float32)
            csq = jnp.sum(Cf * Cf, axis=-1)
            part = csq[None, :] - 2.0 * jnp.matmul(      # (q, k_slab)
                Qf, Cf.T, precision=HIGHEST)
            part = jnp.where((ccid_l >= 0)[None, :], part, jnp.inf)
            d_l, i_l = stable_topk(
                part, jnp.broadcast_to(ccid_l, part.shape),
                min(nprobe, k_slab))
            gd = jax.lax.all_gather(d_l.T, axes, tiled=True)
            gi = jax.lax.all_gather(i_l.T, axes, tiled=True)
            return merge_probe_cells(gd, gi, nprobe)

        def tail(Q, scaps, cids, lid, lod):
            """All-gather local top-k -> stable merge -> finalize (+tel)."""
            q = Q.shape[0]
            agi, agd = jax.lax.all_gather((lid, lod), axes)  # (R, q, t)
            ids, od = merge_shard_topk(agi.reshape(R, *lid.shape),
                                       agd.reshape(R, *lod.shape), topk)
            out = finalize_d2(ids, od, Q)
            if not telemetry:
                return out
            scanned_loc = jnp.sum(scaps[cids], dtype=jnp.int32)
            total = jax.lax.psum(scanned_loc, axes)
            worst = jax.lax.pmax(scanned_loc, axes)
            tel = obs_tel.record(
                obs_tel.init(1), 0, scanned_rows=total,
                scanned_rows_max_shard=worst,
                scan_frac=total.astype(jnp.float32) / (q * cap),
                scanned_bytes=total.astype(jnp.float32) * bpr)
            return out + (tel,)

        def body(Q, svecs, sids, sstarts, scaps, cslab_l, ccid_l):
            q = Q.shape[0]
            # sharded probe; the merged cids are replicated on every shard
            cids = probe_cells(Q, cslab_l, ccid_l)
            tm = build_tile_map(cids, sstarts, scaps, max_tiles=max_tiles,
                                block_rows=bl, null_tile=null_loc)
            if grouped:
                # shard-local grouping (order depends on LOCAL tile ids);
                # scatter raw results back to the original query order so
                # the all-gathered tensors are replicated across shards
                order, union, qmask = build_group_map(tm, group=qgroup,
                                                      null_tile=null_loc)
                Qg = Q[jnp.clip(order, 0, q - 1)]
                gi, gd = kops.ivf_scan_grouped(Qg, svecs, sids, union, qmask,
                                               block_rows=bl, topk=topk,
                                               raw=True)
                lid = jnp.full((q, topk), -1, jnp.int32
                               ).at[order].set(gi, mode="drop")
                lod = jnp.full((q, topk), jnp.inf, jnp.float32
                               ).at[order].set(gd, mode="drop")
            else:
                lid, lod = kops.ivf_scan(Q, svecs, sids, tm, block_rows=bl,
                                         topk=topk, raw=True)
            return tail(Q, scaps, cids, lid, lod)

        def body_codec(Q, svecs, sids, sstarts, scaps, cslab_l, ccid_l,
                       scodes, svnorm, cdc):
            cids = probe_cells(Q, cslab_l, ccid_l)
            tm = build_tile_map(cids, sstarts, scaps, max_tiles=max_tiles,
                                block_rows=bl, null_tile=null_loc)
            # replicated LUT (small: q * M * W f32) — codes stay sharded
            lut, qc = _q.build_lut(cdc, Q)
            lid, lpos, lod = kops.ivf_scan_adc(
                lut, qc, svnorm, scodes, sids, tm, block_rows=bl,
                topk=(depth or topk))
            if depth:
                # each shard reranks its OWN survivors against its f32 slab:
                # the union of per-shard top-depth contains the global
                # top-depth, so the merged exact top-k can only improve on
                # the single-device rerank (equal-or-better recall)
                lid, lod = exact_rerank(Q, svecs, sids, lpos, topk=topk)
            return tail(Q, scaps, cids, lid, lod)

        row, rep = P(self.data_axes), P()
        out_specs = (rep, rep, rep) if telemetry else (rep, rep)
        if codec != "f32":
            prog = jax.jit(shard_map(
                body_codec, mesh=self.mesh,
                in_specs=(rep, row, row, row, row, row, row, row, row, rep),
                out_specs=out_specs, check_vma=False))
        else:
            prog = jax.jit(shard_map(
                body, mesh=self.mesh,
                in_specs=(rep, row, row, row, row, row, row),
                out_specs=out_specs, check_vma=False))
        self._progs[key] = prog
        return prog

    def __repr__(self):
        return (f"ShardedIvf(shards={self.shards}, k={self.k}, "
                f"rows_loc={self.parts.rows_loc})")


def sharded_graph_builder(mesh: Mesh, cfg=None, *,
                          data_axes: Tuple[str, ...] = DATA_AXES):
    """Mesh-resident KNN-graph builder (``core.graph_build.GraphBuilder``).

    The graph-build twin of ``ShardedEngine``: ``builder.build(X, key)``
    runs Alg. 3 (or NN-Descent, ``cfg.source='descent'``) with rows + graph
    rows sharded over ``data_axes`` and the whole tau-round loop in ONE
    shard_map trace.  The padded row count must divide the mesh
    (``usable_rows`` helps for the descent source; the partition layout is a
    power of two and always divides a power-of-two mesh).
    """
    from repro.core.graph_build import GraphBuildConfig, GraphBuilder
    return GraphBuilder(cfg or GraphBuildConfig(), mesh=mesh,
                        data_axes=data_axes)
