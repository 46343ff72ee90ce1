"""Unified device-resident clustering engine: candidate -> score -> move.

Every clustering loop in this repo is the same three steps:

  candidates  which clusters may a sample move to — the clusters of its κ
              graph neighbours (GK-means, Alg. 2), all k clusters (full
              boost k-means), or the top-p probed cells (IVF-style);
  score       ΔI of the move (paper Eqn. 3, mode='bkm') or distance to the
              candidate centroid (mode='lloyd', §5.2 variant);
  move        accept the best move, guard against emptying a cluster, and
              scatter-update the running statistics (D, cnt).

This module implements that core ONCE for both topologies.  ``epoch`` is the
single-device pass; ``sharded_epoch_body`` is the same step sequence written
against ``shard_map`` collectives (``core.distributed`` wraps it) — both call
the shared ``_move_step``, so ``sparse_updates``, ``payload_bf16``, both
modes, and the leaver guard behave identically everywhere.  ``epoch`` can
also *emulate* an R-way sharded visit order bit-exactly (``cfg.shards``),
which is how the parity tests pin the two topologies together.

``run`` is the fully device-resident multi-epoch driver: a
``jax.lax.while_loop`` over ``BKMState`` with the ``min_move_frac``
early stop *inside* the trace and per-epoch distortion computed in O(k·d)
from the running statistics (``sum||x||² − Σ_c ||D_c||²/n_c``, with the
``sum||x||²`` term hoisted out of the loop) — one host sync per run instead
of one per epoch.  ``sharded_run_body`` is the same loop written against the
shard_map collectives (``core.distributed.ShardedEngine`` wraps it), so the
multi-device topology pays one host sync per run too.

Candidate sets are plain array arguments (a ``CandidateSource`` pytree), not
closures: calling the engine with a *new* graph of the same shape reuses the
existing jit trace (the old ``cand_fn``-as-static-argnum API retraced on
every call).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import permute
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.obs import telemetry as obs_tel
from repro.obs.timing import layer_scope


class BKMState(NamedTuple):
    assign: jax.Array  # (n,) int32
    D: jax.Array       # (k, d) float32 — composite vectors
    cnt: jax.Array     # (k,) float32
    moves: jax.Array   # () int32 — moves accepted in the last epoch


def init_state(X: jax.Array, assign: jax.Array, k: int) -> BKMState:
    from repro.core.objective import cluster_stats
    stats = cluster_stats(X, assign, k)
    return BKMState(assign.astype(jnp.int32), stats.D, stats.cnt,
                    jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# candidate sources
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class CandidateSource:
    """Which clusters each sample may move to.

    kind='graph': the clusters of the sample's graph neighbours (``G`` is a
    (n, κ) int32 neighbour-id array — a *traced* leaf, so swapping in a new
    graph of the same shape does not retrace);
    kind='dense': all k clusters, scored with one matmul (the (B, k, d)
    gather is never materialised);
    kind='probe': the ``p`` nearest cells by current centroid (flash-argmin
    top-p probe, ``kernels.ops.probe_centroids``).
    """

    def __init__(self, kind: str, G: Optional[jax.Array] = None, p: int = 0):
        assert kind in ("graph", "dense", "probe"), kind
        self.kind = kind
        self.G = G
        self.p = p

    def tree_flatten(self):
        return (self.G,), (self.kind, self.p)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux[0], children[0], aux[1])

    def __repr__(self):
        return f"CandidateSource({self.kind!r}, p={self.p})"


def graph_source(G: jax.Array) -> CandidateSource:
    return CandidateSource("graph", jnp.maximum(G, 0).astype(jnp.int32))


def dense_source() -> CandidateSource:
    return CandidateSource("dense")


def probe_source(p: int) -> CandidateSource:
    return CandidateSource("probe", p=p)


class EngineConfig(NamedTuple):
    """Static knobs of the engine (hashable: one jit trace per config)."""

    batch_size: int = 1024
    mode: str = "bkm"           # 'bkm' (Eqn. 3) | 'lloyd' (§5.2 variant)
    eps: float = 0.0            # minimum ΔI gain to accept a move
    iters: int = 1              # epochs for `run`
    min_move_frac: float = 0.0  # `run` stops when epoch moves <= frac * n
    sparse_updates: bool = False  # sharded: gather moved rows, not dense psum
    payload_bf16: bool = False    # sparse payload in bf16 (halves wire bytes)
    shards: int = 1             # single-device emulation of an R-way order
    force: Optional[str] = None  # kernel dispatch override (None|'ref'|...)
    telemetry: bool = False     # in-trace per-epoch Telemetry (obs.telemetry)


# ---------------------------------------------------------------------------
# the shared move step
# ---------------------------------------------------------------------------

def _candidates(source: CandidateSource, xb, u, idx, lookup, D, cnt, force):
    """Candidate cluster ids for one batch; None means dense-all-k."""
    if source.kind == "graph":
        return lookup[source.G[idx]]                      # (B, κ)
    if source.kind == "probe":
        C = D / jnp.maximum(cnt, 1.0)[:, None]
        ids, _ = kops.probe_centroids(xb, C, source.p, force=force)
        # The sample's own cluster must stay a candidate: the top-p probe
        # ranks by distance to D/max(cnt,1), so empty cells (centroid at the
        # origin) can crowd u out of the probe set, leaving `is_self`
        # all-False downstream — lloyd scoring then force-moves even when
        # staying is best, and bkm scoring loses its self-move mask.
        return jnp.concatenate([ids, u[:, None]], axis=1)  # (B, p+1)
    return None


def _score_gathered(xb, u, cand, D, cnt, mode, eps, force):
    """Best move per sample among gathered candidates -> (moved, want_v)."""
    score = kops.gather_score(xb, u, cand, D, cnt, mode=mode, force=force)
    with layer_scope("engine", "move"):
        is_self = cand == u[:, None]
        if mode == "bkm":
            score = jnp.where(is_self, -jnp.inf, score)
            best = jnp.argmax(score, axis=1)
            gain = jnp.take_along_axis(score, best[:, None], 1)[:, 0]
            moved = gain > eps
        else:
            best = jnp.argmin(score, axis=1)
            moved = ~jnp.take_along_axis(is_self, best[:, None], 1)[:, 0]
        want_v = jnp.take_along_axis(cand, best[:, None], 1)[:, 0]
    return moved, want_v


def _score_from_rows(xb, u, cand, rows, cnt, mode, eps):
    """Best move per sample from *materialised* candidate centroid rows.

    ``cand`` is (B, C) candidate cluster ids whose LAST column is the
    sample's own cluster u (so the u-terms of the bkm score come from
    ``rows[:, -1]`` without a second exchange); ``rows`` is the matching
    (B, C, d) slab of composite vectors.  This is the scoring path of the
    sharded-centroid topology: the mesh fills ``rows`` via the candidate-row
    exchange (`_exchange_rows`) and the single-device R-way emulation fills
    it with a plain ``D[cand]`` gather — element-for-element the same
    values, so the two topologies share every downstream flop bit-exactly.
    """
    dots = jnp.einsum("bd,bcd->bc", xb, rows,
                      precision=kref.HIGHEST)              # (B, C)
    dsq = jnp.sum(rows * rows, axis=-1)                  # (B, C)
    xsq = jnp.sum(xb * xb, axis=-1)                      # (B,)
    nv = cnt[cand]                                       # (B, C)
    is_self = cand == u[:, None]
    if mode == "bkm":
        gain_v = ((dsq + 2.0 * dots + xsq[:, None]) / (nv + 1.0)
                  - jnp.where(nv > 0, dsq / jnp.maximum(nv, 1.0), 0.0))
        du_sq = dsq[:, -1]
        x_du = dots[:, -1]
        nu = cnt[u]
        num_u = du_sq - 2.0 * x_du + xsq
        resid = jnp.where(nu > 1, num_u / jnp.maximum(nu - 1.0, 1.0), 0.0)
        score = gain_v + (resid - du_sq / jnp.maximum(nu, 1.0))[:, None]
        score = jnp.where(is_self, -jnp.inf, score)
        best = jnp.argmax(score, axis=1)
        moved = jnp.take_along_axis(score, best[:, None], 1)[:, 0] > eps
    else:
        csq_n = jnp.maximum(nv, 1.0)
        d2 = dsq / (csq_n * csq_n) - 2.0 * dots / csq_n
        d2 = jnp.where(nv > 0, d2, jnp.inf)
        best = jnp.argmin(d2, axis=1)
        moved = ~jnp.take_along_axis(is_self, best[:, None], 1)[:, 0]
    want_v = jnp.take_along_axis(cand, best[:, None], 1)[:, 0]
    return moved, want_v


def _score_dense(xb, u, D, cnt, mode, eps):
    """Best move per sample over ALL k clusters, via one matmul (MXU path)."""
    k = D.shape[0]
    dsq = jnp.sum(D * D, axis=-1)                        # (k,)
    dots = jnp.matmul(xb, D.T, precision=kref.HIGHEST)   # (B, k)
    xsq = jnp.sum(xb * xb, axis=-1)                      # (B,)
    if mode == "bkm":
        nv = cnt[None, :]
        gain_v = ((dsq[None, :] + 2.0 * dots + xsq[:, None]) / (nv + 1.0)
                  - jnp.where(nv > 0, dsq[None, :] / jnp.maximum(nv, 1.0),
                              0.0))
        du_sq = dsq[u]
        x_du = jnp.take_along_axis(dots, u[:, None], 1)[:, 0]
        nu = cnt[u]
        num_u = du_sq - 2.0 * x_du + xsq
        resid = jnp.where(nu > 1, num_u / jnp.maximum(nu - 1.0, 1.0), 0.0)
        score = gain_v + (resid - du_sq / jnp.maximum(nu, 1.0))[:, None]
        score = jnp.where(jnp.arange(k)[None, :] == u[:, None], -jnp.inf,
                          score)
        best = jnp.argmax(score, 1).astype(jnp.int32)
        moved = jnp.take_along_axis(score, best[:, None], 1)[:, 0] > eps
    else:
        csq_n = jnp.maximum(cnt, 1.0)
        d2 = (dsq[None, :] / (csq_n * csq_n)[None, :]
              - 2.0 * dots / csq_n[None, :])
        d2 = jnp.where(cnt[None, :] > 0, d2, jnp.inf)
        best = jnp.argmin(d2, 1).astype(jnp.int32)
        moved = best != u
    return moved, best


class _Comm(NamedTuple):
    """Collective hooks of the sharded topology (None -> single device)."""

    data_axes: Tuple[str, ...]


def _psum(x, comm: _Comm):
    return jax.lax.psum(x, comm.data_axes)


def _all_gather(x, comm: _Comm):
    for ax in comm.data_axes:
        x = jax.lax.all_gather(x, ax, tiled=True)
    return x


def _scatter_moves(D, cnt, u, v, gx, gw):
    """Apply the move deltas as ONE fused (k, d+1) scatter pair.

    ``cnt`` rides along as an extra column of ``D`` so XLA issues two
    scatters instead of four per batch.  Scatter-add accumulates every
    column independently, so each column of the fused result — and therefore
    both ``D`` and ``cnt`` — is bitwise-identical to the separate scatters;
    fusing only halves the per-batch scatter dispatch in the epoch hot loop
    (~100us/batch on XLA:CPU at k=256, d=32).  Used by BOTH the sharded
    sparse path and the single-device path so their row-order arithmetic
    stays identical (the cross-topology parity contract).
    """
    Dc = jnp.concatenate([D, cnt[:, None]], axis=1)
    g = jnp.concatenate([gx, gw[:, None]], axis=1)
    Dc = Dc.at[u].add(-g).at[v].add(g)
    return Dc[:, :-1], Dc[:, -1]


# ---------------------------------------------------------------------------
# sharded-centroid helpers: D lives cluster-sharded as D_loc = D[coff:coff+k_loc]
# ---------------------------------------------------------------------------

def _gather_stacked(x, comm: _Comm):
    """All-gather with a leading device axis: (B, ...) -> (R, B, ...)."""
    nd = x.ndim
    for ax in comm.data_axes:
        x = jax.lax.all_gather(x, ax, tiled=False)
    return x.reshape((-1,) + x.shape[x.ndim - nd:])


def _gather_minor(x, comm: _Comm):
    """All-gather concatenated along the LAST axis: (d, B) -> (d, R*B).

    Used to replicate the per-shard batch rows for all-k scoring against
    cluster-sharded centroids.  The transposed layout keeps the replicated
    operand's leading dim at d, which the replication audit does not track
    (a (R*B, d) gather would surface as a f32[n, d] finding in the dense
    variant where R*B == n)."""
    for ax in comm.data_axes:
        x = jax.lax.all_gather(x, ax, axis=x.ndim - 1, tiled=True)
    return x


def _exchange_rows(ids, D_loc, coff, comm: _Comm):
    """Candidate-row exchange: materialise D[ids] against a sharded D.

    ``ids`` is this shard's (B, C) candidate cluster ids.  All shards gather
    the union of candidate ids (s32, O(R·B·C) wire — no (k, d) operand), each
    shard contributes the rows it owns (zeros elsewhere), and a psum
    reconstitutes the full rows.  Every cluster has exactly ONE owner, so
    each psum element reduces owner-value + zeros — bit-exact in any
    reduction order, which is what lets the single-device emulation replace
    the whole exchange with a plain ``D[ids]`` gather.  The gathered id
    block keeps its minor dimension at C < d, so no replicated 2-D operand
    with a tracked leading dim reappears in the audit.
    """
    B = ids.shape[0]
    k_loc = D_loc.shape[0]
    gids = _all_gather(ids, comm)                        # (R*B, C) s32
    loc = gids - coff
    own = (loc >= 0) & (loc < k_loc)
    rows = jnp.where(own[..., None],
                     D_loc[jnp.clip(loc, 0, k_loc - 1)], 0.0)
    rows = _psum(rows, comm)                             # (R*B, C, d)
    s = coff // k_loc
    return jax.lax.dynamic_slice_in_dim(rows, s * B, B, axis=0)


def _probe_sharded(xb, D_loc, cnt, coff, p, comm: _Comm):
    """Top-p probe against cluster-sharded centroids.

    Every shard only holds k_loc centroids, so the batch rows (not the
    centroids) travel: one transposed (d, R*B) row gather, then each shard
    ranks ALL gathered rows against its own cells on the RAW probe partials
    (``||c||² - 2 x·c``), and the per-shard top-min(p, k_loc) partials are
    exchanged in the (L, R*B) layout and merged with the same first-minimum
    tie-break the probe kernels use.  Since every shard surfaces its
    min(p, k_loc) best cells for every row, the union provably contains the
    global top-p; blocks are disjoint, so no id appears twice.
    """
    k = cnt.shape[0]
    B = xb.shape[0]
    k_loc = D_loc.shape[0]
    s = coff // k_loc
    xa = _gather_minor(xb.T, comm).T                     # (R*B, d)
    cnt_loc = jax.lax.dynamic_slice(cnt, (coff,), (k_loc,))
    C_loc = D_loc / jnp.maximum(cnt_loc, 1.0)[:, None]
    csq = jnp.sum(C_loc * C_loc, axis=-1)
    part = csq[None, :] - 2.0 * jnp.matmul(           # (R*B, k_loc)
        xa, C_loc.T, precision=kref.HIGHEST)
    ids0 = jnp.broadcast_to(coff + jnp.arange(k_loc, dtype=jnp.int32),
                            part.shape)
    d_l, i_l = kref.stable_topk(part, ids0, min(p, k_loc))
    gd = _all_gather(d_l.T, comm)                        # (R*p_loc, R*B)
    gi = _all_gather(i_l.T, comm)
    # first-min merge in the transposed layout (leading dim R*p_loc stays
    # out of the audit's tracked roles); rank rows are shard-major just
    # like a stable_topk over the concatenated candidate list would see
    col = jnp.arange(gd.shape[1])
    outs = []
    for _ in range(min(p, k)):
        j = jnp.argmin(gd, axis=0)                       # (R*B,) first-min
        outs.append(gi[j, col])
        gd = gd.at[j, col].set(jnp.inf)
    sel_all = jnp.stack(outs, axis=1)                    # (R*B, min(p, k))
    return jax.lax.dynamic_slice_in_dim(sel_all, s * B, B, axis=0)


def _dense_block_scores(xa, ua, D_blk, cnt, coff_blk, mode):
    """Per-block partial dense scores -> block-best (value, global id) rows.

    Shared VERBATIM by the mesh (each shard scores the gathered rows
    against its own block) and the single-device emulation (loop over the
    R blocks), so the merged first-max/min over the stacked per-block bests
    sees bitwise-identical operands in both topologies.
    """
    k_loc = D_blk.shape[0]
    ids_loc = coff_blk + jnp.arange(k_loc, dtype=jnp.int32)
    dsq = jnp.sum(D_blk * D_blk, axis=-1)                # (k_loc,)
    dots = jnp.matmul(xa, D_blk.T, precision=kref.HIGHEST)  # (R*B, k_loc)
    xsq = jnp.sum(xa * xa, axis=-1)
    nv = cnt[ids_loc][None, :]
    is_self = ids_loc[None, :] == ua[:, None]
    if mode == "bkm":
        gain_v = ((dsq[None, :] + 2.0 * dots + xsq[:, None]) / (nv + 1.0)
                  - jnp.where(nv > 0, dsq[None, :] / jnp.maximum(nv, 1.0),
                              0.0))
        part = jnp.where(is_self, -jnp.inf, gain_v)
        bi = jnp.argmax(part, 1)
    else:
        csq_n = jnp.maximum(nv, 1.0)
        d2 = dsq[None, :] / (csq_n * csq_n) - 2.0 * dots / csq_n
        part = jnp.where(nv > 0, d2, jnp.inf)
        bi = jnp.argmin(part, 1)
    bv = jnp.take_along_axis(part, bi[:, None], 1)[:, 0]
    return bv, ids_loc[bi].astype(jnp.int32)


def _dense_moved_bkm(xb, u, Du, cnt, gain, eps):
    """bkm acceptance test from the merged best gain + the row's own-cluster
    terms (constant per row, hence argmax-invariant — only this eps test
    needs them)."""
    du_sq = jnp.sum(Du * Du, axis=-1)
    x_du = jnp.sum(xb * Du, axis=-1)
    xsq = jnp.sum(xb * xb, axis=-1)
    nu = cnt[u]
    num_u = du_sq - 2.0 * x_du + xsq
    resid = jnp.where(nu > 1, num_u / jnp.maximum(nu - 1.0, 1.0), 0.0)
    return (gain + resid - du_sq / jnp.maximum(nu, 1.0)) > eps


def _score_dense_sharded(xb, u, D_loc, cnt, mode, eps, coff, comm: _Comm):
    """Dense all-k scoring with cluster-sharded centroids.

    The batch rows travel instead of the centroids: one transposed
    (d, R*B) row gather, each shard scores EVERY gathered row against its
    own k_loc block, and only the per-shard best (score, id) pairs are
    exchanged — O(R²·B) wire instead of the (k, d) all-gather.  First-max
    (min for lloyd) over the shard axis after a first-max within each block
    reproduces the single-device lowest-index tie-break, because shards own
    ascending contiguous cluster blocks.
    """
    B = xb.shape[0]
    k_loc = D_loc.shape[0]
    s = coff // k_loc
    xa = _gather_minor(xb.T, comm).T                     # (R*B, d)
    ua = _all_gather(u, comm)                            # (R*B,)
    bv, bid = _dense_block_scores(xa, ua, D_loc, cnt, coff, mode)
    gbv = _gather_stacked(bv, comm)                      # (R, R*B)
    gbi = _gather_stacked(bid, comm)
    pick = (jnp.argmax if mode == "bkm" else jnp.argmin)(gbv, axis=0)
    best_all = jnp.take_along_axis(gbi, pick[None], 0)[0].astype(jnp.int32)
    best = jax.lax.dynamic_slice_in_dim(best_all, s * B, B)
    if mode == "bkm":
        gain_all = jnp.take_along_axis(gbv, pick[None], 0)[0]
        gain = jax.lax.dynamic_slice_in_dim(gain_all, s * B, B)
        Du = _exchange_rows(u[:, None], D_loc, coff, comm)[:, 0]
        moved = _dense_moved_bkm(xb, u, Du, cnt, gain, eps)
    else:
        moved = best != u
    return moved, best


def _score_dense_emulated(xb, u, D, cnt, mode, eps, R):
    """Single-device mirror of ``_score_dense_sharded`` over the whole
    concatenated batch: same per-block partial shapes, same stacked merge,
    and the owned-row psum exchange collapses to a plain ``D[u]`` gather —
    bitwise-equal decisions (the cross-topology parity contract)."""
    k = cnt.shape[0]
    assert k % R == 0
    k_loc = k // R
    outs = [_dense_block_scores(xb, u, D[t * k_loc:(t + 1) * k_loc], cnt,
                                t * k_loc, mode) for t in range(R)]
    gbv = jnp.stack([o[0] for o in outs])                # (R, R*B)
    gbi = jnp.stack([o[1] for o in outs])
    pick = (jnp.argmax if mode == "bkm" else jnp.argmin)(gbv, axis=0)
    best = jnp.take_along_axis(gbi, pick[None], 0)[0].astype(jnp.int32)
    if mode == "bkm":
        gain = jnp.take_along_axis(gbv, pick[None], 0)[0]
        moved = _dense_moved_bkm(xb, u, D[u], cnt, gain, eps)
    else:
        moved = best != u
    return moved, best


def _score_sharded(xb, u, idx, lookup, D_loc, cnt, source, cfg, comm, coff):
    """Scoring inside the mesh: sharded D, candidate-row exchange."""
    if source.kind == "dense":
        return _score_dense_sharded(xb, u, D_loc, cnt, cfg.mode, cfg.eps,
                                    coff, comm)
    if source.kind == "graph":
        cand = lookup[source.G[idx]]
    else:
        cand = _probe_sharded(xb, D_loc, cnt, coff, source.p, comm)
    cand_u = jnp.concatenate([cand, u[:, None]], axis=1)
    rows = _exchange_rows(cand_u, D_loc, coff, comm)
    return _score_from_rows(xb, u, cand_u, rows, cnt, cfg.mode, cfg.eps)


def _score_local(xb, u, idx, lookup, D, cnt, source, cfg):
    """Scoring with the full (k, d) D on one device (incl. R-way emulation)."""
    with layer_scope("engine", "candidates"):
        cand = _candidates(source, xb, u, idx, lookup, D, cnt, cfg.force)
    if cand is None:
        return _score_dense(xb, u, D, cnt, cfg.mode, cfg.eps)
    if cfg.shards > 1 and source.kind == "graph":
        # mirror the mesh's candidate-row-exchange scoring bit-exactly: the
        # psum of owner-masked contributions reduces to this plain gather
        cand_u = jnp.concatenate([cand, u[:, None]], axis=1)
        return _score_from_rows(xb, u, cand_u, D[cand_u], cnt, cfg.mode,
                                cfg.eps)
    return _score_gathered(xb, u, cand, D, cnt, cfg.mode, cfg.eps,
                           cfg.force)


def _move_step(X, assign, D, cnt, moves, idx, lookup, source, cfg, comm,
               coff=None, valid=None):
    """One batched candidate->score->move step (both topologies).

    idx indexes rows of the *local* X/assign; `lookup` is the (global)
    assignment snapshot used for candidate lookup.  `comm` carries the
    shard_map collective hooks; None means single device, where
    ``cfg.sparse_updates`` / ``cfg.payload_bf16`` reproduce the sharded
    sparse path's arithmetic exactly (same scatter over the same row order).
    Under ``comm`` the centroid statistics arrive cluster-sharded: ``D`` is
    this shard's (k_loc, d) block of composite vectors (global rows
    [coff, coff + k_loc)) while ``cnt`` stays the full replicated (k,) —
    1-D, so it never re-enters the replication audit — which keeps the
    leaver guard and every ``cnt[...]`` lookup topology-agnostic.  ``valid``
    masks padded rows (rows >= n) out of proposals, stats and telemetry.
    """
    k = cnt.shape[0]
    with layer_scope("engine", "candidates"):
        xb = X[idx].astype(jnp.float32)
        u = assign[idx]

    if comm is not None:
        moved, want_v = _score_sharded(xb, u, idx, lookup, D, cnt, source,
                                       cfg, comm, coff)
    elif cfg.shards > 1 and source.kind == "dense":
        # the mesh gathers all R shards' batch rows and block-merges, so the
        # emulation scores the whole concatenated batch at once in the same
        # (R*B, k_loc)-blocked shapes
        moved, want_v = _score_dense_emulated(xb, u, D, cnt, cfg.mode,
                                              cfg.eps, cfg.shards)
    elif cfg.shards > 1:
        # score per emulated shard with the sharded program's exact (bs, C)
        # shapes: XLA reductions are only bitwise-reproducible at equal
        # shapes, and the all-or-nothing leaver guard amplifies a single
        # flipped borderline proposal into a whole-cluster divergence
        R, bs = cfg.shards, idx.shape[0] // cfg.shards
        parts = [_score_local(xb[s * bs:(s + 1) * bs],
                              u[s * bs:(s + 1) * bs],
                              idx[s * bs:(s + 1) * bs], lookup, D, cnt,
                              source, cfg) for s in range(R)]
        moved = jnp.concatenate([p[0] for p in parts])
        want_v = jnp.concatenate([p[1] for p in parts])
    else:
        moved, want_v = _score_local(xb, u, idx, lookup, D, cnt, source,
                                     cfg)

    with layer_scope("engine", "move"):
        if valid is not None:
            moved = moved & valid[idx]

        # proposed moves BEFORE the leaver guard (telemetry: the guard's vetoes
        # are `proposed - moves`); None when disabled so it compiles away.
        prop = jnp.sum(moved, dtype=jnp.int32) if cfg.telemetry else None

        if comm is not None and cfg.sparse_updates:
            # gather every replica's proposed moves, then apply the leaver
            # guard + scatter locally — identical on all replicas, O(R*B*d)
            # wire bytes instead of the dense O(k*d) psum (§Perf).
            gx = xb * moved.astype(jnp.float32)[:, None]
            if cfg.payload_bf16:
                # §Perf C3: halve move-payload wire bytes.  The bitcast to
                # u16 keeps XLA's algebraic simplifier from hoisting the f32
                # convert back across the all-gather.
                gx = jax.lax.bitcast_convert_type(
                    gx.astype(jnp.bfloat16), jnp.uint16)
            gu, gv = u, jnp.where(moved, want_v, u)
            gx = _all_gather(gx, comm)
            gu = _all_gather(gu, comm)
            gv = _all_gather(gv, comm)
            if cfg.payload_bf16:
                gx = jax.lax.bitcast_convert_type(gx, jnp.bfloat16)
            gx = gx.astype(jnp.float32)
            gw = (gu != gv).astype(jnp.float32)
            leav = jax.ops.segment_sum(gw, gu, num_segments=k)
            ok = (cnt - leav) >= 1.0
            gv = jnp.where(ok[gu], gv, gu)               # veto unsafe moves
            gx = gx * (gu != gv).astype(jnp.float32)[:, None]
            gw2 = (gu != gv).astype(jnp.float32)
            # scatter only the rows this shard owns into its D block; cnt is
            # replicated, so its pair of (k,) scatters runs identically
            # everywhere.  Same adds in the same gathered-row order as the
            # emulation's fused full-D scatter, hence bitwise-equal blocks.
            # Non-owned rows route to the out-of-range sentinel k_loc (negative
            # indices would WRAP before the drop-mode bounds check).
            k_loc = D.shape[0]
            iu, iv = gu - coff, gv - coff
            iu = jnp.where((iu >= 0) & (iu < k_loc), iu, k_loc)
            iv = jnp.where((iv >= 0) & (iv < k_loc), iv, k_loc)
            D = D.at[iu].add(-gx, mode="drop").at[iv].add(gx, mode="drop")
            cnt = cnt.at[gu].add(-gw2).at[gv].add(gw2)
            moved = moved & ok[u]
            v = jnp.where(moved, want_v, u)
        elif comm is not None:
            # dense statistics sync: global leaver guard + delta psum in the
            # transposed (d, k) layout — same adds in the same order as the
            # (k, d) scatter (bitwise-equal transposed), but the replicated
            # all-reduce operand leads with d, which the audit does not track
            leav = jax.ops.segment_sum(moved.astype(jnp.float32), u,
                                       num_segments=k)
            leav = _psum(leav, comm)
            moved = moved & ((cnt - leav) >= 1.0)[u]
            v = jnp.where(moved, want_v, u)
            w = moved.astype(jnp.float32)[:, None]
            k_loc = D.shape[0]
            gxT = (xb * w).T                                 # (d, B)
            dD_T = (jnp.zeros((D.shape[1], k), jnp.float32)
                    .at[:, u].add(-gxT).at[:, v].add(gxT))
            dD_T = _psum(dD_T, comm)
            dc = jnp.zeros_like(cnt).at[u].add(-w[:, 0]).at[v].add(w[:, 0])
            D = D + jax.lax.dynamic_slice(dD_T, (0, coff),
                                          (D.shape[1], k_loc)).T
            cnt = cnt + _psum(dc, comm)
        else:
            # single device.  The guard blocks all leavers of any cluster whose
            # leaver count would reach its population (conservative, rare).
            leav = jax.ops.segment_sum(moved.astype(jnp.float32), u,
                                       num_segments=k)
            moved = moved & ((cnt - leav) >= 1.0)[u]
            v = jnp.where(moved, want_v, u)
            gx = xb * moved.astype(jnp.float32)[:, None]
            if cfg.payload_bf16 and cfg.sparse_updates:
                gx = gx.astype(jnp.bfloat16).astype(jnp.float32)
            if cfg.shards > 1 and not cfg.sparse_updates:
                # mirror the dense-psum arithmetic: per-shard partial deltas,
                # then a sequential device-order sum (matches the all-reduce up
                # to its backend-defined fp ordering — assignments and counts
                # stay exact, D to ~1 ulp; the parity test pins all three)
                R = cfg.shards
                bs = idx.shape[0] // R
                dD_tot, dc_tot = None, None
                for s in range(R):
                    sl = slice(s * bs, (s + 1) * bs)
                    us, vs, gs = u[sl], v[sl], gx[sl]
                    ms = (us != vs).astype(jnp.float32)
                    dDs = jnp.zeros_like(D).at[us].add(-gs).at[vs].add(gs)
                    dcs = jnp.zeros_like(cnt).at[us].add(-ms).at[vs].add(ms)
                    dD_tot = dDs if s == 0 else dD_tot + dDs
                    dc_tot = dcs if s == 0 else dc_tot + dcs
                D = D + dD_tot
                cnt = cnt + dc_tot
            else:
                gw = (u != v).astype(jnp.float32)
                D, cnt = _scatter_moves(D, cnt, u, v, gx, gw)

        assign = assign.at[idx].set(v.astype(jnp.int32))
        moves = moves + jnp.sum(moved, dtype=jnp.int32)
    return assign, D, cnt, moves, prop


# ---------------------------------------------------------------------------
# single-device epochs and the device-resident run
# ---------------------------------------------------------------------------

def _epoch_impl(X, state: BKMState, source: CandidateSource, key,
                cfg: EngineConfig, valid=None):
    """One epoch; returns (BKMState, prop) where prop is the epoch's total
    pre-guard proposed moves (None unless ``cfg.telemetry``).  ``valid``
    (optional (n,) bool) masks padded rows out of moves and stats."""
    n = X.shape[0]
    R = cfg.shards
    n_loc = n // R
    bs = min(cfg.batch_size, n_loc)
    nb = max(n_loc // bs, 1)
    # the sharded epoch's visit order exactly: one shared local permutation,
    # shard s owning the contiguous rows [s*n_loc, (s+1)*n_loc)
    order_loc = permute.epoch_order(key, n_loc)
    orders = order_loc[None, :] + (jnp.arange(R, dtype=jnp.int32)
                                   * n_loc)[:, None]
    lookup = state.assign      # candidate lookup: epoch-start snapshot
    state = state._replace(moves=jnp.zeros((), jnp.int32))
    prop0 = jnp.zeros((), jnp.int32) if cfg.telemetry else None

    def body(i, carry):
        st, prop = carry
        idx = jax.lax.dynamic_slice(orders, (0, i * bs), (R, bs)).reshape(-1)
        assign, D, cnt, moves, p = _move_step(
            X, st.assign, st.D, st.cnt, st.moves, idx, lookup, source, cfg,
            None, valid=valid)
        if prop is not None:
            prop = prop + p
        return BKMState(assign, D, cnt, moves), prop

    return jax.lax.fori_loop(0, nb, body, (state, prop0))


@functools.partial(jax.jit, static_argnums=(4,))
def epoch(X: jax.Array, state: BKMState, source: CandidateSource,
          key: jax.Array, cfg: EngineConfig = EngineConfig(),
          valid=None) -> BKMState:
    """One engine pass over (a shuffled view of) the data in mini-batches.

    Visits n // batch_size * batch_size samples (the remainder is covered by
    reshuffling across epochs).  The candidate lookup table is the
    epoch-start assignment (refreshing it per batch is a HBM round-trip per
    step; staleness within one epoch matches the sharded semantics).
    """
    return _epoch_impl(X, state, source, key, cfg, valid)[0]


def epoch_inline(X: jax.Array, state: BKMState, source: CandidateSource,
                 key: jax.Array, cfg: EngineConfig = EngineConfig(),
                 valid=None) -> BKMState:
    """``epoch`` without the jit wrapper — for composition inside an outer
    trace.  The graph builder (``core.graph_build``) runs its guided pass
    through this inside the device-resident tau-round scan; semantics are
    identical to ``epoch`` (including the ``cfg.shards`` R-way emulation
    used by the topology-parity tests)."""
    return _epoch_impl(X, state, source, key, cfg, valid)[0]


def stats_distortion(xsq_total, D, cnt, n) -> jax.Array:
    """Distortion in O(k·d) from the running statistics (paper Eqn. 2/4)."""
    dsq = jnp.sum(D * D, axis=-1)
    objective = jnp.sum(jnp.where(cnt > 0, dsq / jnp.maximum(cnt, 1.0), 0.0))
    return (xsq_total - objective) / n


def _stats_distortion_sharded(xsq_total, D_loc, cnt, n, coff, comm: _Comm):
    """``stats_distortion`` with cluster-sharded D: psum of the per-block
    partial objective (O(k_loc·d) per shard, O(1) wire)."""
    k_loc = D_loc.shape[0]
    cnt_loc = jax.lax.dynamic_slice(cnt, (coff,), (k_loc,))
    dsq = jnp.sum(D_loc * D_loc, axis=-1)
    obj = jnp.sum(jnp.where(cnt_loc > 0, dsq / jnp.maximum(cnt_loc, 1.0),
                            0.0))
    return (xsq_total - _psum(obj, comm)) / n


def _epoch_telemetry(tel, t, st, prop, dist):
    """File one epoch's engine slots at row t (None tel passes through)."""
    if tel is None:
        return None
    hit = st.moves.astype(jnp.float32) / jnp.maximum(
        prop.astype(jnp.float32), 1.0)
    return obs_tel.record(tel, t, moves=st.moves, proposed=prop,
                          empty_clusters=jnp.sum(st.cnt <= 0.0,
                                                 dtype=jnp.int32),
                          distortion=dist, hit_rate=hit)


def _run_impl(X, state, source, key, cfg, valid=None):
    if valid is None:
        n = X.shape[0]
        xsq_total = jnp.sum(jnp.square(X.astype(jnp.float32)))  # hoisted once
    else:
        vf = valid.astype(jnp.float32)
        n = jnp.sum(vf)
        xsq_total = jnp.sum(jnp.square(X.astype(jnp.float32) * vf[:, None]))
    hist0 = jnp.full((cfg.iters,), jnp.nan, jnp.float32)
    mhist0 = jnp.zeros((cfg.iters,), jnp.int32)
    tel0 = obs_tel.init(cfg.iters) if cfg.telemetry else None
    thresh = cfg.min_move_frac * n
    if cfg.iters == 0:     # static: a 0-length hist cannot be .at[t]-traced
        return (state, hist0, mhist0, jnp.zeros((), jnp.int32),
                stats_distortion(xsq_total, state.D, state.cnt, n), tel0)

    def cond(carry):
        t, _, _, _, _, done = carry
        return (t < cfg.iters) & ~done

    def body(carry):
        t, st, hist, mhist, tel, _ = carry
        st, prop = _epoch_impl(X, st, source, jax.random.fold_in(key, t),
                               cfg, valid)
        dist = stats_distortion(xsq_total, st.D, st.cnt, n)
        hist = hist.at[t].set(dist)
        mhist = mhist.at[t].set(st.moves)
        tel = _epoch_telemetry(tel, t, st, prop, dist)
        done = st.moves <= thresh
        return t + 1, st, hist, mhist, tel, done

    t, st, hist, mhist, tel, _ = jax.lax.while_loop(
        cond, body,
        (jnp.zeros((), jnp.int32), state, hist0, mhist0, tel0,
         jnp.zeros((), bool)))
    final = stats_distortion(xsq_total, st.D, st.cnt, n)
    return st, hist, mhist, t, final, tel


@functools.partial(jax.jit, static_argnums=(4,))
def run(X: jax.Array, state: BKMState, source: CandidateSource,
        key: jax.Array, cfg: EngineConfig, valid=None
        ) -> Tuple[BKMState, jax.Array, jax.Array, jax.Array, jax.Array,
                   Optional[obs_tel.Telemetry]]:
    """Device-resident multi-epoch run.

    Returns (state, hist (iters,) f32 per-epoch distortion (NaN past the
    early stop), mhist (iters,) int32 per-epoch accepted moves, epochs ()
    int32 actually executed, final () f32 distortion, tel).  ``tel`` is a
    per-epoch ``obs.telemetry.Telemetry`` when ``cfg.telemetry`` (slots:
    moves, proposed, empty_clusters, distortion, hit_rate — rows past the
    early stop stay 0) and None otherwise; being accumulated inside the
    while_loop it returns in the SAME host sync as the state.  The whole
    loop — including the ``min_move_frac`` early stop and the per-epoch
    distortion — runs inside one trace: callers pay one host sync per run,
    not one per epoch.  The state is not donated: it is O(n + k·d), and the
    caller keeps its input state.
    """
    return _run_impl(X, state, source, key, cfg, valid)


# ---------------------------------------------------------------------------
# sharded epoch body (wrapped in shard_map by core.distributed)
# ---------------------------------------------------------------------------

def sharded_epoch_body(X, source: CandidateSource, assign, D, cnt, key, *,
                       cfg: EngineConfig, data_axes: Tuple[str, ...],
                       coff, valid=None):
    """One epoch inside shard_map: X/G/assign row-sharded, D cluster-sharded.

    ``D`` is this shard's (k_loc, d) block of composite vectors — global
    cluster rows [coff, coff + k_loc) — while ``cnt`` stays the replicated
    (k,).  ``coff`` must be data-derived (e.g. the first element of a
    sharded ``arange(k)``), never ``axis_index`` (XLA:CPU forced-host
    partitioning hazard).  ``valid`` is the optional (n_loc,) padded-row
    mask.

    Returns (assign, D, cnt, moves, prop) — ``moves``/``prop`` are psum'd
    global accepted/pre-guard-proposed counts (``prop`` is None unless
    ``cfg.telemetry``).  Shares ``_move_step`` with the
    single-device ``epoch`` — the per-shard visit order and the collective
    hooks are the only topology-specific pieces.

    All shards use ONE shared permutation of their local row indices per
    epoch.  Shards hold disjoint rows, so distinct per-shard orders buy no
    extra randomness — and a shard-index-dependent order is deliberately
    avoided: a per-device value whose only consumer is a collective-bearing
    loop body is unreliably partitioned by some backends (XLA:CPU with
    forced host devices silently collapses it to partition 0's buffer),
    which would make the visit order backend-dependent.
    """
    comm = _Comm(data_axes)
    n_loc = X.shape[0]
    bs = min(cfg.batch_size, n_loc)
    nb = max(n_loc // bs, 1)
    # candidate lookup table: global assignment, stale within the epoch
    lookup = _all_gather(assign, comm)
    order = permute.epoch_order(key, n_loc)

    prop0 = jnp.zeros((), jnp.int32) if cfg.telemetry else None

    def body(i, carry):
        assign_l, D, cnt, moves, prop = carry
        idx = jax.lax.dynamic_slice(order, (i * bs,), (bs,))
        assign_l, D, cnt, moves, p = _move_step(
            X, assign_l, D, cnt, moves, idx, lookup, source, cfg, comm,
            coff=coff, valid=valid)
        if prop is not None:
            prop = prop + p
        return assign_l, D, cnt, moves, prop

    assign, D, cnt, moves, prop = jax.lax.fori_loop(
        0, nb, body, (assign, D, cnt, jnp.zeros((), jnp.int32), prop0))
    return (assign, D, cnt, _psum(moves, comm),
            None if prop is None else _psum(prop, comm))


def sharded_run_body(X, source: CandidateSource, assign, D, cnt, key, *,
                     cfg: EngineConfig, data_axes: Tuple[str, ...],
                     coff, valid=None):
    """The full multi-epoch run inside ONE shard_map trace over the mesh.

    The sharded twin of ``_run_impl``: a ``lax.while_loop`` over epochs with
    ``sharded_epoch_body`` as the body, per-epoch distortion in O(k_loc·d)
    per shard from the cluster-sharded running statistics (the global
    ``sum||x||²`` term psum'd once and hoisted out of the loop), move
    history, and the
    ``min_move_frac`` early stop — all in-trace, so a run costs one host
    sync across the whole mesh instead of one per epoch.

    Returns (assign (n_loc,), D, cnt, hist (iters,) f32 — NaN past the early
    stop, mhist (iters,) int32 global accepted moves, epochs () int32,
    final () f32 distortion, tel).  ``tel`` is a replicated per-epoch
    ``Telemetry`` when ``cfg.telemetry`` (globals via psum — identical on
    all shards) and None otherwise; it rides the same single host sync.
    ``core.distributed.ShardedEngine`` wraps this
    in shard_map; parity with the single-device ``run(..., shards=R)``
    emulation is bit-exact in ``sparse_updates`` mode (same per-epoch
    ``fold_in`` key schedule, same visit order, same scatter arithmetic).
    """
    comm = _Comm(tuple(data_axes))
    if valid is None:
        n = _psum(jnp.asarray(X.shape[0], jnp.float32), comm)
        xsq_total = _psum(jnp.sum(jnp.square(X.astype(jnp.float32))), comm)
    else:
        vf = valid.astype(jnp.float32)
        n = _psum(jnp.sum(vf), comm)
        xsq_total = _psum(
            jnp.sum(jnp.square(X.astype(jnp.float32) * vf[:, None])), comm)
    hist0 = jnp.full((cfg.iters,), jnp.nan, jnp.float32)
    mhist0 = jnp.zeros((cfg.iters,), jnp.int32)
    tel0 = obs_tel.init(cfg.iters) if cfg.telemetry else None
    thresh = cfg.min_move_frac * n
    if cfg.iters == 0:     # static: a 0-length hist cannot be .at[t]-traced
        return (assign, D, cnt, hist0, mhist0, jnp.zeros((), jnp.int32),
                _stats_distortion_sharded(xsq_total, D, cnt, n, coff, comm),
                tel0)

    def cond(carry):
        t, _, _, _, _, _, _, done = carry
        return (t < cfg.iters) & ~done

    def body(carry):
        t, assign_l, D_, cnt_, hist, mhist, tel, _ = carry
        assign_l, D_, cnt_, moves, prop = sharded_epoch_body(
            X, source, assign_l, D_, cnt_, jax.random.fold_in(key, t),
            cfg=cfg, data_axes=data_axes, coff=coff, valid=valid)
        dist = _stats_distortion_sharded(xsq_total, D_, cnt_, n, coff, comm)
        hist = hist.at[t].set(dist)
        mhist = mhist.at[t].set(moves)
        if tel is not None:
            st = BKMState(assign_l, D_, cnt_, moves)
            tel = _epoch_telemetry(tel, t, st, prop, dist)
        done = moves.astype(jnp.float32) <= thresh
        return t + 1, assign_l, D_, cnt_, hist, mhist, tel, done

    t, assign, D, cnt, hist, mhist, tel, _ = jax.lax.while_loop(
        cond, body,
        (jnp.zeros((), jnp.int32), assign, D, cnt, hist0, mhist0, tel0,
         jnp.zeros((), bool)))
    final = _stats_distortion_sharded(xsq_total, D, cnt, n, coff, comm)
    return assign, D, cnt, hist, mhist, t, final, tel
