"""Clustered-KV attention — the paper's insight applied to LM serving.

GK-means' core idea: instead of comparing a sample against all k centroids,
compare only against the clusters its neighbours live in.  For long-context
decode the same structure applies: cluster the cached KEYS with the equal-size
2M tree (paper Alg. 1), score the query against the kc centroids, and attend
only to the members of the top-c clusters — O(c * xi) attended keys instead
of O(S).

Exactness degrades gracefully: softmax attention mass concentrates on
near-neighbour keys, which is precisely what the co-occurrence property
(paper Fig. 1) guarantees the selected clusters contain.  DESIGN.md §5 lists
which assigned architectures this applies to.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.knn_graph import members_table
from repro.core.two_means import two_means_tree

NEG_INF = jnp.float32(-1e30)


class KVClusters(NamedTuple):
    centroids: jax.Array  # (B, Hkv, kc, hd) float32
    table: jax.Array      # (B, Hkv, kc, cap) int32 member ids, -1 padded
    radii: jax.Array      # (B, Hkv, kc) float32 max ||k - centroid||


def _select_clusters(qs: jax.Array, clusters: KVClusters, top_c: int):
    """Top-c clusters per q head by the ball upper bound on member scores.

    q.k = q.c + q.(k-c) <= q.c + ||q||*r  (Cauchy-Schwarz), so ranking by
    q.c + ||q||*r never under-ranks a cluster that could hold a high-score
    key — the cluster-closure idea: a tight centroid score misses clusters
    whose few boundary keys still carry softmax mass.
    """
    cscore = jnp.einsum("bhgd,bhkd->bhgk", qs, clusters.centroids)
    bound = cscore + (jnp.linalg.norm(qs, axis=-1)[..., None]
                      * clusters.radii[:, :, None, :])
    _, top = jax.lax.top_k(bound, top_c)                  # (B, Hkv, G, c)
    return top


def build_kv_clusters(keys: jax.Array, kc: int, key: jax.Array,
                      cap_factor: int = 2, refine_epochs: int = 0,
                      refine_mode: str = "bkm") -> KVClusters:
    """Cluster cached keys per (batch, kv-head).

    keys: (B, S, Hkv, hd).  kc must be a power of two dividing S.

    refine_epochs > 0 polishes the equal-size 2M-tree partition with
    dense-candidate engine epochs (vmapped over the B*Hkv cache slices) —
    lower distortion per cluster at the cost of unequal sizes, so pick a
    ``cap_factor`` with headroom (clusters drifting past ``cap`` lose their
    overflow members from the attended candidate set).
    """
    B, S, H, hd = keys.shape
    cap = cap_factor * (S // kc)
    flat = keys.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    keys_r = jax.random.split(key, B * H)

    assign = jax.vmap(lambda x, k: two_means_tree(x, kc, k, refine_iters=2)
                      )(flat.astype(jnp.float32), keys_r)        # (BH, S)

    if refine_epochs:
        # the engine's device-resident run, vmapped over cache slices: same
        # per-epoch fold_in schedule as a host loop of epochs, whole loop in
        # one trace
        cfg = engine.EngineConfig(batch_size=min(1024, S), mode=refine_mode,
                                  iters=refine_epochs, min_move_frac=-1.0)
        source = engine.dense_source()

        def refine(x, a, kk):
            st, _, _, _, _, _ = engine.run(
                x, engine.init_state(x, a, kc), source, kk, cfg)
            return st.assign

        assign = jax.vmap(refine)(flat.astype(jnp.float32), assign, keys_r)

    def stats(x, a):
        D = jax.ops.segment_sum(x.astype(jnp.float32), a, num_segments=kc)
        n = jax.ops.segment_sum(jnp.ones((S,), jnp.float32), a,
                                num_segments=kc)
        cent = D / jnp.maximum(n, 1.0)[:, None]
        r = jnp.linalg.norm(x.astype(jnp.float32) - cent[a], axis=-1)
        return cent, jax.ops.segment_max(r, a, num_segments=kc)

    cent, radii = jax.vmap(stats)(flat, assign)                   # (BH, kc, .)
    table = jax.vmap(lambda a: members_table(a, kc, cap)[0])(assign)
    return KVClusters(cent.reshape(B, H, kc, hd),
                      table.reshape(B, H, kc, cap),
                      radii.reshape(B, H, kc))


@functools.partial(jax.jit, static_argnames=("top_c",))
def clustered_decode_attention(q: jax.Array, k_cache: jax.Array,
                               v_cache: jax.Array, clusters: KVClusters,
                               length: jax.Array, *, top_c: int = 4
                               ) -> jax.Array:
    """q: (B, 1, Hq, hd); caches: (B, S, Hkv, hd) -> (B, 1, Hq, hd).

    Attends only to members of the top_c clusters per kv head (group-summed
    query-centroid scores pick the clusters, GQA-aware).
    """
    B, _, Hq, hd = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = hd ** -0.5
    qs = (q.astype(jnp.float32) * scale).reshape(B, Hkv, G, hd)

    # per-q-head cluster selection (group-pooled selection washes out heads)
    top = _select_clusters(qs, clusters, top_c)           # (B, Hkv, G, c)

    # candidate key ids per q head: members of its selected clusters
    cap = clusters.table.shape[-1]
    tbl = clusters.table[:, :, None]                      # (B, Hkv, 1, kc, cap)
    cand = jnp.take_along_axis(
        jnp.broadcast_to(tbl, (B, Hkv, G) + tbl.shape[3:]),
        top[..., None], axis=3)                           # (B, Hkv, G, c, cap)
    cand = cand.reshape(B, Hkv, G, top_c * cap)
    valid = (cand >= 0) & (cand < length)
    cand_safe = jnp.maximum(cand, 0)

    # gather keys/values per q head: (B, Hkv, G, T, hd)
    bidx = jnp.arange(B)[:, None, None, None]
    hidx = jnp.arange(Hkv)[None, :, None, None]
    kg = k_cache[bidx, cand_safe, hidx]
    vg = v_cache[bidx, cand_safe, hidx]

    scores = jnp.einsum("bhgd,bhgtd->bhgt", qs, kg.astype(jnp.float32))
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgt,bhgtd->bhgd", p, vg.astype(jnp.float32))
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def candidate_recall(q, k_cache, clusters, length, top_c: int) -> jax.Array:
    """Diagnostic: fraction of (batch, q-head) whose TRUE max-score key is in
    the selected candidate set."""
    B, _, Hq, hd = q.shape
    _, S, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    qs = q.astype(jnp.float32).reshape(B, Hkv, G, hd)
    full = jnp.einsum("bhgd,bshd->bhgs", qs, k_cache.astype(jnp.float32))
    full = jnp.where((jnp.arange(S) < length)[None, None, None], full,
                     NEG_INF)
    best = jnp.argmax(full, axis=-1)                      # (B, Hkv, G)

    top = _select_clusters(qs, clusters, top_c)           # (B, Hkv, G, c)
    tbl = clusters.table[:, :, None]
    cand = jnp.take_along_axis(
        jnp.broadcast_to(tbl, top.shape[:3] + tbl.shape[3:]),
        top[..., None], axis=3)
    cand = cand.reshape(*top.shape[:3], -1)
    hit = jnp.any(cand == best[..., None], axis=-1)
    return jnp.mean(hit.astype(jnp.float32))
