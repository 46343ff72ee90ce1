"""Clustering objectives for boost k-means / GK-means.

The boost k-means objective (paper Eqn. 2) is

    I = sum_r  ||D_r||^2 / n_r,      D_r = sum_{x in S_r} x

and the k-means distortion (paper Eqn. 4) relates to it via

    sum_i ||x_i - C_{a_i}||^2 = sum_i ||x_i||^2 - I,

so maximising I is exactly minimising distortion.  All statistics are kept in
float32 regardless of the input dtype.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import HIGHEST


class ClusterStats(NamedTuple):
    """Sufficient statistics of a clustering: composite vectors + counts."""

    D: jax.Array  # (k, d) float32, D_r = sum of members
    cnt: jax.Array  # (k,) float32, n_r


def cluster_stats(X: jax.Array, assign: jax.Array, k: int) -> ClusterStats:
    """Compute (D, cnt) from an assignment vector."""
    Xf = X.astype(jnp.float32)
    D = jax.ops.segment_sum(Xf, assign, num_segments=k)
    cnt = jax.ops.segment_sum(jnp.ones((X.shape[0],), jnp.float32), assign,
                              num_segments=k)
    return ClusterStats(D, cnt)


def centroids(stats: ClusterStats) -> jax.Array:
    """C_r = D_r / n_r (zero for empty clusters)."""
    safe = jnp.maximum(stats.cnt, 1.0)
    return stats.D / safe[:, None]


def objective_I(stats: ClusterStats) -> jax.Array:
    """Boost k-means objective I = sum_r ||D_r||^2 / n_r."""
    sq = jnp.sum(stats.D * stats.D, axis=-1)
    safe = jnp.maximum(stats.cnt, 1.0)
    return jnp.sum(jnp.where(stats.cnt > 0, sq / safe, 0.0))


def distortion(X: jax.Array, assign: jax.Array, k: int) -> jax.Array:
    """Average distortion E (paper Eqn. 4) = (sum ||x||^2 - I) / n."""
    stats = cluster_stats(X, assign, k)
    xsq = jnp.sum(X.astype(jnp.float32) ** 2)
    n = X.shape[0]
    return (xsq - objective_I(stats)) / n


def delta_I(
    x: jax.Array,          # (..., d) sample(s)
    D_u: jax.Array,        # (..., d) composite vector of source cluster
    n_u: jax.Array,        # (...,)   count of source cluster
    D_v: jax.Array,        # (..., C, d) composite vectors of candidate targets
    n_v: jax.Array,        # (..., C) counts of candidate targets
) -> jax.Array:
    """Paper Eqn. 3: objective change when moving x from cluster u to v.

    Returns (..., C).  If n_u == 1 the source cluster empties and its residual
    term ||D_u - x||^2/(n_u - 1) is defined as 0.
    """
    x = x.astype(jnp.float32)
    D_u = D_u.astype(jnp.float32)
    D_v = D_v.astype(jnp.float32)
    xsq = jnp.sum(x * x, axis=-1)                      # (...,)
    du_sq = jnp.sum(D_u * D_u, axis=-1)                # (...,)
    dv_sq = jnp.sum(D_v * D_v, axis=-1)                # (..., C)
    x_du = jnp.sum(x * D_u, axis=-1)                   # (...,)
    x_dv = jnp.sum(x[..., None, :] * D_v, axis=-1)     # (..., C)

    # target gain: ||D_v + x||^2/(n_v+1) - ||D_v||^2/n_v
    nv_safe = jnp.maximum(n_v, 1.0)
    gain_v = (dv_sq + 2.0 * x_dv + xsq[..., None]) / (n_v + 1.0)
    gain_v = gain_v - jnp.where(n_v > 0, dv_sq / nv_safe, 0.0)

    # source loss: ||D_u - x||^2/(n_u-1) - ||D_u||^2/n_u
    num_u = du_sq - 2.0 * x_du + xsq
    den_u = jnp.maximum(n_u - 1.0, 1.0)
    resid = jnp.where(n_u > 1, num_u / den_u, 0.0)
    loss_u = resid - du_sq / jnp.maximum(n_u, 1.0)

    return gain_v + loss_u[..., None]


def delta_I_brute(X: jax.Array, assign: jax.Array, k: int, i: int,
                  v: int) -> jax.Array:
    """Oracle: I(after moving sample i to cluster v) - I(before).

    O(n) recomputation; used only by tests to validate ``delta_I``.
    """
    s0 = cluster_stats(X, assign, k)
    new_assign = assign.at[i].set(v)
    s1 = cluster_stats(X, new_assign, k)
    return objective_I(s1) - objective_I(s0)


@functools.partial(jax.jit, static_argnums=(2,))
def assignment_distortion(X: jax.Array, C: jax.Array, block: int = 2048
                          ) -> Tuple[jax.Array, jax.Array]:
    """Exact nearest-centroid assignment + distortion, blocked over samples.

    Reference implementation (the kernels package has the fused version).
    Returns (assign (n,), mean distortion).
    """
    n = X.shape[0]
    csq = jnp.sum(C.astype(jnp.float32) ** 2, axis=-1)

    def body(xb):
        dots = jnp.matmul(xb.astype(jnp.float32), C.astype(jnp.float32).T,
                          precision=HIGHEST)
        d2 = csq[None, :] - 2.0 * dots
        a = jnp.argmin(d2, axis=-1)
        best = jnp.min(d2, axis=-1) + jnp.sum(xb.astype(jnp.float32) ** 2, -1)
        return a.astype(jnp.int32), best

    nb = max(1, n // block) if n % block == 0 else 1
    if n % block == 0 and n > block:
        a, best = jax.lax.map(body, X.reshape(nb, block, -1))
        a, best = a.reshape(n), best.reshape(n)
    else:
        a, best = body(X)
    return a, jnp.mean(jnp.maximum(best, 0.0))
