"""Graph-quality metrics: brute-force ground truth + recall (paper §5.1)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ref import HIGHEST


@functools.partial(jax.jit, static_argnums=(1, 2))
def brute_force_knn(X: jax.Array, kappa: int, chunk: int = 1024) -> jax.Array:
    """Exact top-kappa neighbour ids (self excluded). O(n^2 d) — tests only."""
    n, d = X.shape
    Xf = X.astype(jnp.float32)
    sq = jnp.sum(Xf * Xf, axis=-1)

    def body(args):
        xb, base = args
        d2 = (jnp.sum(xb * xb, -1)[:, None] + sq[None, :]
              - 2.0 * jnp.matmul(xb, Xf.T, precision=HIGHEST))  # (c, n)
        own = base + jnp.arange(xb.shape[0])
        d2 = d2.at[jnp.arange(xb.shape[0]), own].set(jnp.inf)
        _, ids = jax.lax.top_k(-d2, kappa)
        return ids.astype(jnp.int32)

    if n % chunk == 0 and n > chunk:
        ids = jax.lax.map(body, (Xf.reshape(n // chunk, chunk, d),
                                 jnp.arange(0, n, chunk)))
        return ids.reshape(n, kappa)
    return body((Xf, jnp.zeros((), jnp.int32)))


def recall_top1(ids: jax.Array, gt: jax.Array) -> jax.Array:
    """Paper's metric: fraction of samples whose TRUE 1-NN appears anywhere
    in their kappa-list.  gt: (n, >=1) brute-force ids."""
    return jnp.mean(jnp.any(ids == gt[:, :1], axis=1).astype(jnp.float32))


def recall_at(ids: jax.Array, gt: jax.Array, at: int) -> jax.Array:
    """|top-at of graph ∩ top-at of truth| / at, averaged over samples."""
    hits = (ids[:, :at, None] == gt[:, None, :at]).any(-1)
    return jnp.mean(hits.astype(jnp.float32))


def cooccurrence_rate(assign: jax.Array, gt: jax.Array) -> jax.Array:
    """Fig. 1: P(sample and its j-th true NN share a cluster), per j.

    Returns (gt.shape[1],) rates."""
    return jnp.mean((assign[gt] == assign[:, None]).astype(jnp.float32),
                    axis=0)
