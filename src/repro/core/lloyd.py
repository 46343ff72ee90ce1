"""Traditional k-means (Lloyd) and k-means++ seeding — quality baselines."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.kernels.ref import HIGHEST


def init_random(X: jax.Array, k: int, key: jax.Array) -> jax.Array:
    idx = jax.random.choice(key, X.shape[0], (k,), replace=False)
    return X[idx].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def init_kmeanspp(X: jax.Array, k: int, key: jax.Array) -> jax.Array:
    """k-means++ seeding (Arthur & Vassilvitskii) — sequential over k."""
    n, d = X.shape
    Xf = X.astype(jnp.float32)
    xsq = jnp.sum(Xf * Xf, axis=-1)
    first = jax.random.randint(key, (), 0, n)
    C = jnp.zeros((k, d), jnp.float32).at[0].set(Xf[first])
    d2 = (xsq + jnp.sum(Xf[first] ** 2)
          - 2.0 * jnp.matmul(Xf, Xf[first], precision=HIGHEST))
    d2 = jnp.maximum(d2, 0.0)

    def body(i, carry):
        C, d2 = carry
        kk = jax.random.fold_in(key, i)
        p = d2 / jnp.maximum(jnp.sum(d2), 1e-30)
        nxt = jax.random.choice(kk, n, p=p)
        c = Xf[nxt]
        C = C.at[i].set(c)
        nd = xsq + jnp.sum(c * c) - 2.0 * jnp.matmul(Xf, c, precision=HIGHEST)
        return C, jnp.minimum(d2, jnp.maximum(nd, 0.0))

    C, _ = jax.lax.fori_loop(1, k, body, (C, d2))
    return C


def lloyd(X: jax.Array, k: int, *, iters: int = 30, key: jax.Array,
          init: str = "kmeans++") -> Tuple[jax.Array, jax.Array, list]:
    """Full Lloyd iterations. Returns (assign, centroids, distortion history).

    Assignment uses the fused flash-argmin kernel path (kernels/ops.py).
    """
    n = X.shape[0]
    C = (init_kmeanspp(X, k, key) if init == "kmeans++"
         else init_random(X, k, key))
    hist = []
    assign = None
    for _ in range(iters):
        assign, d2 = kops.assign_centroids(X, C)
        hist.append(float(jnp.mean(d2)))
        D = jax.ops.segment_sum(X.astype(jnp.float32), assign, num_segments=k)
        cnt = jax.ops.segment_sum(jnp.ones((n,), jnp.float32), assign,
                                  num_segments=k)
        newC = D / jnp.maximum(cnt, 1.0)[:, None]
        C = jnp.where((cnt > 0)[:, None], newC, C)  # keep empty centroids
        if len(hist) > 2 and abs(hist[-2] - hist[-1]) <= 1e-7 * hist[-1]:
            break
    return assign, C, hist
