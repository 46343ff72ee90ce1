"""Mini-Batch k-means (Sculley, WWW 2010) — speed baseline (paper §5)."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.lloyd import init_random
from repro.kernels import ops as kops
from repro.kernels.ref import HIGHEST


@functools.partial(jax.jit, static_argnums=(3, 4))
def _steps(X, C, key, batch_size: int, steps: int):
    n, d = X.shape
    k = C.shape[0]

    def body(i, carry):
        C, counts = carry
        idx = jax.random.randint(jax.random.fold_in(key, i),
                                 (batch_size,), 0, n)
        xb = X[idx].astype(jnp.float32)
        csq = jnp.sum(C * C, axis=-1)
        a = jnp.argmin(
            csq[None, :] - 2.0 * jnp.matmul(xb, C.T, precision=HIGHEST),
            axis=-1)
        bs = jax.ops.segment_sum(jnp.ones((batch_size,), jnp.float32), a,
                                 num_segments=k)
        bsum = jax.ops.segment_sum(xb, a, num_segments=k)
        new_counts = counts + bs
        # per-centre learning rate 1/counts: C += (bsum - bs*C) / counts
        C = C + jnp.where((new_counts > 0)[:, None],
                          (bsum - bs[:, None] * C) /
                          jnp.maximum(new_counts, 1.0)[:, None], 0.0)
        return C, new_counts

    C, _ = jax.lax.fori_loop(0, steps, body,
                             (C, jnp.zeros((k,), jnp.float32)))
    return C


def minibatch_kmeans(X: jax.Array, k: int, *, steps: int = 100,
                     batch_size: int = 1024, key: jax.Array
                     ) -> Tuple[jax.Array, jax.Array]:
    """Returns (assign, centroids) after `steps` mini-batch updates."""
    kc, ks = jax.random.split(key)
    C = init_random(X, k, kc)
    C = _steps(X, C, ks, min(batch_size, X.shape[0]), steps)
    assign, _ = kops.assign_centroids(X, C)
    return assign, C
