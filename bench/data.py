"""The benchmark's data generator: Gaussian-mixture rows made on the device.

A copy of the program's ``data.synthetic.gmm_blobs`` (one component per
requested cluster, heterogeneous scales), kept here so that a change to
the program cannot change the data it is measured on.  Queries are further
draws from the same mixture, never indexed.  A clustering start is made
here too (``random_row_start``), so that the program prepares none of the
inputs that the reference is handed besides the KNN graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (64 bits are kept)."""
    s = seed % (1 << 64)
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, s & 0xFFFFFFFF)
    return jax.random.fold_in(k, s >> 32)


def _mixture(key: jax.Array, d: int, components: int, spread: float):
    kc, ks, ka, kx = jax.random.split(key, 4)
    means = jax.random.normal(kc, (components, d)) * spread
    scales = jnp.exp(jax.random.normal(ks, (components, 1)) * 0.3)
    return means, scales, ka, kx


def _draw(means, scales, ka, kx, n: int) -> jax.Array:
    comp = jax.random.randint(ka, (n,), 0, means.shape[0])
    noise = jax.random.normal(kx, (n, means.shape[1]))
    return (means[comp] + noise * scales[comp]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def gmm_blobs(key: jax.Array, n: int, d: int, components: int,
              spread: float = 4.0) -> jax.Array:
    """n samples from ``components`` Gaussians with random means/scales."""
    means, scales, ka, kx = _mixture(key, d, components, spread)
    return _draw(means, scales, ka, kx, n)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def rows_and_queries(key: jax.Array, qkey: jax.Array, n: int, nq: int,
                     d: int, components: int, spread: float = 4.0):
    """(X (n, d), Q (nq, d)): the rows ``gmm_blobs(key, n, ...)`` makes,
    and nq queries drawn under ``qkey`` from the same mixture, never
    indexed."""
    means, scales, ka, kx = _mixture(key, d, components, spread)
    return (_draw(means, scales, ka, kx, n),
            _draw(means, scales, *jax.random.split(qkey), nq))


def lists_for(k: int) -> int:
    """k rounded up to a power of two, as the program's clustering job
    rounds the number of clusters it makes."""
    return 1 << max(k - 1, 0).bit_length()


@functools.partial(jax.jit, static_argnames=("k", "block"))
def random_row_start(X: jax.Array, key: jax.Array, *, k: int,
                     block: int) -> jax.Array:
    """A k-means start from the seed: k distinct rows drawn as centroids,
    every row assigned to its nearest one (float32 at HIGHEST, in row
    blocks).  Each drawn row is nearest to itself, so no cluster starts
    empty."""
    n, d = X.shape
    C = X[jax.random.choice(key, n, (k,), replace=False)]
    csq = jnp.sum(C * C, axis=1)
    nb = -(-n // block)
    Xb = jnp.pad(X, ((0, nb * block - n), (0, 0))).reshape(nb, block, d)

    def nearest(xb):
        dots = jax.lax.dot_general(xb, C, (((1,), (1,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
        return jnp.argmin(csq[None, :] - 2.0 * dots, axis=1).astype(
            jnp.int32)

    return jax.lax.map(nearest, Xb).reshape(-1)[:n]
