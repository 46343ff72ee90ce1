"""Paper Fig. 5: distortion vs iterations and vs wall time for GK-means,
full boost k-means, Lloyd, closure k-means, Mini-Batch."""
from __future__ import annotations

import time

import jax

from repro.core import (closure_kmeans, distortion, gk_means, lloyd,
                        minibatch_kmeans, run_bkm, two_means_tree)
from repro.data import gmm_blobs


def run(quick: bool = True):
    n, d, k = (16384, 64, 256) if quick else (1_000_000, 128, 10_000)
    X = gmm_blobs(jax.random.PRNGKey(0), n, d, 256)
    iters = 10
    rows = []

    t0 = time.perf_counter()
    res = gk_means(X, k, kappa=16, xi=64, tau=5, iters=iters,
                   key=jax.random.PRNGKey(1))
    t = (time.perf_counter() - t0) * 1e6
    hist = "|".join(f"{h:.3f}" for h in res.history)
    rows.append(("fig5/GK-means", t, f"final={res.distortion:.4f};hist={hist}"
                 + f";total_s={res.seconds['total']:.1f}"))

    t0 = time.perf_counter()
    a0 = two_means_tree(X, k, jax.random.PRNGKey(2))
    _, hist_b = run_bkm(X, a0, k, iters=iters, batch_size=1024,
                        key=jax.random.PRNGKey(3))
    t = (time.perf_counter() - t0) * 1e6
    rows.append(("fig5/BoostKM(full)", t, f"final={float(hist_b[-1]):.4f}"))

    t0 = time.perf_counter()
    _, _, hl = lloyd(X, k, iters=iters, key=jax.random.PRNGKey(4))
    t = (time.perf_counter() - t0) * 1e6
    rows.append(("fig5/k-means(++)", t, f"final={hl[-1]:.4f}"))

    t0 = time.perf_counter()
    _, _, hc = closure_kmeans(X, k, iters=iters, key=jax.random.PRNGKey(5))
    t = (time.perf_counter() - t0) * 1e6
    rows.append(("fig5/closure", t, f"final={hc[-1]:.4f}"))

    t0 = time.perf_counter()
    am, _ = minibatch_kmeans(X, k, steps=10 * (n // 1024),
                             key=jax.random.PRNGKey(6))
    t = (time.perf_counter() - t0) * 1e6
    rows.append(("fig5/mini-batch", t,
                 f"final={float(distortion(X, am, k)):.4f}"))
    return rows
