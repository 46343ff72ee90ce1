"""Paper Table 2 (scaled): the very-large-k challenge — n/k = 10 samples per
cluster (VLAD10M -> 1M clusters had n/k=10).  CPU-scaled: n=131072, k=8192+.
Reports init time, iteration time, distortion, graph recall — same columns."""
from __future__ import annotations

import time

import jax

from repro.core import (brute_force_knn, closure_kmeans, gk_means, nn_descent,
                        recall_top1)
from repro.data import gmm_blobs


def run(quick: bool = True):
    n, d = (131072, 64) if quick else (10_000_000, 512)
    k = n // 16  # n/k=16 samples per cluster (paper: 10)
    X = gmm_blobs(jax.random.PRNGKey(0), n, d, 1024)
    gt = brute_force_knn(X[:4096], 1)  # recall estimated on a subsample
    rows = []

    res = gk_means(X, k, kappa=16, xi=64, tau=4, iters=8,
                   key=jax.random.PRNGKey(1))
    rec = float(recall_top1(res.graph.ids[:4096], gt))
    rows.append((f"table2/GK-means(k={res.k})",
                 res.seconds["total"] * 1e6,
                 f"distortion={res.distortion:.4f};recall~={rec:.2f}"))

    t0 = time.perf_counter()
    g = nn_descent(X, 16, iters=6, key=jax.random.PRNGKey(2))
    kg = gk_means(X, k, kappa=16, iters=8, key=jax.random.PRNGKey(1),
                  graph=g)
    t_kg = time.perf_counter() - t0
    rec = float(recall_top1(g.ids[:4096], gt))
    rows.append((f"table2/KGraph+GK-means(k={kg.k})", t_kg * 1e6,
                 f"total_s={t_kg:.1f};distortion={kg.distortion:.4f};"
                 f"recall~={rec:.2f}"))

    t0 = time.perf_counter()
    _, _, hc = closure_kmeans(X, k, iters=8, key=jax.random.PRNGKey(3))
    t_c = time.perf_counter() - t0
    rows.append((f"table2/closure(k={k})", t_c * 1e6,
                 f"total_s={t_c:.1f};distortion={hc[-1]:.4f}"))
    return rows
