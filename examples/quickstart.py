"""Quickstart: cluster 16k points into 256 clusters with GK-means.

    PYTHONPATH=src python examples/quickstart.py [--n 16384] [--k 256]
"""
import argparse

import jax

from repro.core import brute_force_knn, gk_means, lloyd, recall_top1
from repro.data import gmm_blobs
from repro.launch import runtime

ap = argparse.ArgumentParser()
ap.add_argument("--n", type=int, default=16384)
ap.add_argument("--k", type=int, default=256)
ap.add_argument("--d", type=int, default=64)
args = ap.parse_args()
runtime.init()

key = jax.random.PRNGKey(0)
X = gmm_blobs(key, args.n, args.d, args.k)

# the whole paper in one call: Alg. 3 builds the KNN graph by calling fast
# k-means on itself; Alg. 2 then clusters guided by that graph.  The epoch
# loop runs device-resident (engine.run): one host sync for all `iters`.
res = gk_means(X, k=args.k, kappa=16, xi=64, tau=5, iters=10, key=key)
print(f"GK-means: distortion={res.distortion:.4f} "
      f"({res.seconds['total']:.1f}s)")
assert res.history[-1] <= res.history[0], "distortion must not increase"

# compare against classical Lloyd k-means(++)
_, _, hist = lloyd(X, args.k, iters=20, key=key)
print(f"Lloyd(k-means++): distortion={hist[-1]:.4f}")

# the self-built KNN graph is a byproduct you can keep (paper §4.3)
m = min(args.n, 2048)
gt = brute_force_knn(X[:m], 1)
print(f"graph recall@1 (sampled): {recall_top1(res.graph.ids[:m], gt):.3f}")
