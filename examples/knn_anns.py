"""ANN search two ways on the same data (paper §4.3 + the IVF subsystem):

1. graph search — build a KNN graph with Alg. 3 (more tau = better graph),
   then serve queries with greedy best-first search;
2. cluster -> build index -> serve queries — GK-means becomes the coarse
   quantizer of an IVF index that scans only the probed cells' lists, and
   persists to disk so a serving restart skips the clustering entirely.

    PYTHONPATH=src python examples/knn_anns.py
"""
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro import index as ivf
from repro.core import build_knn_graph, gk_means, graph_search
from repro.data import gmm_blobs
from repro.launch import runtime

runtime.init()
key = jax.random.PRNGKey(0)
n, d = 32768, 64
X = gmm_blobs(key, n, d, 512)

t0 = time.time()
# the whole tau-round build is one device-resident trace (one dispatch /
# one host sync); diagnostics report per-round member-table overflow and
# guided-pass moves
g, diag = build_knn_graph(X, 16, xi=64, tau=8, key=key,   # ANNS: higher tau
                          return_diagnostics=True)
print(f"[build] KNN graph (n={n}) in {time.time() - t0:.1f}s, "
      f"overflow/round={[int(v) for v in diag.overflow]}, "
      f"guided moves/round={[int(v) for v in diag.guided_moves]}")

nq = 256
q = X[:nq] + 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (nq, d))
search = jax.jit(lambda qq: graph_search(X, g.ids, qq, topk=10, ef=96,
                                         iters=64))
ids, d2 = search(q)   # compile
t0 = time.time()
ids, d2 = search(q)
jax.block_until_ready(ids)
dt = time.time() - t0

# exact ground truth for recall
dd = jnp.sum((q[:, None, :] - X[None]) ** 2, -1)
true1 = jnp.argmin(dd, 1)
rec = float(jnp.mean((ids[:, 0] == true1).astype(jnp.float32)))
print(f"[graph] {nq} queries in {dt*1e3:.1f}ms "
      f"({dt/nq*1e6:.0f}us/query), recall@1={rec:.3f}")

# --- cluster -> build index -> serve queries (the IVF path) ----------------
t0 = time.time()
res = gk_means(X, 256, kappa=16, xi=64, tau=3, iters=8,
               key=jax.random.fold_in(key, 2))
idx = ivf.build_ivf(X, res, block_rows=128)
print(f"[ivf] clustered k={res.k} + packed {idx.n_rows} rows "
      f"in {time.time() - t0:.1f}s")

# persist: a serving restart loads the index instead of re-clustering
path = os.path.join(tempfile.gettempdir(), "knn_anns_example.ivf")
ivf.save_index(idx, path)
idx = ivf.load_index(path)
print(f"[ivf] saved + reloaded {path} ({os.path.getsize(path) / 1e6:.1f} MB)")

for nprobe in (1, 4, 16):
    ids, d2 = ivf.search(idx, q, topk=10, nprobe=nprobe)   # compile
    t0 = time.time()
    ids, d2 = ivf.search(idx, q, topk=10, nprobe=nprobe)
    jax.block_until_ready(ids)
    dt = time.time() - t0
    rec = float(jnp.mean((ids[:, 0] == true1).astype(jnp.float32)))
    frac = ivf.scan_fraction(idx, q, nprobe=nprobe)
    print(f"[ivf] nprobe={nprobe:2d}: {dt/nq*1e6:.0f}us/query, "
          f"recall@1={rec:.3f}, scanned {100 * frac:.1f}% of the database")
