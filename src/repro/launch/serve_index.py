"""IVF index query-serving launcher: warmup, latency percentiles, recall/QPS.

Builds (or loads) an index over synthetic data, then sweeps `nprobe` to map
the recall-vs-throughput frontier — the serving-side mirror of
`launch/serve.py`'s prefill/decode loop.  `--qgroup G` serves through the
query-grouped scan layout (each list tile streamed once per group of G
probe-local queries).  Multi-device serving goes through
`core.distributed.ShardedIvf` (lists sharded by cell, one shard_map trace
and one host sync per query batch — see README "Serving the index");
`benchmarks/anns_ivf_bench.py --mode sharded` drives it on forced host
devices.  `--codec int8|pq` serves the compressed-list ADC scan path
(README "Compressed inverted lists"): the codec is trained and attached at
build time (and persisted by `--save`, so a `--load` run serves it without
retraining), candidates come from `kernels.ivf_scan_adc` over the u8 code
slabs, and the top `--rerank` survivors are exact-rescored against the f32
originals.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_index --n 32768 --d 64 --k 256
  PYTHONPATH=src python -m repro.launch.serve_index --save /tmp/ix.ivf
  PYTHONPATH=src python -m repro.launch.serve_index --load /tmp/ix.ivf
  PYTHONPATH=src python -m repro.launch.serve_index --qgroup 8
  PYTHONPATH=src python -m repro.launch.serve_index --codec pq --nsub 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import index as ivf
from repro.core import gk_means
from repro.data import gmm_blobs
from repro.launch import runtime


def build(args) -> tuple[ivf.IvfIndex, jax.Array]:
    key = jax.random.PRNGKey(args.seed)
    if args.load:
        index = ivf.load_index(args.load)
        # regenerate the dataset the index was built over: shapes come from
        # the index itself; --components/--seed must match the build run
        if (args.n, args.d) != (index.size, index.dim):
            print(f"[load] overriding --n/--d with the index's "
                  f"n={index.size} d={index.dim}")
        if args.codec != "f32" and index.codec_kind != args.codec:
            raise SystemExit(f"--codec {args.codec} but the saved index "
                             f"carries {index.codec_kind!r}")
        X = gmm_blobs(key, index.size, index.dim, args.components)
        return index, X
    X = gmm_blobs(key, args.n, args.d, args.components)
    t0 = time.perf_counter()
    res = gk_means(X, args.k, kappa=args.kappa, xi=64, tau=args.tau,
                   iters=args.iters, key=jax.random.fold_in(key, 1))
    t_cluster = time.perf_counter() - t0
    t0 = time.perf_counter()
    index = ivf.build_ivf(X, res, block_rows=args.block_rows)
    print(f"[build] gk_means k={res.k} in {t_cluster:.1f}s, "
          f"pack {index.n_rows} rows in {time.perf_counter() - t0:.2f}s")
    if args.codec != "f32":
        t0 = time.perf_counter()
        index = ivf.quantize_index(index, args.codec, nsub=args.nsub,
                                   key=jax.random.fold_in(key, 2))
        bpr = ivf.bytes_per_row(index.codec, index.dim)
        print(f"[build] {args.codec} codec in {time.perf_counter() - t0:.2f}s"
              f" ({bpr} B/row vs {4 * index.dim} f32)")
    if args.save:
        ivf.save_index(index, args.save)
        print(f"[build] saved -> {args.save} "
              f"({ivf.store.index_nbytes(args.save) / 1e6:.1f} MB)")
    return index, X


def serve_sweep(index: ivf.IvfIndex, X: jax.Array, *, nq: int, topk: int,
                probes, batch: int, rounds: int, seed: int,
                qgroup: int | None = None, codec: str = "f32",
                rerank: int | None = None):
    key = jax.random.PRNGKey(seed)
    batch = min(batch, nq)
    nq -= nq % batch  # whole batches only: one compile footprint per sweep
    Q = X[:nq] + 0.05 * jax.random.normal(key, (nq, X.shape[1]))
    # exact ground truth for recall@topk
    d2 = jnp.sum((Q[:, None, :] - X[None]) ** 2, -1)
    gt = jnp.argsort(d2, axis=1)[:, :topk]
    kw = {} if codec == "f32" else {"codec": codec, "rerank": rerank}

    print(f"{'nprobe':>6} {'recall@%d' % topk:>10} {'scan%':>7} "
          f"{'p50_ms':>8} {'p90_ms':>8} {'p99_ms':>8} {'QPS':>10}")
    rows = []
    for p in probes:
        ids, _ = ivf.search(index, Q, topk=topk, nprobe=p,
                            qgroup=qgroup, **kw)                  # for recall
        w, _ = ivf.search(index, Q[:batch], topk=topk, nprobe=p,
                          qgroup=qgroup, **kw)                    # warm batch
        jax.block_until_ready((ids, w))
        lat = []
        for r in range(rounds):
            for b0 in range(0, nq, batch):
                qb = Q[b0:b0 + batch]
                t0 = time.perf_counter()
                out, _ = ivf.search(index, qb, topk=topk, nprobe=p,
                                    qgroup=qgroup, **kw)
                jax.block_until_ready(out)
                lat.append(time.perf_counter() - t0)
        lat = np.sort(np.array(lat)) * 1e3                         # ms/batch
        hits = (ids[:, :, None] == gt[:, None, :]).any(-1)
        rec = float(jnp.mean(hits.astype(jnp.float32)))
        frac = ivf.scan_fraction(index, Q, nprobe=p)
        qps = batch / (lat.mean() / 1e3)
        pct = [lat[int(q * (len(lat) - 1))] for q in (0.5, 0.9, 0.99)]
        print(f"{p:>6} {rec:>10.3f} {100 * frac:>6.1f}% "
              f"{pct[0]:>8.2f} {pct[1]:>8.2f} {pct[2]:>8.2f} {qps:>10.0f}")
        rows.append({"nprobe": p, "recall": rec, "scan_frac": frac,
                     "p50_ms": pct[0], "p90_ms": pct[1], "p99_ms": pct[2],
                     "qps": qps})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=32768)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--k", type=int, default=256)
    ap.add_argument("--components", type=int, default=512)
    ap.add_argument("--kappa", type=int, default=16)
    ap.add_argument("--tau", type=int, default=3)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--block-rows", type=int, default=128)
    ap.add_argument("--nq", type=int, default=256)
    ap.add_argument("--topk", type=int, default=10)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--probes", default="1,2,4,8,16")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None, help="write index after build")
    ap.add_argument("--load", default=None, help="serve a saved index")
    ap.add_argument("--qgroup", type=int, default=None,
                    help="query-grouped scan layout: queries per group")
    ap.add_argument("--codec", default="f32",
                    choices=["f32", "int8", "pq"],
                    help="compressed-list ADC scan path (exact-rerank tail)")
    ap.add_argument("--rerank", type=int, default=None,
                    help="codec rerank depth (default 4*topk; 0 disables)")
    ap.add_argument("--nsub", type=int, default=8,
                    help="pq subspaces (code bytes per vector)")
    args = ap.parse_args()
    runtime.init()
    if args.codec != "f32" and args.qgroup:
        raise SystemExit("--codec is per-query only (drop --qgroup)")

    index, X = build(args)
    probes = [int(p) for p in args.probes.split(",") if int(p) <= index.k]
    serve_sweep(index, X, nq=args.nq, topk=args.topk, probes=probes,
                batch=args.batch, rounds=args.rounds, seed=args.seed + 9,
                qgroup=args.qgroup, codec=args.codec, rerank=args.rerank)


if __name__ == "__main__":
    main()
