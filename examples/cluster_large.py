"""End-to-end large-scale clustering driver (paper Table 2, CPU-scaled):
cluster n=131072 vectors into k=8192 clusters — n/k=16 samples per cluster,
the regime where traditional k-means is hopeless and GK-means shines.

    PYTHONPATH=src python examples/cluster_large.py [--n 131072] [--k 8192]

Both topologies run the epoch loop fully device-resident — ``engine.run`` on
one device, ``ShardedEngine.run`` SPMD across a multi-device mesh — so either
way the whole loop (per-epoch distortion + ``min_move_frac`` early stop) costs
ONE host sync, runtime-verified by ``obs.sync_counter`` with per-epoch
telemetry riding the same sync.  Every row is clustered in-engine: the
graph build pads internally, the 2M-tree init pads via ``pad_plan`` (wrap
rows, sliced off the assignment), and ``ShardedEngine.run`` threads a
padded-row validity mask when n is not divisible by the device count — no
truncation, no post-hoc nearest-centroid remainder pass (whose empty-cluster
origin centroids were a correctness hazard).

Diagnostics (graph-build round diagnostics, per-epoch telemetry) land in a
structured ``repro.bench.v1`` run record — printed as JSONL, or written to
``--emit PATH``.
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import build_knn_graph, engine, two_means_tree
from repro.core.distributed import ShardedEngine
from repro.core.two_means import pad_plan
from repro.data import gmm_blobs
from repro.launch import runtime
from repro.launch.mesh import data_mesh
from repro.obs import emit, sync_counter
from repro.obs import telemetry as obs_tel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=131072)
    ap.add_argument("--k", type=int, default=8192)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--emit", default=None, metavar="PATH",
                    help="write the run record to PATH instead of stdout")
    args = ap.parse_args()
    runtime.init()

    key = jax.random.PRNGKey(0)
    print(f"[data] generating n={args.n} d={args.d}")
    X = gmm_blobs(key, args.n, args.d, 1024)

    n_dev = len(jax.devices())
    n2, k2 = pad_plan(args.n, args.k)
    if k2 != args.k:
        raise SystemExit(f"k={args.k} must be a power of two")
    if args.n < args.k:
        raise SystemExit(f"n={args.n} must be at least k={args.k}")
    # ShardedEngine needs equal per-shard cluster blocks (k % R == 0)
    if args.k % n_dev:
        raise SystemExit(f"k={args.k} does not divide over the {n_dev} "
                         f"devices: ShardedEngine needs equal cluster blocks")
    sharded = n_dev > 1

    t0 = time.time()
    g, gdiag = build_knn_graph(X, 16, xi=64, tau=4, key=key,
                               return_diagnostics=True, telemetry=True)
    t_graph = time.time() - t0
    print(f"[graph] built in {t_graph:.1f}s")

    # 2M-tree init wants k | n: pad with wrap rows, slice the phantom
    # assignments off (pad_plan's documented protocol) — the engine run
    # itself clusters all n rows natively.
    t0 = time.time()
    Xi = X if n2 == args.n else jnp.concatenate([X, X[: n2 - args.n]])
    a0 = two_means_tree(Xi, args.k, key)[: args.n]
    t_init = time.time() - t0
    print(f"[init] 2M tree ({args.k} clusters) in {t_init:.1f}s")

    st = engine.init_state(X, a0, args.k)
    xsq = jnp.sum(jnp.square(X.astype(jnp.float32)))
    d_init = float(engine.stats_distortion(xsq, st.D, st.cnt, args.n))
    print(f"[init] distortion {d_init:.4f}")
    cfg = engine.EngineConfig(batch_size=1024, iters=args.iters,
                              min_move_frac=1e-4, telemetry=True)
    t0 = time.time()
    if sharded:
        eng = ShardedEngine(data_mesh(n_dev), cfg)
        G = jnp.maximum(g.ids, 0)
        with sync_counter() as sc:
            out = eng.run(X, G, st.assign, st.D, st.cnt, key)
            (assign, D, cnt, hist, moves, epochs, final,
             tel) = sc.get(out)                           # the ONE sync
        where = f"{n_dev} devices"
    else:
        with sync_counter() as sc:
            out = engine.run(X, st, engine.graph_source(g.ids), key, cfg)
            st, hist, moves, epochs, final, tel = sc.get(out)
        assign, D, cnt = st.assign, st.D, st.cnt
        where = "1 device"
    dt = time.time() - t0
    assert sc.syncs == 1, sc.syncs
    for t in range(int(epochs)):
        print(f"[iter {t}] moves={int(moves[t])} dist={hist[t]:.4f}")
    print(f"[run] {int(epochs)} device-resident epochs in {dt:.1f}s "
          f"({where}, one host sync)")
    d_last = float(final)

    assert assign.shape == (args.n,), assign.shape
    assert int(jnp.sum(jnp.asarray(cnt))) == args.n, "every row assigned"
    print(f"[run] all {args.n} rows assigned in-engine")

    assert d_last < d_init, (d_init, d_last)
    print(f"[done] distortion {d_init:.4f} -> {d_last:.4f} (converging)")

    # the structured run record: graph-build round diagnostics + per-epoch
    # telemetry, one schema with the benchmarks
    rec = emit.run_record(
        "cluster_large",
        shapes={"n": args.n, "d": args.d, "k": args.k,
                "devices": n_dev if sharded else 1,
                "init_pad_rows": n2 - args.n},
        config={"iters": args.iters, "batch_size": 1024,
                "min_move_frac": 1e-4, "telemetry": True},
        metrics={
            "graph_build_s": t_graph, "init_s": t_init, "run_s": dt,
            "epochs": int(epochs), "host_syncs_run": sc.syncs,
            "distortion_init": d_init, "distortion_final": d_last,
            "rows_assigned": int(jnp.sum(jnp.asarray(cnt))),
            "graph_overflow_per_round": [int(v) for v in gdiag.overflow],
            "graph_guided_moves_per_round": [int(v)
                                             for v in gdiag.guided_moves],
        },
        telemetry=obs_tel.to_dict(
            jax.device_get(tel), rows=int(epochs),
            slots=["moves", "proposed", "empty_clusters", "distortion",
                   "hit_rate"]),
    )
    if args.emit:
        emit.write_json(args.emit, rec)
        print(f"[emit] run record -> {args.emit}")
    else:
        emit.emit_stdout([rec])


if __name__ == "__main__":
    main()
