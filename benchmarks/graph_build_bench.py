"""Graph-build bench: device-resident GraphBuilder vs host-driven rounds.

The pre-PR4 ``build_knn_graph`` dispatched 3-4 separate jitted calls per tau
round from Python (tree, guided epoch, member table, refine).  The
GraphBuilder core runs the whole tau-round loop in ONE trace: one dispatch
and one host sync per build, for both graph sources.

Modes:

  single   device-resident ``build_graph`` vs a host-driven loop that
           dispatches the same round pieces from Python (the pre-refactor
           shape), for both Alg. 3 and NN-Descent; reports dispatches/build,
           epochs/s, recall@kappa, and per-round diagnostics;
  sharded  the same Alg. 3 build through ``GraphBuilder(mesh=...)`` on
           forced host devices (child process), asserting bit-exact parity
           with the single-device ``shards=R`` emulation;
  scale    a large-k0 sharded build: the distributed histogram-median 2M
           tree and the shard-local member table instead of replicated
           (n_pad,) sorts and a replicated (k0, cap) table.  Reports the
           per-shard peak candidate-set size per row, the exchanged bytes
           per round vs the old replicated state, asserts ONE host sync,
           and merges its section into ``BENCH_scale.json`` next to
           engine_bench's.

Emits ``BENCH_graph_build.json`` (a ``repro.bench.v1`` run record; the
device-resident build runs with ``cfg.telemetry`` ON and its per-round rows
land in the record's ``telemetry`` section, still in ONE host sync —
``obs.sync_counter``-verified).  CLI (the CI smoke step):
``python benchmarks/graph_build_bench.py --quick``.
"""
from __future__ import annotations

import argparse
import time

SHARDED_DEVICES = 4
OUT_JSON = "BENCH_graph_build.json"
SHARDED_JSON = "BENCH_graph_build_sharded.json"
SCALE_JSON = "BENCH_scale.json"


def _bench_case(quick: bool):
    n, d, kappa, xi, tau = ((8192, 32, 16, 64, 4) if quick
                            else (262144, 64, 32, 64, 8))
    return n, d, kappa, xi, tau


def run_single(quick: bool = True):
    import jax
    from repro.core import (GraphBuildConfig, brute_force_knn, build_graph,
                            engine, recall_at, two_means_tree)
    from repro.core.graph_build import _refine_rows
    from repro.core.knn_graph import members_table
    from repro.data import gmm_blobs
    from repro.obs import run_record, sync_counter, write_json
    from repro.obs import telemetry as obs_tel

    n, d, kappa, xi, tau = _bench_case(quick)
    key = jax.random.PRNGKey(0)
    X = gmm_blobs(key, n, d, 256)
    gt = brute_force_knn(X, kappa, chunk=2048)
    cfg = GraphBuildConfig(kappa=kappa, xi=xi, tau=tau, telemetry=True)

    # ---- host-driven baseline: the pre-PR4 dispatch shape (tree, guided
    # epoch, member table + refine dispatched separately per round) --------
    import jax.numpy as jnp
    from repro.core import random_graph
    from repro.core.graph_build import _plan
    refine_jit = jax.jit(lambda X, rows, ids, gi, gd: _refine_rows(
        X, rows, ids, gi, gd, X, cfg.chunk, None))
    k0, _ = _plan(n, cfg)

    def host_driven(key):
        dispatches = 0
        kinit, kloop = jax.random.split(key)
        own = jnp.arange(n, dtype=jnp.int32)
        cand0 = random_graph(kinit, n, kappa)
        g_ids = jnp.full((n, kappa), -1, jnp.int32)
        g_d = jnp.full((n, kappa), jnp.inf, jnp.float32)
        g_ids, g_d = refine_jit(X, jnp.maximum(cand0, 0), cand0, g_ids, g_d)
        dispatches += 1
        for t in range(tau):
            kt = jax.random.fold_in(kloop, t)
            k1, k2 = jax.random.split(kt)
            assign = two_means_tree(X, k0, k1)
            dispatches += 1
            if t > 0:
                st = engine.init_state(X, assign, k0)
                st = engine.epoch(X, st, engine.graph_source(g_ids), k2,
                                  engine.EngineConfig(batch_size=1024,
                                                      sparse_updates=True))
                assign = st.assign
                dispatches += 2
            table, _ = members_table(assign, k0, 2 * xi)
            rows = table[assign]
            ids = jnp.where(rows >= 0, rows, -1)
            ids = jnp.where(ids == own[:, None], -1, ids)
            g_ids, g_d = refine_jit(X, jnp.maximum(rows, 0), ids, g_ids, g_d)
            dispatches += 2
        return g_ids, g_d, dispatches

    # warm both paths, then time
    jax.block_until_ready(host_driven(key)[0])
    jax.block_until_ready(build_graph(X, key, cfg)[0].ids)

    t0 = time.perf_counter()
    h_ids, _, host_dispatches = host_driven(key)
    jax.block_until_ready(h_ids)
    t_host = time.perf_counter() - t0

    # dispatch under a device->host transfer guard: the "1 host sync" claim
    # written below is runtime-verified, not declared — with per-round
    # telemetry riding the same sync
    t0 = time.perf_counter()
    with sync_counter() as sc:
        out = build_graph(X, key, cfg)
        graph, diag = sc.get(out)                           # the ONE sync
    t_dev = time.perf_counter() - t0
    assert sc.syncs == 1, sc.syncs

    rec_dev = float(recall_at(graph.ids, gt, kappa))
    rec_host = float(recall_at(h_ids, gt, kappa))

    # descent source through the same core (NN-Descent converges slower per
    # round than Alg. 3 — give it 2x the rounds for a meaningful recall)
    nnd_iters = 2 * tau
    t0 = time.perf_counter()
    gd, _ = jax.device_get(build_graph(
        X, key, GraphBuildConfig(kappa=kappa, source="descent",
                                 tau=nnd_iters)))
    t_nnd = time.perf_counter() - t0
    rec_nnd = float(recall_at(gd.ids, gt, kappa))

    rec = run_record(
        "graph_build",
        shapes={"n": n, "d": d, "kappa": kappa, "xi": xi, "tau": tau,
                "nn_descent_iters": nnd_iters},
        config={"telemetry": True},
        metrics={
            "host_driven_s": t_host, "device_resident_s": t_dev,
            "nn_descent_s": t_nnd,
            "epochs_per_sec_host": tau / t_host,
            "epochs_per_sec_device": tau / t_dev,
            "dispatches_host_driven": host_dispatches,
            "dispatches_device_resident": 1,
            "host_syncs_device_resident": sc.syncs,
            "recall_at_kappa": rec_dev,
            "recall_at_kappa_host_driven": rec_host,
            "recall_at_kappa_nn_descent": rec_nnd,
        },
        telemetry=obs_tel.to_dict(
            diag.telemetry,
            slots=["overflow", "guided_moves", "graph_updates",
                   "graph_mean_dist"]),
    )
    write_json(OUT_JSON, rec)
    return [
        ("graph_build/host_driven", t_host * 1e6,
         f"epochs_per_s={tau / t_host:.2f};dispatches={host_dispatches};"
         f"recall@{kappa}={rec_host:.3f}"),
        ("graph_build/device_resident", t_dev * 1e6,
         f"epochs_per_s={tau / t_dev:.2f};dispatches=1;syncs=1;"
         f"recall@{kappa}={rec_dev:.3f};speedup={t_host / t_dev:.2f}x"),
        ("graph_build/nn_descent_device_resident", t_nnd * 1e6,
         f"recall@{kappa}={rec_nnd:.3f};dispatches=1"),
    ]


def _sharded_child(quick: bool):
    """Sharded build on forced host devices + bit-exact parity check."""
    import jax
    import numpy as np
    from repro.core import GraphBuildConfig, GraphBuilder, build_graph
    from repro.data import gmm_blobs
    from repro.obs import run_record, sync_counter, write_json
    from repro.obs import telemetry as obs_tel

    n, d, kappa, xi, tau = _bench_case(quick)
    R = len(jax.devices())
    key = jax.random.PRNGKey(0)
    X = gmm_blobs(key, n, d, 256)
    cfg = GraphBuildConfig(kappa=kappa, xi=xi, tau=tau, shards=R,
                           telemetry=True)
    from repro.launch.mesh import data_mesh
    mesh = data_mesh(R)
    builder = GraphBuilder(cfg, mesh=mesh)

    g1, d1 = jax.device_get(build_graph(X, key, cfg))   # R-way emulation
    jax.block_until_ready(builder.build(X, key)[0].ids)  # warm

    t0 = time.perf_counter()
    with sync_counter() as sc:
        out = builder.build(X, key)
        g2, d2 = sc.get(out)                             # the ONE sync
    t_sharded = time.perf_counter() - t0
    assert sc.syncs == 1, sc.syncs

    np.testing.assert_array_equal(g1.ids, g2.ids)
    np.testing.assert_array_equal(g1.dist, g2.dist)
    np.testing.assert_array_equal(d1.overflow, d2.overflow)
    np.testing.assert_array_equal(d1.guided_moves, d2.guided_moves)
    np.testing.assert_array_equal(d1.telemetry.i32, d2.telemetry.i32)
    np.testing.assert_allclose(d1.telemetry.f32, d2.telemetry.f32,
                               rtol=1e-5)

    rec = run_record(
        "graph_build_sharded",
        shapes={"n": n, "d": d, "kappa": kappa, "xi": xi, "tau": tau,
                "devices": R},
        config={"telemetry": True},
        metrics={
            "sharded_build_s": t_sharded,
            "epochs_per_sec_sharded": tau / t_sharded,
            "host_syncs_sharded_build": sc.syncs,
            "parity_bitexact_vs_single_device": True,
        },
        telemetry=obs_tel.to_dict(
            d2.telemetry,
            slots=["overflow", "guided_moves", "graph_updates",
                   "graph_mean_dist"]),
    )
    write_json(SHARDED_JSON, rec)


def _scale_child(quick: bool):
    """Large-k0 sharded build: distributed-tree / local-table wire figures.

    Per level the distributed tree psums one (256, k0)-digit int32
    histogram — O(k0) wire independent of n — where the old tree sorted a
    replicated (n_pad,) projection (which required every row on every
    shard).  Per round the member-table exchange moves each shard's
    transposed (cap/R, k0) slice plus its (spill,) list, vs the old
    replicated (k0, cap) table.  Refinement candidates per row are the
    table column plus the gathered spill lists — static, so the per-shard
    peak candidate set is cap + R·spill by construction.
    """
    import jax
    from repro.core import GraphBuildConfig, GraphBuilder
    from repro.core.graph_build import _plan
    from repro.data import gmm_blobs
    from repro.obs import sync_counter
    try:
        from benchmarks.common import merge_scale_record
    except ImportError:
        from common import merge_scale_record

    n, d, kappa, xi, tau = ((8192, 16, 8, 16, 2) if quick
                            else (131072, 64, 16, 32, 4))
    R = len(jax.devices())
    key = jax.random.PRNGKey(0)
    X = gmm_blobs(key, n, d, 256)
    cfg = GraphBuildConfig(kappa=kappa, xi=xi, tau=tau, shards=R)
    k0, n_pad = _plan(n, cfg)
    cap = cfg.cap_factor * xi
    from repro.launch.mesh import data_mesh
    mesh = data_mesh(R)
    builder = GraphBuilder(cfg, mesh=mesh)
    jax.block_until_ready(builder.build(X, key)[0].ids)   # warm

    t0 = time.perf_counter()
    with sync_counter() as sc:
        out = builder.build(X, key)
        sc.get(out)                                       # the ONE sync
    t_build = time.perf_counter() - t0
    assert sc.syncs == 1, sc.syncs

    tree_psum = 256 * k0 * 4                  # per level, k-proportional
    old_sort = n_pad * 4                      # replicated projection, per level
    table_exch = R * ((cap // R) * k0 + cfg.spill) * 4    # per round
    old_table = k0 * cap * 4                  # replicated table, per round
    merge_scale_record(
        SCALE_JSON, "graph_build",
        shapes={"n": n, "d": d, "kappa": kappa, "xi": xi, "tau": tau,
                "k0": k0, "devices": R},
        config={"cap": cap, "spill": cfg.spill},
        metrics={
            "build_s": t_build,
            "host_syncs": sc.syncs,
            "peak_candidate_rows_per_row": cap + R * cfg.spill,
            "tree_hist_psum_bytes_per_level": tree_psum,
            "old_tree_replicated_bytes_per_level": old_sort,
            "table_exchange_bytes_per_round": table_exch,
            "old_table_replicated_bytes_per_round": old_table,
            "table_exchange_vs_replicated_ratio": table_exch / old_table,
        })


def run_scale(quick: bool = True, devices: int = SHARDED_DEVICES):
    """Scale mode (``_scale_child``) via ``common.run_sharded_mode``."""
    try:
        from benchmarks.common import run_sharded_mode
    except ImportError:
        from common import run_sharded_mode
    from repro.obs import load_records
    run_sharded_mode(__file__, _scale_child, quick, devices,
                     extra=("--kind", "scale"))
    rec = load_records(SCALE_JSON)[0]
    m = rec["metrics"]
    return [
        ("graph_build/scale_sharded_build",
         m["graph_build.build_s"] * 1e6,
         f"k0={rec['shapes']['graph_build.k0']};"
         f"syncs={m['graph_build.host_syncs']};"
         f"cand_rows_per_row={m['graph_build.peak_candidate_rows_per_row']};"
         f"table_exchange_vs_replicated="
         f"{m['graph_build.table_exchange_vs_replicated_ratio']:.3f}x"),
    ]


def run_sharded(quick: bool = True, devices: int = SHARDED_DEVICES):
    """Sharded mode: in-process on the real devices, or a forced-host-device
    CPU rehearsal (``benchmarks.common.run_sharded_mode``)."""
    try:
        from benchmarks.common import run_sharded_mode
    except ImportError:       # run directly: benchmarks/ itself is sys.path
        from common import run_sharded_mode
    from repro.obs import load_records
    run_sharded_mode(__file__, _sharded_child, quick, devices)
    rec = load_records(SHARDED_JSON)[0]
    m = rec["metrics"]
    return [
        ("graph_build/sharded_device_resident", m["sharded_build_s"] * 1e6,
         f"epochs_per_s={m['epochs_per_sec_sharded']:.2f};"
         f"syncs={m['host_syncs_sharded_build']};telemetry=on;"
         f"devices={rec['shapes']['devices']};parity=bitexact"),
    ]


def run(quick: bool = True):
    """Both modes — the benchmarks.run harness entry point."""
    return run_single(quick) + run_sharded(quick)


def main():
    ap = argparse.ArgumentParser()
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--quick", dest="quick", action="store_true",
                      default=True)
    size.add_argument("--full", dest="quick", action="store_false")
    ap.add_argument("--mode", default="both",
                    choices=["single", "sharded", "scale", "both"])
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--kind", default="sharded",
                    choices=["sharded", "scale"], help=argparse.SUPPRESS)
    args = ap.parse_args()
    from repro.launch import runtime
    runtime.init()
    if args.child:
        (_scale_child if args.kind == "scale" else _sharded_child)(args.quick)
        return
    rows = []
    if args.mode in ("single", "both"):
        rows += run_single(args.quick)
    if args.mode in ("sharded", "both"):
        rows += run_sharded(args.quick)
    if args.mode == "scale":
        rows += run_scale(args.quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
