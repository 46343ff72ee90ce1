"""Engine vs the pre-engine host-driven path: epochs/sec and host syncs.

The pre-engine driver ran one jitted epoch per Python-loop step and recomputed
the O(n·d) distortion on the host after every epoch (one sync per epoch).
``engine.run`` keeps the whole loop device-resident — per-epoch distortion in
O(k·d) from the running stats, early stop in-trace, ONE host sync per run.

Both timed device-resident runs enable ``cfg.telemetry``: the per-epoch
telemetry rows come back in the SAME single ``device_get`` as the results
(``obs.sync_counter`` runtime-verifies the count stays 1), and land in the
emitted record's ``telemetry`` section.

Three modes:

  single   the single-device ``engine.run`` vs a host-driven epoch loop
           (emits ``BENCH_engine.json``);
  sharded  the same comparison across a mesh: ``ShardedEngine.run`` vs a
           host-driven loop of ``ShardedEngine.epoch`` + per-epoch
           ``ShardedEngine.distortion`` syncs, on the real devices — or,
           with ``JAX_PLATFORMS=cpu``, in a child process with
           ``--xla_force_host_platform_device_count`` (emits
           ``BENCH_sharded_run.json``);
  scale    a large-k ``ShardedEngine.run``: the probe-candidate centroid
           exchange instead of a replicated (k, d) matrix.  Reports the
           per-shard peak candidate-set size (static by construction — the
           exchange is a dense (B, C) id block), the exchanged bytes per
           batch step vs the old (k, d) all-gather, and asserts the run
           still pays exactly ONE host sync.  Merges its section into
           ``BENCH_scale.json`` next to graph_build_bench's.

All JSON files are ``repro.bench.v1`` run records (``repro.obs.emit``).
CLI (the CI smoke step): ``python benchmarks/engine_bench.py --quick``
runs single+sharded; ``--mode scale`` runs the large-k mode.
"""
from __future__ import annotations

import argparse
import time

SHARDED_DEVICES = 4
OUT_JSON = "BENCH_engine.json"
SHARDED_JSON = "BENCH_sharded_run.json"
SCALE_JSON = "BENCH_scale.json"


def _host_driven(X, a0, k, source, key, iters, batch_size):
    """The pre-engine driver: epoch dispatch + host distortion sync/epoch."""
    import jax
    from repro.core import distortion, engine
    st = engine.init_state(X, a0, k)
    cfg = engine.EngineConfig(batch_size=batch_size)
    hist = []
    for t in range(iters):
        st = engine.epoch(X, st, source, jax.random.fold_in(key, t), cfg)
        hist.append(float(distortion(X, st.assign, k)))   # host sync here
    return st, hist


def run(quick: bool = True):
    """Both modes — the benchmarks.run harness entry point."""
    return run_single(quick) + run_sharded(quick)


def run_single(quick: bool = True):
    import jax
    from repro.core import build_knn_graph, engine, two_means_tree
    from repro.data import gmm_blobs
    from repro.obs import run_record, sync_counter, write_json
    from repro.obs import telemetry as obs_tel

    n, d, k, iters = (16384, 32, 256, 10) if quick else (262144, 64, 4096, 10)
    bs = 1024
    key = jax.random.PRNGKey(0)
    X = gmm_blobs(key, n, d, 256)
    g = build_knn_graph(X, 16, xi=64, tau=3, key=key)
    a0 = two_means_tree(X, k, key)
    source = engine.graph_source(g.ids)

    # warm both compile paths (same static configs as the timed runs);
    # the timed device-resident run has telemetry ON — the satellite claim
    # is that the sync count is UNCHANGED (still 1) with it enabled
    cfg = engine.EngineConfig(batch_size=bs, iters=iters, min_move_frac=-1.0,
                              telemetry=True)
    _host_driven(X, a0, k, source, key, 1, bs)
    jax.block_until_ready(
        engine.run(X, engine.init_state(X, a0, k), source, key, cfg)[0])

    t0 = time.perf_counter()
    _, hist_host = _host_driven(X, a0, k, source, key, iters, bs)
    t_host = time.perf_counter() - t0

    t0 = time.perf_counter()
    with sync_counter() as sc:
        out = engine.run(X, engine.init_state(X, a0, k), source, key, cfg)
        st, hist, _, epochs, final, tel = sc.get(out)    # the ONE sync
    t_run = time.perf_counter() - t0
    assert sc.syncs == 1, sc.syncs

    rec = run_record(
        "engine",
        shapes={"n": n, "d": d, "k": k, "kappa": 16},
        config={"iters": iters, "batch_size": bs, "min_move_frac": -1.0,
                "telemetry": True},
        metrics={
            "host_driven_s": t_host, "engine_run_s": t_run,
            "epochs_per_sec_host": iters / t_host,
            "epochs_per_sec_engine": iters / t_run,
            "speedup": t_host / t_run,
            "host_syncs_host_driven": iters,
            "host_syncs_engine_run": sc.syncs,
            "final_distortion_host": hist_host[-1],
            "final_distortion_engine": float(final),
        },
        telemetry=obs_tel.to_dict(tel, rows=int(epochs)),
    )
    write_json(OUT_JSON, rec)

    return [
        ("engine/host_driven", t_host * 1e6,
         f"epochs_per_s={iters / t_host:.2f};syncs={iters};"
         f"final={hist_host[-1]:.4f}"),
        ("engine/device_resident_run", t_run * 1e6,
         f"epochs_per_s={iters / t_run:.2f};syncs={sc.syncs};telemetry=on;"
         f"final={float(final):.4f};speedup={t_host / t_run:.2f}x"),
    ]


def _sharded_child(quick: bool):
    """Body of the sharded mode — must run under R forced host devices."""
    import jax
    import jax.numpy as jnp
    from repro.core import build_knn_graph, engine, two_means_tree
    from repro.core.distributed import ShardedEngine
    from repro.data import gmm_blobs
    from repro.obs import run_record, sync_counter, write_json
    from repro.obs import telemetry as obs_tel

    n, d, k, iters = (8192, 32, 256, 8) if quick else (262144, 64, 4096, 10)
    R = len(jax.devices())
    bs = 256                    # per-shard; global batch = R * bs
    key = jax.random.PRNGKey(0)
    X = gmm_blobs(key, n, d, 256)
    g = build_knn_graph(X, 16, xi=64, tau=3, key=key)
    G = jnp.maximum(g.ids, 0)
    a0 = two_means_tree(X, k, key)
    st = engine.init_state(X, a0, k)

    from repro.launch.mesh import data_mesh
    mesh = data_mesh(R)
    cfg = engine.EngineConfig(batch_size=bs, iters=iters, min_move_frac=-1.0,
                              telemetry=True)
    eng = ShardedEngine(mesh, cfg)

    # warm every compile path
    jax.block_until_ready(eng.epoch(X, G, st.assign, st.D, st.cnt, key))
    jax.block_until_ready(eng.distortion(X, st.assign, st.D, st.cnt))
    jax.block_until_ready(eng.run(X, G, st.assign, st.D, st.cnt, key)[0])

    t0 = time.perf_counter()
    assign, D, cnt = st.assign, st.D, st.cnt
    hist_host = []
    for t in range(iters):
        assign, D, cnt, moves = eng.epoch(X, G, assign, D, cnt,
                                          jax.random.fold_in(key, t))
        hist_host.append(float(eng.distortion(X, assign, D, cnt)))  # sync
    t_host = time.perf_counter() - t0

    # whole-mesh run with telemetry ON, still exactly one host sync
    t0 = time.perf_counter()
    with sync_counter() as sc:
        out = eng.run(X, G, st.assign, st.D, st.cnt, key)
        (assign_r, D_r, cnt_r, hist, mhist, epochs, final,
         tel) = sc.get(out)                              # the ONE sync
    t_run = time.perf_counter() - t0
    assert sc.syncs == 1, sc.syncs

    rec = run_record(
        "engine_sharded",
        shapes={"n": n, "d": d, "k": k, "kappa": 16, "devices": R},
        config={"iters": iters, "batch_size_per_shard": bs,
                "min_move_frac": -1.0, "telemetry": True},
        metrics={
            "host_driven_s": t_host, "sharded_run_s": t_run,
            "epochs_per_sec_host": iters / t_host,
            "epochs_per_sec_sharded_run": iters / t_run,
            "speedup": t_host / t_run,
            "host_syncs_host_driven": iters,
            "host_syncs_sharded_run": sc.syncs,
            "final_distortion_host": hist_host[-1],
            "final_distortion_sharded_run": float(final),
        },
        telemetry=obs_tel.to_dict(tel, rows=int(epochs)),
    )
    write_json(SHARDED_JSON, rec)


def _scale_child(quick: bool):
    """Large-k sharded run: candidate exchange wire cost vs (k, d) gather.

    A graph-kind ``ShardedEngine.run`` at a k where the old replicated
    (k, d) all-gather dwarfs the candidate-row exchange.  The exchange per
    batch step moves the gathered (R·B, C) s32 id block plus the psum'd
    (R·B, C, d) f32 candidate rows — O(R²·B·C·d) wire, INDEPENDENT of k —
    while the old path moved k·d·4 bytes per shard.  The per-shard
    candidate set is exactly B·C rows by construction (the exchange is a
    dense id block, no data-dependent dedupe), so its peak is static.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import engine, random_graph, two_means_tree
    from repro.core.distributed import ShardedEngine
    from repro.data import gmm_blobs
    from repro.obs import sync_counter
    try:
        from benchmarks.common import merge_scale_record
    except ImportError:
        from common import merge_scale_record

    n, d, k, iters = ((32768, 32, 16384, 2) if quick
                      else (262144, 64, 65536, 3))
    kappa, bs = 8, 256
    R = len(jax.devices())
    key = jax.random.PRNGKey(0)
    X = gmm_blobs(key, n, d, 256)
    # candidate QUALITY is irrelevant here (wire cost and sync count are
    # shape-determined), so a random graph stands in for a built one and
    # the bench stays a smoke-test size
    G = jnp.maximum(random_graph(key, n, kappa), 0)
    st = engine.init_state(X, two_means_tree(X, k, key), k)

    from repro.launch.mesh import data_mesh
    mesh = data_mesh(R)
    cfg = engine.EngineConfig(batch_size=bs, iters=iters, min_move_frac=-1.0)
    eng = ShardedEngine(mesh, cfg, kind="graph")
    jax.block_until_ready(eng.run(X, G, st.assign, st.D, st.cnt, key)[0])

    t0 = time.perf_counter()
    with sync_counter() as sc:
        out = eng.run(X, G, st.assign, st.D, st.cnt, key)
        sc.get(out)                                      # the ONE sync
    t_run = time.perf_counter() - t0
    assert sc.syncs == 1, sc.syncs

    C = kappa + 1                     # neighbour clusters + own cluster
    exch = R * bs * C * 4 + R * bs * C * d * 4     # ids gather + rows psum
    old = k * d * 4                                # replicated (k, d) f32
    merge_scale_record(
        SCALE_JSON, "engine",
        shapes={"n": n, "d": d, "k": k, "kappa": kappa, "devices": R},
        config={"iters": iters, "batch_size_per_shard": bs,
                "kind": "graph"},
        metrics={
            "run_s": t_run,
            "host_syncs": sc.syncs,
            "peak_candidate_rows_per_shard_step": bs * C,
            "candidate_width": C,
            "exchange_bytes_per_step": exch,
            "old_kd_allgather_bytes_per_step": old,
            "exchange_vs_kd_ratio": exch / old,
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
        ("engine/scale_sharded_run", m["engine.run_s"] * 1e6,
         f"k={rec['shapes']['engine.k']};syncs={m['engine.host_syncs']};"
         f"cand_rows_per_step={m['engine.peak_candidate_rows_per_shard_step']};"
         f"exchange_vs_kd={m['engine.exchange_vs_kd_ratio']:.3f}x"),
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
    m, R = rec["metrics"], rec["shapes"]["devices"]
    return [
        ("engine/sharded_host_driven", m["host_driven_s"] * 1e6,
         f"epochs_per_s={m['epochs_per_sec_host']:.2f};"
         f"syncs={m['host_syncs_host_driven']};"
         f"devices={R};"
         f"final={m['final_distortion_host']:.4f}"),
        ("engine/sharded_device_resident_run", m["sharded_run_s"] * 1e6,
         f"epochs_per_s={m['epochs_per_sec_sharded_run']:.2f};"
         f"syncs={m['host_syncs_sharded_run']};telemetry=on;"
         f"devices={R};"
         f"final={m['final_distortion_sharded_run']:.4f};"
         f"speedup={m['speedup']:.2f}x"),
    ]


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
    quick = args.quick
    if args.child:
        (_scale_child if args.kind == "scale" else _sharded_child)(quick)
        return
    rows = []
    if args.mode in ("single", "both"):
        rows += run_single(quick)
    if args.mode in ("sharded", "both"):
        rows += run_sharded(quick)
    if args.mode == "scale":
        rows += run_scale(quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
