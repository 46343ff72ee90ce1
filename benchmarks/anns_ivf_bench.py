"""IVF multi-probe vs. graph search at matched recall (index subsystem).

The coarse quantizer is the paper's GK-means; the claim under test is that
its clustering is good enough that probing a few percent of the database
reaches ANN-grade recall@10, competitive with greedy KNN-graph search.

Modes (the CI bench-smoke step runs ``--quick --mode both``):

  single   the nprobe sweep (per-query and query-grouped scan layouts) plus
           the graph-search baseline; pins recall@10 = 1.0 at ~0.4% scanned
           (nprobe=1 on the quick synth workload — the PR 1 pin);
  sharded  ``core.distributed.ShardedIvf`` serving on the real devices, or
           on 4 forced host devices in a CPU child
           (``benchmarks.common.run_sharded_mode``):
           bit-exact parity with single-device search and exactly 1
           transfer-guard-verified host sync per query batch (f32 AND
           codec'd rerank=0 search);
  pq       the recall-vs-compression sweep over the compressed-list codecs
           (codec x nprobe x rerank depth, `index.quantize` +
           `kernels.ivf_scan_adc`); pins recall@10 >= 0.98 after exact
           rerank at <= 0.5% of the database scanned, with >= 3x fewer
           HBM bytes streamed than the f32 scan.

Emits ``BENCH_anns_ivf.json``, ``BENCH_anns_ivf_sharded.json`` and
``BENCH_anns_ivf_pq.json`` (``repro.bench.v1`` run records; the sharded
search runs with ``telemetry=True`` — scanned-rows/scan-fraction/
scanned-bytes counters ride the same single ``obs.sync_counter``-verified
host sync).
"""
from __future__ import annotations

import argparse
import time

SHARDED_DEVICES = 4
OUT_JSON = "BENCH_anns_ivf.json"
SHARDED_JSON = "BENCH_anns_ivf_sharded.json"
PQ_JSON = "BENCH_anns_ivf_pq.json"


def run_single(quick: bool = True):
    import jax
    import jax.numpy as jnp

    from repro import index as ivf
    from repro.core import build_knn_graph, gk_means, graph_search
    from repro.data import gmm_blobs
    from repro.obs import run_record, write_json

    n, d, k = (32768, 64, 256) if quick else (1_000_000, 128, 4096)
    X = gmm_blobs(jax.random.PRNGKey(0), n, d, 512)
    nq, topk = 256, 10
    q = X[:nq] + 0.05 * jax.random.normal(jax.random.PRNGKey(9), (nq, d))
    # dot-product form: (nq, n) scores, no (nq, n, d) intermediate
    d2 = (jnp.sum(q * q, -1)[:, None] + jnp.sum(X * X, -1)[None]
          - 2.0 * (q @ X.T))
    gt = jnp.argsort(d2, axis=1)[:, :topk]

    def recall(ids):
        hits = (ids[:, :, None] == gt[:, None, :]).any(-1)
        return float(jnp.mean(hits.astype(jnp.float32)))

    rows = []
    t0 = time.perf_counter()
    res = gk_means(X, k, kappa=16, xi=64, tau=3, iters=8,
                   key=jax.random.PRNGKey(1))
    index = ivf.build_ivf(X, res, block_rows=128)
    rows.append(("ivf/build", (time.perf_counter() - t0) * 1e6,
                 f"k={res.k} rows={index.n_rows}"))

    metrics = {}
    for nprobe in (1, 2, 4, 8, 16, 32):
        f = lambda qq: ivf.search(index, qq, topk=topk, nprobe=nprobe)
        ids, _ = f(q)
        t0 = time.perf_counter()
        ids, _ = f(q)
        jax.block_until_ready(ids)
        us_q = (time.perf_counter() - t0) * 1e6 / nq
        frac = ivf.scan_fraction(index, q, nprobe=nprobe)
        r = recall(ids)
        rows.append((f"ivf/nprobe={nprobe}", us_q,
                     f"recall@10={r:.3f} scan={100 * frac:.1f}%"))
        if nprobe == 1:
            metrics["recall_at_10_nprobe1"] = r
            metrics["scan_frac_nprobe1"] = float(frac)

    # query-grouped scan layout: same probes, tile loads amortized per group
    for nprobe, G in ((8, 8), (16, 8)):
        f = lambda qq: ivf.search(index, qq, topk=topk, nprobe=nprobe,
                                  qgroup=G)
        gids, _ = f(q)
        t0 = time.perf_counter()
        gids, _ = f(q)
        jax.block_until_ready(gids)
        us_q = (time.perf_counter() - t0) * 1e6 / nq
        rows.append((f"ivf/grouped_nprobe={nprobe}_G={G}", us_q,
                     f"recall@10={recall(gids):.3f}"))
        if nprobe == 8:
            metrics["recall_at_10_grouped_nprobe8"] = recall(gids)

    g = build_knn_graph(X, 16, xi=64, tau=3, key=jax.random.PRNGKey(2))
    for ef, iters in ((32, 24), (64, 48), (96, 64)):
        f = jax.jit(lambda qq: graph_search(X, g.ids, qq, topk=topk,
                                            ef=ef, iters=iters))
        ids, _ = f(q)
        t0 = time.perf_counter()
        ids, _ = f(q)
        jax.block_until_ready(ids)
        us_q = (time.perf_counter() - t0) * 1e6 / nq
        rows.append((f"graph/ef={ef}", us_q,
                     f"recall@10={recall(ids):.3f}"))

    write_json(OUT_JSON, run_record(
        "anns_ivf",
        shapes={"n": n, "d": d, "k": k, "topk": topk, "nq": nq},
        config={"block_rows": 128},
        metrics=metrics,
    ))
    return rows


def run_pq(quick: bool = True):
    """Recall-vs-compression sweep: codec x nprobe x rerank depth.

    The workload is ``run_single``'s quick synth set; the sweep scans the
    same probed lists through the f32 kernel and both compressed codecs,
    counting HBM bytes per scanned row analytically
    (``quantize.bytes_per_row`` — the same per-row cost the sharded path's
    ``scanned_bytes`` telemetry uses) and recall against brute force.
    """
    import jax
    import jax.numpy as jnp

    from repro import index as ivf
    from repro.core import gk_means
    from repro.data import gmm_blobs
    from repro.index import quantize
    from repro.kernels import ops as kops
    from repro.obs import run_record, sync_counter, write_json

    n, d, k = (32768, 64, 256) if quick else (1_000_000, 128, 4096)
    nsub = 8
    X = gmm_blobs(jax.random.PRNGKey(0), n, d, 512)
    nq, topk = 256, 10
    q = X[:nq] + 0.05 * jax.random.normal(jax.random.PRNGKey(9), (nq, d))
    d2 = (jnp.sum(q * q, -1)[:, None] + jnp.sum(X * X, -1)[None]
          - 2.0 * (q @ X.T))
    gt = jnp.argsort(d2, axis=1)[:, :topk]

    def recall(ids):
        hits = (ids[:, :, None] == gt[:, None, :]).any(-1)
        return float(jnp.mean(hits.astype(jnp.float32)))

    rows = []
    res = gk_means(X, k, kappa=16, xi=64, tau=3, iters=8,
                   key=jax.random.PRNGKey(1))
    index = ivf.build_ivf(X, res, block_rows=128)
    indices = {"f32": index,
               "int8": ivf.quantize_index(index, "int8"),
               "pq": ivf.quantize_index(index, "pq", nsub=nsub,
                                        key=jax.random.PRNGKey(3))}
    bpr = {"f32": quantize.bytes_per_row("f32", d),
           "int8": quantize.bytes_per_row(indices["int8"].codec, d),
           "pq": quantize.bytes_per_row(indices["pq"].codec, d)}

    sweep = []
    for nprobe in (1, 2, 4, 8):
        cids, _ = kops.probe_centroids(q, index.centroids,
                                       min(nprobe, index.k))
        scanned = float(jnp.sum(index.caps[cids]))
        frac = scanned / (nq * max(index.capacity_rows, 1))
        for codec in ("f32", "int8", "pq"):
            reranks = (None,) if codec == "f32" else (0, None, 8 * topk)
            for rerank in reranks:
                kw = {} if codec == "f32" else {"codec": codec,
                                                "rerank": rerank}
                f = lambda qq: ivf.search(indices[codec], qq, topk=topk,
                                          nprobe=nprobe, **kw)
                ids, _ = f(q)
                t0 = time.perf_counter()
                ids, _ = f(q)
                jax.block_until_ready(ids)
                us_q = (time.perf_counter() - t0) * 1e6 / nq
                r = recall(ids)
                entry = {"codec": codec, "nprobe": nprobe,
                         "rerank": rerank, "recall_at_10": r,
                         "scan_frac": frac, "us_per_query": us_q,
                         "scanned_rows": scanned,
                         "scanned_bytes": scanned * bpr[codec],
                         "bytes_per_row": bpr[codec]}
                sweep.append(entry)
                tag = "" if rerank is None else f"_rerank={rerank}"
                rows.append((f"pq/{codec}_nprobe={nprobe}{tag}", us_q,
                             f"recall@10={r:.3f} scan={100 * frac:.2f}% "
                             f"bytes/row={bpr[codec]}"))

    # the PR gate: at <= 0.5% of the database scanned, both codecs reach
    # recall@10 >= 0.98 AFTER the exact-rerank tail while streaming >= 3x
    # fewer HBM bytes than the f32 scan of the same lists
    gate = [e for e in sweep if e["scan_frac"] <= 0.005
            and e["codec"] != "f32" and e["rerank"] == 8 * topk]
    assert gate, "no codec sweep point at <= 0.5% scanned"
    for e in gate:
        assert e["recall_at_10"] >= 0.98, e
        assert bpr["f32"] >= 3 * e["bytes_per_row"], e

    # codec'd serving stays ONE host sync per query batch: the dispatch
    # makes no device->host transfer, the single sc.get is the only sync
    with sync_counter() as sc:
        out = ivf.search(indices["pq"], q, topk=topk, nprobe=8, codec="pq")
        sc.get(out)
    assert sc.syncs == 1, sc.syncs

    best = {e["codec"]: e for e in gate}
    write_json(PQ_JSON, run_record(
        "anns_ivf_pq",
        shapes={"n": n, "d": d, "k": k, "topk": topk, "nq": nq,
                "nsub": nsub},
        config={"block_rows": 128, "gate_scan_frac": 0.005,
                "gate_recall": 0.98, "gate_bytes_ratio": 3.0},
        metrics={
            "sweep": sweep,
            "bytes_per_row": bpr,
            "syncs_per_query_batch": sc.syncs,
            **{f"recall_at_10_{c}_gate": e["recall_at_10"]
               for c, e in best.items()},
            **{f"bytes_ratio_f32_over_{c}": bpr["f32"] / e["bytes_per_row"]
               for c, e in best.items()},
        },
    ))
    return rows


def _sharded_child(quick: bool):
    """ShardedIvf serving on forced host devices + bit-exact parity check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import index as ivf
    from repro.core import gk_means
    from repro.core.distributed import ShardedIvf
    from repro.data import gmm_blobs
    from repro.obs import run_record, sync_counter, write_json
    from repro.obs import telemetry as obs_tel

    n, d, k = (8192, 32, 64) if quick else (131072, 64, 512)
    R = len(jax.devices())
    X = gmm_blobs(jax.random.PRNGKey(0), n, d, 128)
    nq, topk, nprobe = 128, 10, 8
    q = X[:nq] + 0.05 * jax.random.normal(jax.random.PRNGKey(9), (nq, d))
    d2 = (jnp.sum(q * q, -1)[:, None] + jnp.sum(X * X, -1)[None]
          - 2.0 * (q @ X.T))
    gt = jnp.argsort(d2, axis=1)[:, :topk]

    res = gk_means(X, k, kappa=16, xi=64, tau=3, iters=6,
                   key=jax.random.PRNGKey(1))
    index = ivf.build_ivf(X, res, block_rows=64)
    from repro.launch.mesh import data_mesh
    mesh = data_mesh(R)
    sivf = ShardedIvf(mesh, index)

    i1, d1 = jax.device_get(ivf.search(index, q, topk=topk, nprobe=nprobe))
    jax.block_until_ready(sivf.search(q, topk=topk, nprobe=nprobe,
                                      telemetry=True))   # warm

    # ONE host sync per query batch, with scanned-rows telemetry riding it:
    # the dispatch makes no device->host transfer; the single sc.get below
    # is the only sync
    t0 = time.perf_counter()
    with sync_counter() as sc:
        out = sivf.search(q, topk=topk, nprobe=nprobe, telemetry=True)
        i2, d2s, tel = sc.get(out)                       # the ONE sync
    t_sharded = time.perf_counter() - t0
    assert sc.syncs == 1, sc.syncs

    np.testing.assert_array_equal(i1, i2)
    np.testing.assert_array_equal(d1, d2s)
    hits = (i2[:, :, None] == np.asarray(gt)[:, None, :]).any(-1)
    rec10 = float(hits.mean())

    # codec'd sharded serving: pq slabs shard like the f32 slabs, the
    # rerank=0 path is bit-exact with single-device codec search, and the
    # scanned_bytes telemetry rides the same single verified sync
    pqix = ivf.quantize_index(index, "pq", nsub=8, key=jax.random.PRNGKey(3))
    spq = ShardedIvf(mesh, pqix)
    ip, dp = jax.device_get(ivf.search(pqix, q, topk=topk, nprobe=nprobe,
                                       codec="pq", rerank=0))
    jax.block_until_ready(spq.search(q, topk=topk, nprobe=nprobe,
                                     codec="pq", rerank=0,
                                     telemetry=True))     # warm
    t0 = time.perf_counter()
    with sync_counter() as scq:
        out = spq.search(q, topk=topk, nprobe=nprobe, codec="pq", rerank=0,
                         telemetry=True)
        ip2, dp2, telq = scq.get(out)                    # the ONE sync
    t_pq = time.perf_counter() - t0
    assert scq.syncs == 1, scq.syncs
    np.testing.assert_array_equal(ip, ip2)
    np.testing.assert_array_equal(dp, dp2)
    pq_bytes = float(obs_tel.column(telq, "scanned_bytes")[0])
    f32_bytes = float(obs_tel.column(tel, "scanned_rows")[0]) * 4 * d
    assert pq_bytes > 0 and f32_bytes >= 3 * pq_bytes, (f32_bytes, pq_bytes)

    rec = run_record(
        "anns_ivf_sharded",
        shapes={"n": n, "d": d, "k": k, "devices": R, "nq": nq},
        config={"nprobe": nprobe, "topk": topk, "block_rows": 64,
                "telemetry": True},
        metrics={
            "sharded_search_s": t_sharded,
            "us_per_query_sharded": t_sharded * 1e6 / nq,
            "recall_at_10_sharded": rec10,
            "syncs_per_query_batch": sc.syncs,
            "parity_bitexact_vs_single_device": True,
            "pq_sharded_search_s": t_pq,
            "pq_syncs_per_query_batch": scq.syncs,
            "pq_parity_bitexact_vs_single_device": True,
            "pq_scanned_bytes": pq_bytes,
            "f32_scanned_bytes": f32_bytes,
        },
        telemetry=obs_tel.to_dict(
            tel, slots=["scanned_rows", "scanned_rows_max_shard",
                        "scan_frac"]),
    )
    write_json(SHARDED_JSON, rec)


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
    scan_frac = rec.get("telemetry", {}).get("scan_frac", [-1.0])[0]
    return [
        ("ivf/sharded_search", m["sharded_search_s"] * 1e6,
         f"us_per_query={m['us_per_query_sharded']:.1f};"
         f"syncs={m['syncs_per_query_batch']};telemetry=on;"
         f"devices={rec['shapes']['devices']};parity=bitexact;"
         f"recall@10={m['recall_at_10_sharded']:.3f};"
         f"scan={100 * scan_frac:.1f}%"),
    ]


def run(quick: bool = True):
    """All modes — the benchmarks.run harness entry point."""
    return run_single(quick) + run_sharded(quick) + run_pq(quick)


def main():
    ap = argparse.ArgumentParser()
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--quick", dest="quick", action="store_true",
                      default=True)
    size.add_argument("--full", dest="quick", action="store_false")
    ap.add_argument("--mode", default="both",
                    choices=["single", "sharded", "pq", "both"])
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    from repro.launch import runtime
    runtime.init()
    if args.child:
        _sharded_child(args.quick)
        return
    rows = []
    if args.mode in ("single", "both"):
        rows += run_single(args.quick)
    if args.mode in ("sharded", "both"):
        rows += run_sharded(args.quick)
    if args.mode in ("pq", "both"):
        rows += run_pq(args.quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
