"""Traffic runner for index serving: a closed loop of query batches
through ``index.search`` — the next batch goes when the last returned.

Set-up builds the deployment's index: ``lloyd_iters`` Lloyd iterations
into the config's ``lists`` cells from centroids at random rows (so the
lists are as unequal as k-means makes them), ``build_ivf``, and a codec
where the traffic asks for one.  The rows and the index are made from the
traffic's fixed ``index_seed``, the same in every run, as a deployment
serves one index: the longest list sets the scan's grid, and it differs
from one drawn index to the next.  The data rows are then dropped from the
device: the index holds the deployment.  Queries are held-out draws from
the same mixture under ``--seed``; batch i of the window sends pool batch
``order[i % B]``.

Traffic keys: ``codec`` (f32 | pq), ``batch``, ``topk``, ``nprobe``,
``rerank`` (pq only), ``pool_batches``, ``lloyd_iters``,
``checked_batches``, ``index_seed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import data as bdata
from bench import reference as ref
from bench import work as bwork

REF_BLOCK = 32768


class State:
    pass


class _Clustering:
    def __init__(self, assign, centroids, k):
        self.assign, self.centroids, self.k = assign, centroids, k


def setup(cell, key, seed, log):
    from repro import index as ivf
    from repro.core import init_random, pad_plan
    from repro.kernels import ops

    cfg, tr = cell.config, cell.traffic
    st = State()
    st.cfg, st.tr, st.force = cfg, tr, cell.force
    st.kd, kt, kc = jax.random.split(bdata.seed_key(tr["index_seed"]), 3)
    st.kq, ko = jax.random.split(key)
    n, nq = cfg["n"], tr["batch"] * tr["pool_batches"]
    X, st.Q = bdata.rows_and_queries(st.kd, st.kq, n, nq, cfg["d"],
                                     cfg["components"])
    # centroids start at random rows (the 2M tree needs several copies of
    # the rows and does not fit one chip at d = 960), then Lloyd
    _, k = pad_plan(n, cfg["lists"])
    C = init_random(X, k, kt)
    assign = ops.assign_centroids(X, C, force=cell.force)[0]
    for _ in range(tr["lloyd_iters"] - 1):
        D, cnt = ref.segment_stats(X, assign, k=k)
        C = jnp.where((cnt > 0)[:, None], D / jnp.maximum(cnt, 1.0)[:, None],
                      C)
        assign = ops.assign_centroids(X, C, force=cell.force)[0]
    D, cnt = ref.segment_stats(X, assign, k=k)
    C = jnp.where((cnt > 0)[:, None], D / jnp.maximum(cnt, 1.0)[:, None], C)
    st.index = ivf.build_ivf(X, _Clustering(assign, C, k),
                             block_rows=cfg["block_rows"])
    if tr["codec"] == "pq":
        st.index = ivf.quantize_index(st.index, "pq", nsub=cfg["pq_nsub"],
                                      key=kc)
    sizes = np.bincount(np.asarray(assign), minlength=k)
    st.sizes = sizes
    log(f"[index] {k} lists, {st.index.n_rows} packed rows, "
        f"{st.index.max_list_tiles} tiles in the longest; list sizes "
        f"min/p5/p50/p95/max {np.percentile(sizes, [0, 5, 50, 95, 100])}, "
        f"{int(np.sum(sizes == 0))} empty")
    del X, assign, D, cnt
    st.order = np.asarray(jax.random.permutation(ko, tr["pool_batches"]))
    B = tr["batch"]
    st.batches = [st.Q[b * B:(b + 1) * B] for b in range(tr["pool_batches"])]
    st.search = ivf.search
    for i in range(2):
        jax.block_until_ready(_search(st, st.batches[0]))
    st.outs = []
    return st


def _search(st, Qb):
    tr = st.tr
    return st.search(st.index, Qb, topk=tr["topk"], nprobe=tr["nprobe"],
                     codec=tr["codec"], rerank=tr.get("rerank"),
                     force=st.force)


def call(st, i):
    b = int(st.order[i % len(st.order)])
    ids, d2 = _search(st, st.batches[b])
    st.outs.append((b, ids, d2))
    return ids, d2


def end_to_end(st, win):
    lat = np.asarray(win.latencies) * 1e3
    return {"search_qps": st.tr["batch"] * win.calls / win.elapsed,
            "search_p95_ms": float(np.percentile(lat, 95))}


def counts(st, win):
    return {"calls": win.calls, "batches": win.calls}


def _scanned_rows(st):
    """Live rows of each pool query's ``nprobe`` nearest lists (exact
    probe against the index's centroids)."""
    tr = st.tr
    C = st.index.centroids
    csq = jnp.sum(C * C, axis=1)
    rows = []
    B = tr["batch"]
    for b in range(tr["pool_batches"]):
        q = st.Q[b * B:(b + 1) * B]
        d2 = csq[None, :] - 2.0 * ref._dot_t(q, C)
        _, cells = jax.lax.top_k(-d2, tr["nprobe"])
        rows.append(np.asarray(cells))
    cells = np.concatenate(rows)
    return st.sizes[cells].sum(axis=1)          # (pool queries,)


def work(st, win):
    tr, cfg = st.tr, st.cfg
    per_q = _scanned_rows(st)
    B = tr["batch"]
    scanned = sum(int(per_q[b * B:(b + 1) * B].sum()) for b, _, _ in st.outs)
    q = B * len(st.outs)
    if tr["codec"] == "f32":
        return {"ivf_scan": bwork.ivf_scan(scanned, q, cfg["d"],
                                           tr["topk"])}
    depth = max(tr.get("rerank") or 4 * tr["topk"], tr["topk"])
    return {"ivf_scan_adc": bwork.ivf_scan_adc(scanned, q, cfg["pq_nsub"],
                                               256, depth)}


def attempted(st, win):
    return st.tr["batch"] * win.calls, 0


def check(st, win, seed, log):
    """Batches drawn from the seed among those answered: ids against
    brute-force top-k over every row, distances against exact ones — the
    largest relative gap, and the largest of the batches' median gaps,
    which a lower matmul precision moves as steadily (the float32 norms
    set the program's own floor)."""
    cfg, tr = st.cfg, st.tr
    st.index = None
    X, _ = bdata.rows_and_queries(st.kd, st.kq, cfg["n"],
                                  tr["batch"] * tr["pool_batches"], cfg["d"],
                                  cfg["components"])
    rng = np.random.default_rng(seed % (1 << 63))
    picks = rng.choice(len(st.outs), min(tr["checked_batches"],
                                         len(st.outs)), replace=False)
    B = tr["batch"]
    dist_err, gaps, recalls, bad = 0.0, [], [], 0
    none = jnp.full((B,), -1, jnp.int32)
    for p in sorted(picks):
        b, ids, d2 = st.outs[p]
        Qb = st.batches[b]
        gt = ref.brute_topk(Qb, X, none, k=tr["topk"], block=min(REF_BLOCK, cfg["n"]))
        exact = ref.pair_sqdist(Qb, X, ids)
        ids, d2, gt, exact = jax.device_get((ids, d2, gt, exact))
        got = np.where(ids >= 0, d2, np.inf)
        dist_err = max(dist_err, ref.rel_err(got, exact))
        gaps.append(ref.rel_err(got, exact, "median"))
        recalls.append(ref.recall(ids, gt))
        bad += ref.bad_slots(ids)
    r = float(np.mean(recalls))
    log(f"[check] recall@{tr['topk']} {r:.6f} over {len(picks) * B} "
        f"queries (worst batch {min(recalls):.6f})")
    return {"recall_short": 1.0 - r, "dist_err": dist_err,
            "dist_err_median": float(np.max(gaps)), "bad_ids": float(bad)}
