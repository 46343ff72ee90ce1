"""Traffic runner for graph builds: each timed call is one
``GraphBuilder(GraphBuildConfig(kappa, xi, tau)).build(X, key)`` with a
fresh key, on the same data.

The config gives the build (``kappa``, ``xi``, ``tau``).  Traffic keys:
``sample_rows`` (rows of each
build's graph compared with the reference), ``checked_builds`` (how many of
the window's builds, drawn from the seed, are compared).
"""
from __future__ import annotations

import jax
import numpy as np

from bench import data as bdata
from bench import reference as ref
from bench import work as bwork

REF_BLOCK = 65536


class State:
    pass


def setup(cell, key, seed, log):
    from repro.core import GraphBuildConfig, GraphBuilder

    cfg, tr = cell.config, cell.traffic
    st = State()
    st.cfg, st.tr, st.seed = cfg, tr, seed
    kd, st.kb = jax.random.split(key)
    st.X = bdata.gmm_blobs(kd, cfg["n"], cfg["d"], cfg["components"])
    st.gcfg = GraphBuildConfig(kappa=cfg["kappa"], xi=cfg["xi"],
                               tau=cfg["tau"], force=cell.force)
    st.graphs = GraphBuilder(st.gcfg)
    # one whole build warms the program (the first call of a jitted
    # program traces and loads it, which must not fall in the window)
    jax.block_until_ready(st.graphs.build(st.X, jax.random.fold_in(
        st.kb, 1 << 30)))
    st.outs = []
    return st


def call(st, i):
    g, _ = st.graphs.build(st.X, jax.random.fold_in(st.kb, i))
    st.outs.append((g.ids, g.dist))
    return g.ids, g.dist


def end_to_end(st, win):
    rows = st.cfg["n"] * st.cfg["tau"] * win.calls
    return {"graph_rows_per_s": rows / win.elapsed}


def counts(st, win):
    return {"calls": win.calls, "rounds": win.calls * st.cfg["tau"]}


def work(st, win):
    c = st.cfg
    f, b = bwork.graph_build(c["n"], c["d"], c["kappa"], st.cfg["tau"],
                             st.gcfg.cap_factor * c["xi"], st.gcfg.spill)
    return {"refine_merge": (f * win.calls, b * win.calls)}


def attempted(st, win):
    return win.calls, 0


def check(st, win, seed, log):
    """Each checked build's lists on rows drawn from the seed: reported
    distances against exact ones, ids against brute-force κ-NN."""
    cfg, tr = st.cfg, st.tr
    st.graphs = None
    rng = np.random.default_rng(seed % (1 << 63))
    picks = rng.choice(len(st.outs), min(tr["checked_builds"],
                                         len(st.outs)), replace=False)
    dist_err, worst_recall, bad = 0.0, 1.0, 0
    for p in sorted(picks):
        ids, dist = st.outs[p]
        rows = np.sort(rng.choice(cfg["n"], tr["sample_rows"],
                                  replace=False)).astype(np.int32)
        r = jax.numpy.asarray(rows)
        gi, gd = ids[r], dist[r]
        xr = st.X[r]
        exact = ref.pair_sqdist(xr, st.X, gi)
        gt = ref.brute_topk(xr, st.X, r, k=cfg["kappa"], block=min(REF_BLOCK, cfg["n"]))
        gi, gd, exact, gt = jax.device_get((gi, gd, exact, gt))
        ok = gi >= 0
        dist_err = max(dist_err, ref.rel_err(np.where(ok, gd, np.inf),
                                             exact))
        worst_recall = min(worst_recall, ref.recall(gi, gt))
        bad += ref.bad_slots(gi, rows)
        log(f"[check] build {p}: recall@{cfg['kappa']} "
            f"{ref.recall(gi, gt):.6f} on {len(rows)} rows")
    return {"dist_err": dist_err, "recall_short": 1.0 - worst_recall,
            "bad_slots": float(bad)}
