"""Traffic runner for clustering epochs: each timed call is one
``engine.run`` of a fixed number of epochs from the same start state, with
early stop off, so every call does the same work.

Set-up makes the start from the seed in the benchmark's own code (k
random rows as centroids, each row at its nearest: ``data.random_row_start``,
k rounded up to a power of two as the program's job rounds it), and has
the program build the KNN graph as the config gives it (``kappa``, ``xi``,
``tau``); the check counts that graph's faulty list entries over every
row.  Traffic keys: ``epochs``, ``batch``, ``mode`` (bkm).

Under ``--control precision`` the engine also runs its own bf16 move
payload (``payload_bf16``): the precision of its dots steers only which
moves are taken, and the outputs cannot tell that from the rounding of
any two sound runs (``PERF.md``), while the payload's precision reaches
the returned statistics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import data as bdata
from bench import reference as ref
from bench import work as bwork

REF_BLOCK = 65536
START_BLOCK = 8192


class State:
    pass


def setup(cell, key, seed, log):
    from repro.core import GraphBuildConfig, GraphBuilder, engine

    cfg, tr = cell.config, cell.traffic
    st = State()
    st.cfg, st.tr = cfg, tr
    kd, kg, kt, st.ke = jax.random.split(key, 4)
    n = cfg["n"]
    st.X = bdata.gmm_blobs(kd, n, cfg["d"], cfg["components"])
    g, _ = GraphBuilder(GraphBuildConfig(
        kappa=cfg["kappa"], xi=cfg["xi"], tau=cfg["tau"],
        force=cell.force)).build(st.X, kg)
    st.G = g.ids
    del g
    st.k = bdata.lists_for(cfg["k"])
    st.a0 = bdata.random_row_start(st.X, kt, k=st.k, block=START_BLOCK)
    st.s0 = engine.init_state(st.X, st.a0, st.k)
    st.source = engine.graph_source(st.G)
    # min_move_frac < 0: the run never stops early
    st.ecfg = engine.EngineConfig(batch_size=tr["batch"], mode=tr["mode"],
                                  iters=tr["epochs"], min_move_frac=-1.0,
                                  force=cell.force)
    if cell.control == "precision":
        # the engine's own lower-precision path: moved rows added to the
        # statistics in bf16
        st.ecfg = st.ecfg._replace(sparse_updates=True, payload_bf16=True)
    st.run = engine.run
    jax.block_until_ready(st.run(st.X, st.s0, st.source,
                                 jax.random.fold_in(st.ke, 1 << 30), st.ecfg))
    st.outs = []
    return st


def call(st, i):
    key = jax.random.fold_in(st.ke, i)
    s, _, _, epochs, final, _ = st.run(st.X, st.s0, st.source, key, st.ecfg)
    st.outs.append((key, s.assign, s.D, s.cnt, epochs, final))
    return s.assign, s.D, s.cnt, final


def end_to_end(st, win):
    rows = st.cfg["n"] * st.tr["epochs"] * win.calls
    return {"cluster_rows_per_s": rows / win.elapsed}


def counts(st, win):
    return {"calls": win.calls, "epochs": win.calls * st.tr["epochs"]}


def work(st, win):
    c = st.cfg
    f, b = bwork.engine_scoring(c["n"], c["kappa"], c["d"])
    e = win.calls * st.tr["epochs"]
    return {"gather_score": (f * e, b * e)}


def attempted(st, win):
    return win.calls, 0


def check(st, win, seed, log):
    """Every call: the returned statistics against segment sums of the
    returned assignment, the reported distortion against the exact one.
    One call drawn from the seed: the assignment against the reference
    boost k-means run from the same start, graph and key.  The graph the
    program built in set-up: faulty entries of its lists, every row."""
    cfg, tr = st.cfg, st.tr
    n, k = cfg["n"], st.k
    graph_bad = float(ref.list_faults(st.G))
    stats_err, dist_gap, bad = 0.0, 0.0, 0
    for key, a, D, cnt, epochs, final in st.outs:
        Dr, cr = ref.segment_stats(st.X, a, k=k)
        exact = ref.distortion(st.X, a, Dr, cr, block=REF_BLOCK)
        a_h, D_h, c_h, Dr_h, cr_h = jax.device_get((a, D, cnt, Dr, cr))
        bad += int(np.sum((a_h < 0) | (a_h >= k)))
        bad += int(np.sum(np.abs(c_h - cr_h)))
        bad += n * abs(int(epochs) - tr["epochs"])
        norm = np.linalg.norm(Dr_h, axis=1)
        scale = np.maximum(norm, np.median(norm))
        stats_err = max(stats_err, float(np.max(
            np.linalg.norm(D_h - Dr_h, axis=1) / scale)))
        dist_gap = max(dist_gap, abs(float(final) - float(exact))
                       / float(exact))
    rng = np.random.default_rng(seed % (1 << 63))
    key, a, *_ = st.outs[int(rng.integers(len(st.outs)))]
    a_ref = ref.bkm_epochs(st.X, st.G, st.a0, key, k=k, epochs=tr["epochs"],
                           batch=tr["batch"])
    mismatch = float(jnp.mean((a_ref != a).astype(jnp.float32)))
    moved = float(jnp.mean((a_ref != st.a0).astype(jnp.float32)))
    D0, c0 = ref.segment_stats(st.X, st.a0, k=k)
    start = float(ref.distortion(st.X, st.a0, D0, c0, block=REF_BLOCK))
    log(f"[check] reference moved {moved:.6f} of rows from the start; "
        f"program and reference differ on {mismatch:.6f}; a state left "
        f"unchanged would read distortion_gap "
        f"{abs(start - float(final)) / start:.6f}")
    return {"assign_mismatch": mismatch, "stats_err": stats_err,
            "distortion_gap": dist_gap, "bad_rows": float(bad),
            "graph_bad": graph_bad}
