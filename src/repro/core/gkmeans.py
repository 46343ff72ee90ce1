"""GK-means (paper Alg. 2) — graph-driven boost k-means, the paper's headline.

Pipeline (paper §4.5 summary): (1) build an approximate KNN graph with Alg. 3
(which itself calls fast k-means), (2) initialise k clusters with the 2M tree,
(3) run graph-guided engine epochs where each sample only scores the clusters
of its kappa graph neighbours — O(n*kappa*d) per epoch, independent of k.

The whole epoch loop runs device-resident through ``engine.run``: early stop,
per-epoch distortion (O(k·d) from the running statistics) and the move
counters all live inside one ``lax.while_loop`` trace, so a full gk_means
run performs exactly ONE host sync regardless of `iters` (the pre-engine
driver synced per epoch for its O(n·d) distortion recompute).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.graph_build import BuildDiagnostics
from repro.core.knn_graph import KnnGraph, build_knn_graph
from repro.core.two_means import pad_plan, two_means_tree
from repro.obs.timing import span


@dataclass
class GKMeansResult:
    assign: jax.Array          # (n,) int32
    centroids: jax.Array       # (k, d) float32
    k: int
    distortion: float
    history: List[float]       # per-epoch distortion
    moves: List[int]           # per-epoch accepted moves
    graph: Optional[KnnGraph]
    # {"total": s}: host seconds from entry until the results were ready;
    # stage device times come from the named scopes in a profile
    seconds: dict = field(default_factory=dict)
    # per-round Alg. 3 build observability (None when a graph was passed in)
    graph_diag: Optional[BuildDiagnostics] = None
    # per-epoch engine Telemetry (None unless gk_means(telemetry=True));
    # rows past the early stop are zero — truncate with `epochs` like history
    telemetry: Optional["object"] = None
    # distortion of the 2M-tree initialisation, before any engine epoch
    distortion_init: float = float("nan")


def _tree_init(X: jax.Array, k: int, key: jax.Array) -> jax.Array:
    """Equal-size 2M-tree initialisation, padding (n, k) as needed."""
    n = X.shape[0]
    n2, k2 = pad_plan(n, k)
    if n2 > n:
        extra = jax.random.randint(jax.random.fold_in(key, 7),
                                   (n2 - n,), 0, n, dtype=jnp.int32)
        Xp = jnp.concatenate([X, X[extra]], axis=0)
    else:
        Xp = X
    assign = two_means_tree(Xp, k2, key)
    return assign[:n]


def gk_means(
    X: jax.Array,
    k: int,
    *,
    kappa: int = 32,
    xi: int = 64,
    tau: int = 8,
    iters: int = 20,
    batch_size: int = 1024,
    key: jax.Array,
    graph: Optional[KnnGraph] = None,
    mode: str = "bkm",            # 'bkm' (paper) or 'lloyd' (§5.2 variant)
    min_move_frac: float = 1e-4,  # early stop when epoch moves fall below
    guided_graph: bool = True,
    telemetry: bool = False,      # in-trace per-epoch engine Telemetry
) -> GKMeansResult:
    """Cluster X (n, d) into k clusters (k is rounded up to a power of two).

    graph: pass a pre-built KnnGraph (e.g. from NN-descent) to reproduce the
    paper's "KGraph+GK-means" configuration; None builds Alg. 3's own graph.
    """
    n, _ = X.shape
    _, k2 = pad_plan(n, k)
    kg, ki, kb = jax.random.split(key, 3)

    gdiag = None
    # graph build, init and engine run are dispatched back to back with no
    # host sync in between; the span closes on the run's ONE host sync, so
    # its seconds are the whole job's
    with span("repro.gk_means") as sp:
        if graph is None:
            graph, gdiag = build_knn_graph(X, kappa, xi=xi, tau=tau, key=kg,
                                           guided=guided_graph,
                                           return_diagnostics=True)
        state = engine.init_state(X, _tree_init(X, k2, ki), k2)
        dist0_d = engine.stats_distortion(
            jnp.sum(jnp.square(X.astype(jnp.float32))), state.D, state.cnt, n)
        source = engine.graph_source(graph.ids)
        cfg = engine.EngineConfig(batch_size=min(batch_size, n), mode=mode,
                                  iters=iters, min_move_frac=min_move_frac,
                                  telemetry=telemetry)
        state, hist_d, moves_d, epochs_d, final_d, tel_d = engine.run(
            X, state, source, kb, cfg)
        C = state.D / jnp.maximum(state.cnt, 1.0)[:, None]

        # the run's ONE host sync: everything below is numpy (the telemetry
        # rides the same sync — it was accumulated inside the run's
        # while_loop); a block on ``sp.result`` first would be a second
        state, hist, moves, epochs, final, C, tel, dist0 = jax.device_get(
            (state, hist_d, moves_d, epochs_d, final_d, C, tel_d, dist0_d))

    epochs = int(epochs)
    history = [float(h) for h in hist[:epochs]]
    return GKMeansResult(state.assign, C, k2, float(final), history,
                         [int(m) for m in moves[:epochs]], graph,
                         {"total": sp.seconds},
                         gdiag, tel, float(dist0))
