"""Distributed engine epochs (shard_map) on virtual CPU devices — subprocess
tests (the parent process must keep seeing the real 1-device platform)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=timeout)


CODE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.data import gmm_blobs
from repro.core import (build_knn_graph, two_means_tree, init_state,
                        distortion, cluster_stats)
from repro.core.distributed import make_sharded_epoch, sharded_distortion

key = jax.random.PRNGKey(0)
n, d, k = 4096, 16, 32
assert len(jax.devices()) == 8
X = gmm_blobs(key, n, d, 32)
g = build_knn_graph(X, 8, xi=32, tau=3, key=key)
a0 = two_means_tree(X, k, key)
st = init_state(X, a0, k)
from repro.launch.mesh import data_mesh
mesh = data_mesh(8)
epoch = make_sharded_epoch(mesh, batch_size=128)
dist_fn = sharded_distortion(mesh)
assign, D, cnt = st.assign, st.D, st.cnt
G = jnp.maximum(g.ids, 0)
d_first = float(dist_fn(X, assign, D, cnt))
for t in range(6):
    assign, D, cnt, moves = epoch(X, G, assign, D, cnt,
                                  jax.random.fold_in(key, t))
d_last = float(distortion(X, assign, k))
assert d_last < d_first, (d_first, d_last)
s2 = cluster_stats(X, assign, k)
np.testing.assert_allclose(np.asarray(D), np.asarray(s2.D),
                           rtol=1e-4, atol=1e-2)
np.testing.assert_allclose(np.asarray(cnt), np.asarray(s2.cnt))
assert float(cnt.min()) >= 1.0
# sharded distortion agrees with the single-device formula
np.testing.assert_allclose(float(dist_fn(X, assign, D, cnt)), d_last,
                           rtol=1e-4)
print("DIST_OK", d_first, d_last)
"""


@pytest.mark.slow
def test_sharded_epoch_8dev():
    r = _run(CODE)
    assert "DIST_OK" in r.stdout, r.stderr[-3000:]


CODE_QUALITY = r"""
import jax, jax.numpy as jnp
from repro.data import gmm_blobs
from repro.core import (build_knn_graph, two_means_tree, init_state, engine,
                        distortion)
from repro.core.distributed import make_sharded_epoch

key = jax.random.PRNGKey(0)
n, d, k = 4096, 16, 32
X = gmm_blobs(key, n, d, 32)
g = build_knn_graph(X, 8, xi=32, tau=3, key=key)
G = jnp.maximum(g.ids, 0)
a0 = two_means_tree(X, k, key)

# single-device reference (same effective batch = 128*8)
st = init_state(X, a0, k)
cfg = engine.EngineConfig(batch_size=1024)
for t in range(6):
    st = engine.epoch(X, st, engine.graph_source(G), jax.random.fold_in(key, t),
                      cfg)
ref = float(distortion(X, st.assign, k))

from repro.launch.mesh import data_mesh
mesh = data_mesh(8)
epoch = make_sharded_epoch(mesh, batch_size=128)
assign, D, cnt = a0, *init_state(X, a0, k)[1:3]
for t in range(6):
    assign, D, cnt, _ = epoch(X, G, assign, D, cnt,
                              jax.random.fold_in(key, t))
dist = float(distortion(X, assign, k))
assert dist < ref * 1.1, (dist, ref)
print("QUALITY_OK", dist, ref)
"""


@pytest.mark.slow
def test_sharded_quality_matches_single_device():
    r = _run(CODE_QUALITY)
    assert "QUALITY_OK" in r.stdout, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# topology parity: the sharded engine epoch must equal the single-device
# engine epoch run with the same R-way visit order (`cfg.shards=R`) — for
# BOTH statistic-update paths and BOTH move rules.
# ---------------------------------------------------------------------------

CODE_PARITY = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.data import gmm_blobs
from repro.core import build_knn_graph, two_means_tree, init_state, engine
from repro.core.distributed import make_sharded_epoch

key = jax.random.PRNGKey(0)
n, d, k, R = 2048, 16, 32, 4
assert len(jax.devices()) == R
X = gmm_blobs(key, n, d, 32)
g = build_knn_graph(X, 8, xi=32, tau=2, key=key)
G = jnp.maximum(g.ids, 0)
a0 = two_means_tree(X, k, key)
from repro.launch.mesh import data_mesh
mesh = data_mesh(R)
source = engine.graph_source(G)

for mode in ("bkm", "lloyd"):
    for sparse in (False, True):
        epoch = make_sharded_epoch(mesh, batch_size=128, mode=mode,
                                   sparse_updates=sparse)
        st0 = init_state(X, a0, k)
        assign, D, cnt = st0.assign, st0.D, st0.cnt
        st = init_state(X, a0, k)
        cfg = engine.EngineConfig(batch_size=128, mode=mode,
                                  sparse_updates=sparse, shards=R)
        for t in range(3):
            kt = jax.random.fold_in(key, t)
            assign, D, cnt, moves = epoch(X, G, assign, D, cnt, kt)
            st = engine.epoch(X, st, source, kt, cfg)
            np.testing.assert_array_equal(np.asarray(assign),
                                          np.asarray(st.assign),
                                          err_msg=f"{mode}/{sparse}/ep{t}")
            np.testing.assert_array_equal(np.asarray(cnt), np.asarray(st.cnt),
                                          err_msg=f"{mode}/{sparse}/ep{t}")
            assert int(moves) == int(st.moves), (mode, sparse, t)
            if sparse:
                # identical scatter over the identical gathered row order
                np.testing.assert_array_equal(np.asarray(D), np.asarray(st.D))
            else:
                np.testing.assert_allclose(np.asarray(D), np.asarray(st.D),
                                           rtol=2e-6, atol=1e-4)
print("PARITY_OK")
"""


@pytest.mark.slow
def test_sharded_single_device_parity_4dev():
    """Acceptance: identical assignments across topologies, every mode."""
    r = _run(CODE_PARITY, devices=4)
    assert "PARITY_OK" in r.stdout, r.stderr[-3000:]


CODE_DENSE_PROBE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.data import gmm_blobs
from repro.core import two_means_tree, init_state, distortion
from repro.core.distributed import make_sharded_epoch

key = jax.random.PRNGKey(0)
n, d, k = 2048, 16, 32
X = gmm_blobs(key, n, d, 32)
a0 = two_means_tree(X, k, key)
from repro.launch.mesh import data_mesh
mesh = data_mesh(4)
Gdummy = jnp.zeros((n, 1), jnp.int32)
d0 = float(distortion(X, a0, k))
for kind in ("dense", "probe"):
    st = init_state(X, a0, k)
    epoch = make_sharded_epoch(mesh, batch_size=128, kind=kind, probe_p=8)
    assign, D, cnt = st.assign, st.D, st.cnt
    for t in range(3):
        assign, D, cnt, _ = epoch(X, Gdummy, assign, D, cnt,
                                  jax.random.fold_in(key, t))
    d1 = float(distortion(X, assign, k))
    assert d1 < d0, (kind, d0, d1)
print("KINDS_OK")
"""


@pytest.mark.slow
def test_sharded_dense_and_probe_sources_4dev():
    """The CandidateSource matrix is available in the sharded topology too."""
    r = _run(CODE_DENSE_PROBE, devices=4)
    assert "KINDS_OK" in r.stdout, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# sharded_run: the whole epoch loop in ONE shard_map trace — bit-exact parity
# with the single-device `engine.run(..., shards=R)` emulation, exactly one
# host sync per run (obs.sync_counter: device->host transfers disallowed
# around the dispatch, UNCHANGED with telemetry on), and the in-trace early
# stop.
# ---------------------------------------------------------------------------

CODE_SHARDED_RUN = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.data import gmm_blobs
from repro.core import build_knn_graph, two_means_tree, init_state, engine
from repro.core.distributed import ShardedEngine
from repro.obs import sync_counter
from repro.obs import telemetry as obs_tel

key = jax.random.PRNGKey(0)
n, d, k, R = 2048, 16, 32, 4
assert len(jax.devices()) == R
X = gmm_blobs(key, n, d, 32)
g = build_knn_graph(X, 8, xi=32, tau=2, key=key)
G = jnp.maximum(g.ids, 0)
a0 = two_means_tree(X, k, key)
from repro.launch.mesh import data_mesh
mesh = data_mesh(R)
iters = 5
cfg = engine.EngineConfig(batch_size=128, sparse_updates=True, iters=iters,
                          min_move_frac=-1.0, telemetry=True)
eng = ShardedEngine(mesh, cfg)
st0 = init_state(X, a0, k)

# ONE host sync per run, with telemetry ON: compile+dispatch makes no
# device->host transfer; the per-epoch telemetry rows come back in the same
# single counted device_get as the results
with sync_counter() as sc:
    out = eng.run(X, G, st0.assign, st0.D, st0.cnt, key)
    assign, D, cnt, hist, mhist, epochs, final, tel = sc.get(out)
assert sc.syncs == 1, sc.syncs

# bit-exact parity with the single-device R-way emulation (sparse mode),
# telemetry included (i32 slots exact, f32 to float tolerance)
st = init_state(X, a0, k)
st1, hist1, mhist1, epochs1, final1, tel1 = jax.device_get(
    engine.run(X, st, engine.graph_source(G), key, cfg._replace(shards=R)))
np.testing.assert_array_equal(assign, st1.assign)
np.testing.assert_array_equal(cnt, st1.cnt)
np.testing.assert_array_equal(D, st1.D)
np.testing.assert_array_equal(mhist, mhist1)
assert int(epochs) == int(epochs1) == iters
np.testing.assert_allclose(hist, hist1, rtol=1e-5)
np.testing.assert_allclose(final, final1, rtol=1e-5)
np.testing.assert_array_equal(tel.i32, tel1.i32)
np.testing.assert_allclose(tel.f32, tel1.f32, rtol=1e-5)

# the telemetry rows agree with the returned histories
np.testing.assert_array_equal(obs_tel.column(tel, "moves"), mhist)
np.testing.assert_allclose(obs_tel.column(tel, "distortion"), hist,
                           rtol=1e-6)
assert np.all(obs_tel.column(tel, "proposed")
              >= obs_tel.column(tel, "moves"))

# telemetry OFF: same single sync, bit-identical clustering, tel is None
eng_off = ShardedEngine(mesh, cfg._replace(telemetry=False))
jax.block_until_ready(
    eng_off.run(X, G, st0.assign, st0.D, st0.cnt, key)[0])
with sync_counter() as sc0:
    out0 = eng_off.run(X, G, st0.assign, st0.D, st0.cnt, key)
    got0 = sc0.get(out0)
assert sc0.syncs == 1, sc0.syncs
assert got0[7] is None
np.testing.assert_array_equal(got0[0], assign)
np.testing.assert_array_equal(got0[4], mhist)

# the min_move_frac early stop runs inside the trace
eng2 = ShardedEngine(mesh, engine.EngineConfig(batch_size=128, iters=8,
                                               min_move_frac=1.0))
_, _, _, hist2, _, ep2, _, _ = jax.device_get(
    eng2.run(X, G, st0.assign, st0.D, st0.cnt, key))
assert int(ep2) == 1 and np.isnan(hist2[1:]).all()
print("SHARDED_RUN_OK")
"""


@pytest.mark.slow
def test_sharded_run_parity_and_single_sync_4dev():
    """Acceptance: sharded_run == engine.run(shards=R) bit-exactly, one host
    sync per run, early stop in-trace."""
    r = _run(CODE_SHARDED_RUN, devices=4)
    assert "SHARDED_RUN_OK" in r.stdout, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# sharded graph build: the whole tau-round loop in ONE shard_map trace —
# bit-exact parity with the single-device build (`GraphBuildConfig.shards=R`
# emulation), O(1) host syncs enforced by the transfer guard, both sources.
# ---------------------------------------------------------------------------

CODE_GRAPH_BUILD = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.data import gmm_blobs
from repro.core import GraphBuildConfig, GraphBuilder, build_graph
from repro.core.distributed import sharded_graph_builder
from repro.obs import sync_counter
from repro.obs import telemetry as obs_tel

key = jax.random.PRNGKey(0)
n, d, R = 2048, 16, 4
assert len(jax.devices()) == R
X = gmm_blobs(key, n, d, 32)
from repro.launch.mesh import data_mesh
mesh = data_mesh(R)

# Alg. 3 partition source: bit-exact parity, one host sync per build
cfg = GraphBuildConfig(kappa=8, xi=32, tau=3, chunk=256, shards=R)
builder = sharded_graph_builder(mesh, cfg)
g1, d1 = jax.device_get(build_graph(X, key, cfg))   # single-device, R-way
jax.block_until_ready(builder.build(X, key)[0].ids)  # warm the program
with sync_counter() as sc:
    out = builder.build(X, key)
    g2, d2 = sc.get(out)                             # the ONE sync
assert sc.syncs == 1, sc.syncs
np.testing.assert_array_equal(g1.ids, g2.ids)
np.testing.assert_array_equal(g1.dist, g2.dist)
np.testing.assert_array_equal(d1.overflow, d2.overflow)
np.testing.assert_array_equal(d1.guided_moves, d2.guided_moves)
assert int(d2.guided_moves[0]) == 0 and int(d2.guided_moves[1]) > 0
assert d2.telemetry is None                          # telemetry off

# telemetry ON: per-round rows ride the same single sync, the build is
# bit-identical, and sharded == single-device telemetry too
cfg_t = cfg._replace(telemetry=True)
builder_t = sharded_graph_builder(mesh, cfg_t)
_, d1t = jax.device_get(build_graph(X, key, cfg_t))
jax.block_until_ready(builder_t.build(X, key)[0].ids)
with sync_counter() as sct:
    out = builder_t.build(X, key)
    g2t, d2t = sct.get(out)
assert sct.syncs == 1, sct.syncs
np.testing.assert_array_equal(g2t.ids, g1.ids)
np.testing.assert_array_equal(g2t.dist, g1.dist)
np.testing.assert_array_equal(d1t.telemetry.i32, d2t.telemetry.i32)
np.testing.assert_allclose(d1t.telemetry.f32, d2t.telemetry.f32, rtol=1e-5)
np.testing.assert_array_equal(obs_tel.column(d2t.telemetry, "overflow"),
                              d2t.overflow)
np.testing.assert_array_equal(obs_tel.column(d2t.telemetry, "guided_moves"),
                              d2t.guided_moves)
assert np.all(np.isfinite(obs_tel.column(d2t.telemetry, "graph_mean_dist")))

# NN-Descent source through the same sharded core
cfgd = GraphBuildConfig(kappa=8, source="descent", tau=3, chunk=256)
gd1, _ = jax.device_get(build_graph(X, key, cfgd))
gd2, _ = jax.device_get(GraphBuilder(cfgd, mesh=mesh).build(X, key))
np.testing.assert_array_equal(gd1.ids, gd2.ids)
np.testing.assert_array_equal(gd1.dist, gd2.dist)
print("GRAPH_BUILD_OK")
"""


@pytest.mark.slow
def test_sharded_graph_build_parity_and_single_sync_4dev():
    """Acceptance: sharded build == single-device build bit-exactly on a
    4-virtual-device mesh, O(1) host syncs per build, both sources."""
    r = _run(CODE_GRAPH_BUILD, devices=4)
    assert "GRAPH_BUILD_OK" in r.stdout, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# sharded IVF serving: probe -> local scan -> all-gather -> merge in ONE
# shard_map trace — bit-exact ids AND distances vs the single-device search,
# exactly one host sync per query batch (transfer-guard-enforced), ragged
# k % R and skewed list sizes, edge cases through the same path.
# ---------------------------------------------------------------------------

CODE_IVF = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import index as ivf
from repro.core.distributed import ShardedIvf
from repro.data import gmm_blobs
from repro.kernels import ref
from repro.obs import sync_counter
from repro.obs import telemetry as obs_tel

class FakeResult:
    def __init__(self, assign, centroids, k):
        self.assign, self.centroids, self.k = assign, centroids, k

key = jax.random.PRNGKey(0)
R = len(jax.devices())
assert R == 4
n, d, k, bl = 1000, 16, 37, 16          # k % R != 0, ragged skewed lists
X = gmm_blobs(key, n, d, 24)
C = gmm_blobs(jax.random.fold_in(key, 1), k, d, 24)
a, _ = ref.assign_centroids(X, C)
index = ivf.build_ivf(X, FakeResult(a, C, k), block_rows=bl)
from repro.launch.mesh import data_mesh
mesh = data_mesh(R)
sivf = ShardedIvf(mesh, index)
nq = 32
Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (nq, d))

for topk, nprobe in ((10, 6), (64, 2), (5, 999)):   # incl. topk>candidates
    i1, d1 = jax.device_get(ivf.search(index, Q, topk=topk,
                                       nprobe=min(nprobe, k)))
    jax.block_until_ready(sivf.search(Q, topk=topk, nprobe=nprobe))  # warm
    # exactly ONE host sync per query batch: the dispatch itself transfers
    # nothing device->host; the single counted sc.get below is the sync
    with sync_counter() as sc:
        out = sivf.search(Q, topk=topk, nprobe=nprobe)
        i2, d2 = sc.get(out)
    assert sc.syncs == 1, sc.syncs
    np.testing.assert_array_equal(i1, i2, err_msg=f"{topk}/{nprobe}")
    np.testing.assert_array_equal(d1, d2, err_msg=f"{topk}/{nprobe}")

# telemetry ON: scanned-rows counters ride the same single sync, results
# bit-identical
i1, d1 = jax.device_get(ivf.search(index, Q, topk=10, nprobe=6))
jax.block_until_ready(sivf.search(Q, topk=10, nprobe=6, telemetry=True))
with sync_counter() as sct:
    out = sivf.search(Q, topk=10, nprobe=6, telemetry=True)
    i2t, d2t, tel = sct.get(out)
assert sct.syncs == 1, sct.syncs
np.testing.assert_array_equal(i1, i2t)
np.testing.assert_array_equal(d1, d2t)
scanned = int(obs_tel.column(tel, "scanned_rows")[0])
worst = int(obs_tel.column(tel, "scanned_rows_max_shard")[0])
frac = float(obs_tel.column(tel, "scan_frac")[0])
assert 0 < worst <= scanned <= Q.shape[0] * index.capacity_rows
assert 0.0 < frac <= 1.0

# q=1 through the sharded path
i1, d1 = jax.device_get(ivf.search(index, Q[:1], topk=5, nprobe=4))
i2, d2 = jax.device_get(sivf.search(Q[:1], topk=5, nprobe=4))
np.testing.assert_array_equal(i1, i2)
np.testing.assert_array_equal(d1, d2)

# slab padding rows (-1 ids) never surface even at exhaustive probe width
i3, d3 = jax.device_get(sivf.search(Q, topk=20, nprobe=k))
assert np.all(i3[np.isfinite(d3)] >= 0)

# mutation then re-shard: results track the mutated index
idx2 = ivf.remove(index, np.arange(0, 100))
s2 = ShardedIvf(mesh, idx2)
i4, _ = jax.device_get(s2.search(Q, topk=5, nprobe=6))
assert np.all(i4[i4 >= 0] >= 100)
print("SHARDED_IVF_OK")
"""


@pytest.mark.slow
def test_sharded_ivf_search_parity_and_single_sync_4dev():
    """Acceptance: sharded IVF search == single-device search bit-exactly
    (ids and distances) on a 4-virtual-device mesh, one host sync per query
    batch, edge cases (topk > candidates, nprobe > k, q=1) included."""
    r = _run(CODE_IVF, devices=4)
    assert "SHARDED_IVF_OK" in r.stdout, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# grouped + sharded IVF (the PR 5 caveat): the qgroup grouped-scan layout
# composed with ShardedIvf — each shard groups against its LOCAL tile map and
# scatters raw partial results back to the original query order BEFORE the
# all-gather, so ids must still be bit-exact vs the single-device PER-QUERY
# search (distances to grouped-dot tolerance: the grouped scan batches its
# dot_generals differently, ~5e-4 relative).
# ---------------------------------------------------------------------------

CODE_IVF_GROUPED = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import index as ivf
from repro.core.distributed import ShardedIvf
from repro.data import gmm_blobs
from repro.kernels import ref
from repro.obs import sync_counter
from repro.obs import telemetry as obs_tel

class FakeResult:
    def __init__(self, assign, centroids, k):
        self.assign, self.centroids, self.k = assign, centroids, k

key = jax.random.PRNGKey(0)
R = len(jax.devices())
assert R == 4
n, d, k, bl = 1000, 16, 37, 16          # k % R != 0, ragged skewed lists
X = gmm_blobs(key, n, d, 24)
C = gmm_blobs(jax.random.fold_in(key, 1), k, d, 24)
a, _ = ref.assign_centroids(X, C)
index = ivf.build_ivf(X, FakeResult(a, C, k), block_rows=bl)
from repro.launch.mesh import data_mesh
mesh = data_mesh(R)
sivf = ShardedIvf(mesh, index)
nq = 32
Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (nq, d))

for topk, nprobe, G in ((10, 6, 8), (5, 4, 4)):
    i1, d1 = jax.device_get(ivf.search(index, Q, topk=topk, nprobe=nprobe))
    jax.block_until_ready(sivf.search(Q, topk=topk, nprobe=nprobe,
                                      qgroup=G))                      # warm
    with sync_counter() as sc:
        out = sivf.search(Q, topk=topk, nprobe=nprobe, qgroup=G)
        i2, d2 = sc.get(out)                         # the ONE sync
    assert sc.syncs == 1, sc.syncs
    np.testing.assert_array_equal(i1, i2, err_msg=f"{topk}/{nprobe}/G={G}")
    np.testing.assert_allclose(d1, d2, rtol=1e-3, atol=1e-4,
                               err_msg=f"{topk}/{nprobe}/G={G}")

# grouped single-device vs grouped sharded agree too
ig, dg = jax.device_get(ivf.search(index, Q, topk=10, nprobe=6, qgroup=8))
i2, d2 = jax.device_get(sivf.search(Q, topk=10, nprobe=6, qgroup=8))
np.testing.assert_array_equal(ig, i2)

# ragged group: q=3 < qgroup=8, composed with telemetry
i1, d1 = jax.device_get(ivf.search(index, Q[:3], topk=5, nprobe=4))
i2, d2, tel = jax.device_get(sivf.search(Q[:3], topk=5, nprobe=4, qgroup=8,
                                         telemetry=True))
np.testing.assert_array_equal(i1, i2)
np.testing.assert_allclose(d1, d2, rtol=1e-3, atol=1e-4)
assert int(obs_tel.column(tel, "scanned_rows")[0]) > 0
print("SHARDED_IVF_GROUPED_OK")
"""


@pytest.mark.slow
def test_sharded_ivf_grouped_scan_parity_4dev():
    """Satellite: qgroup grouped scans composed with ShardedIvf — ids pinned
    bit-exact against single-device per-query `ivf.search`, one host sync,
    ragged q < qgroup and telemetry composition included."""
    r = _run(CODE_IVF_GROUPED, devices=4)
    assert "SHARDED_IVF_GROUPED_OK" in r.stdout, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# codec'd sharded IVF: the compressed-list ADC scan composed with ShardedIvf —
# replicated in-trace LUT, sharded u8 slabs, per-shard exact-rerank tail, and
# the same one-all-gather / one-host-sync schedule as the f32 path.
# ---------------------------------------------------------------------------

CODE_IVF_CODEC = r"""
import jax, jax.numpy as jnp, numpy as np
from repro import index as ivf
from repro.core.distributed import ShardedIvf
from repro.data import gmm_blobs
from repro.kernels import ref
from repro.obs import sync_counter
from repro.obs import telemetry as obs_tel

class FakeResult:
    def __init__(self, assign, centroids, k):
        self.assign, self.centroids, self.k = assign, centroids, k

key = jax.random.PRNGKey(0)
R = len(jax.devices())
assert R == 4
n, d, k, bl = 1000, 16, 37, 16          # k % R != 0, ragged skewed lists
X = gmm_blobs(key, n, d, 24)
C = gmm_blobs(jax.random.fold_in(key, 1), k, d, 24)
a, _ = ref.assign_centroids(X, C)
base = ivf.build_ivf(X, FakeResult(a, C, k), block_rows=bl)
from repro.launch.mesh import data_mesh
mesh = data_mesh(R)
nq = 32
Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (nq, d))

for kind in ("int8", "pq"):
    index = ivf.quantize_index(base, kind, nsub=8,
                               key=jax.random.fold_in(key, 5))
    sivf = ShardedIvf(mesh, index)
    bpr = ivf.bytes_per_row(index.codec, d)

    # rerank=0 (pure ADC): bit-exact vs the single-device codec search,
    # exactly one host sync for the whole query batch
    i1, d1 = jax.device_get(ivf.search(index, Q, topk=10, nprobe=6,
                                       codec=kind, rerank=0))
    jax.block_until_ready(sivf.search(Q, topk=10, nprobe=6, codec=kind,
                                      rerank=0))                      # warm
    with sync_counter() as sc:
        out = sivf.search(Q, topk=10, nprobe=6, codec=kind, rerank=0)
        i2, d2 = sc.get(out)
    assert sc.syncs == 1, (kind, sc.syncs)
    np.testing.assert_array_equal(i1, i2, err_msg=kind)
    np.testing.assert_array_equal(d1, d2, err_msg=kind)

    # rerank tail on: each shard reranks its own top-depth survivors, a
    # SUPERSET of the global top-depth, so per-slot exact d2 can only be
    # <= the single-device result (and stays exact squared L2)
    si, sd = jax.device_get(ivf.search(index, Q, topk=10, nprobe=6,
                                       codec=kind))
    with sync_counter() as sr:
        out = sivf.search(Q, topk=10, nprobe=6, codec=kind)
        ri, rd = sr.get(out)
    assert sr.syncs == 1, (kind, sr.syncs)
    fin = np.isfinite(sd)
    assert np.all(rd[fin] <= sd[fin] + 1e-5), kind
    assert np.all(ri[np.isfinite(rd)] >= 0), kind

    # telemetry rides the same sync; scanned_bytes is exactly rows * B/row
    with sync_counter() as st:
        out = sivf.search(Q, topk=10, nprobe=6, codec=kind, telemetry=True)
        ti, td, tel = st.get(out)
    assert st.syncs == 1, (kind, st.syncs)
    np.testing.assert_array_equal(ti, ri, err_msg=kind)
    rows = int(obs_tel.column(tel, "scanned_rows")[0])
    nbytes = int(obs_tel.column(tel, "scanned_bytes")[0])
    assert rows > 0 and nbytes == rows * bpr, (kind, rows, nbytes, bpr)

# the f32 path reports 4d bytes/row through the same slot
sivf32 = ShardedIvf(mesh, base)
_, _, tel32 = jax.device_get(sivf32.search(Q, topk=10, nprobe=6,
                                           telemetry=True))
rows32 = int(obs_tel.column(tel32, "scanned_rows")[0])
assert int(obs_tel.column(tel32, "scanned_bytes")[0]) == rows32 * 4 * d
print("SHARDED_IVF_CODEC_OK")
"""


@pytest.mark.slow
def test_sharded_ivf_codec_parity_and_single_sync_4dev():
    """Tentpole acceptance: codec'd ShardedIvf search keeps the single-sync
    schedule — rerank=0 bit-exact vs single-device, rerank tail never worse
    per slot, scanned_bytes telemetry exact for int8/pq/f32 byte rates."""
    r = _run(CODE_IVF_CODEC, devices=4)
    assert "SHARDED_IVF_CODEC_OK" in r.stdout, r.stderr[-3000:]


@pytest.mark.slow
def test_cluster_large_example_indivisible_n_4dev():
    """examples/cluster_large.py multi-device path: n % n_dev != 0 clusters
    ALL rows in-engine through ShardedEngine.run's padded-row validity mask
    (one host sync) — no truncation warning, no post-hoc nearest-centroid
    remainder pass — and the final distortion matches the single-device run
    (same data/init/epochs; only the visit order differs)."""
    root = os.path.join(os.path.dirname(__file__), "..")
    cmd = [sys.executable, os.path.join(root, "examples", "cluster_large.py"),
           "--n", "2050", "--k", "64", "--d", "16", "--iters", "3"]

    def run(devices):
        env = dict(os.environ, PYTHONPATH=SRC,
                   XLA_FLAGS=f"--xla_force_host_platform_device_count"
                             f"={devices}")
        r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                           timeout=900)
        assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
        return r.stdout

    out4 = run(4)
    assert "[warn]" not in out4 and "[remainder]" not in out4
    assert "all 2050 rows assigned in-engine" in out4
    assert "(4 devices, one host sync)" in out4
    out1 = run(1)
    assert "all 2050 rows assigned in-engine" in out1

    def final(out):
        line = [ln for ln in out.splitlines() if ln.startswith("[done]")][0]
        return float(line.split("->")[1].split()[0])

    d4, d1 = final(out4), final(out1)
    assert abs(d4 - d1) / d1 < 0.05, (d4, d1)


# ---------------------------------------------------------------------------
# distributed 2M tree: the mesh bisection (histogram medians, O(k) replicated
# state) is bit-exact vs its single-device shards=R emulation, produces
# exactly equal-size clusters, and matches the replicated global-sort tree's
# partition quality.
# ---------------------------------------------------------------------------

CODE_TREE_PARITY = r"""
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.data import gmm_blobs
from repro.core.two_means import two_means_dist, two_means_scan

key = jax.random.PRNGKey(3)
n, d, k, R = 2048, 16, 16, 4
assert len(jax.devices()) == R
X = gmm_blobs(key, n, d, 32)
row_ids = jnp.arange(n, dtype=jnp.int32)
from repro.launch.mesh import data_mesh
mesh = data_mesh(R)
kt = jax.random.fold_in(key, 7)

def body(Xl, rl):
    return two_means_dist(Xl, rl, k, kt, shards=R, data_axes=("data",))

mesh_fn = jax.jit(shard_map(body, mesh=mesh,
                            in_specs=(P("data"), P("data")),
                            out_specs=P("data"), check_vma=False))
a_mesh = np.asarray(mesh_fn(X, row_ids))
a_emu = np.asarray(two_means_dist(X, row_ids, k, kt, shards=R))
np.testing.assert_array_equal(a_mesh, a_emu)   # bit-exact across topologies
np.testing.assert_array_equal(np.bincount(a_mesh, minlength=k),
                              np.full(k, n // k))    # exactly equal sizes

def cost(a):
    Xn = np.asarray(X, np.float32)
    C = np.stack([Xn[a == c].mean(0) for c in range(k)])
    return float(np.mean(np.sum((Xn - C[a]) ** 2, axis=1)))

# partition quality in the replicated global-sort tree's ballpark (the
# algorithms differ — exact equality is impossible; 1.5x covers seed noise)
c_new = cost(a_mesh)
c_old = cost(np.asarray(two_means_scan(X, k, kt)))
assert c_new < 1.5 * c_old, (c_new, c_old)

# shards=1 plain path: still equal-size, still a valid partition
a1 = np.asarray(two_means_dist(X, row_ids, k, kt))
np.testing.assert_array_equal(np.bincount(a1, minlength=k),
                              np.full(k, n // k))
print("TREE_PARITY_OK")
"""


@pytest.mark.slow
def test_distributed_tree_parity_4dev():
    """Acceptance: two_means_dist on the mesh == its shards=R emulation
    bit-exactly; exactly equal cluster sizes; quality matches the replicated
    global-sort tree it displaced."""
    r = _run(CODE_TREE_PARITY, devices=4)
    assert "TREE_PARITY_OK" in r.stdout, r.stderr[-3000:]


# ---------------------------------------------------------------------------
# sharded-centroid assignment with padded rows: ShardedEngine on n % R != 0
# is bit-exact vs the single-device emulation (zero-padded rows + validity
# mask) for every candidate kind — the probe/dense candidate exchange and
# the in-engine mask replace the old truncate-and-assign-remainder protocol.
# ---------------------------------------------------------------------------

CODE_ENGINE_PAD_PARITY = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.data import gmm_blobs
from repro.core import two_means_tree, init_state, engine
from repro.core.distributed import ShardedEngine

key = jax.random.PRNGKey(0)
n, d, k, R = 2050, 16, 32, 4            # n % R == 2
assert len(jax.devices()) == R
X = gmm_blobs(key, n, d, 32)
n2 = -(-n // k) * k
a0 = two_means_tree(jnp.concatenate([X, X[: n2 - n]]), k, key)[:n]
st0 = init_state(X, a0, k)
g = jax.random.randint(key, (n, 8), 0, n, dtype=jnp.int32)
from repro.launch.mesh import data_mesh
mesh = data_mesh(R)
iters = 3
n_pad = -(-n // R) * R
valid = jnp.arange(n_pad) < n
Xp = jnp.concatenate([X, jnp.zeros((n_pad - n, d), X.dtype)])
gp = jnp.concatenate([g, jnp.zeros((n_pad - n, 8), jnp.int32)])
ap = jnp.concatenate([a0, jnp.zeros((n_pad - n,), jnp.int32)])

for kind, src in (("graph", engine.graph_source(gp)),
                  ("dense", engine.dense_source()),
                  ("probe", engine.probe_source(8))):
    cfg = engine.EngineConfig(batch_size=128, iters=iters,
                              min_move_frac=-1.0, sparse_updates=True)
    eng = ShardedEngine(mesh, cfg, kind=kind, probe_p=8)
    assign, D, cnt, hist, mhist, epochs, final, _ = jax.device_get(
        eng.run(X, g, st0.assign, st0.D, st0.cnt, key))
    assert assign.shape == (n,), assign.shape
    assert int(cnt.sum()) == n, kind    # every real row assigned, no ghosts

    stp = engine.BKMState(ap, st0.D, st0.cnt, jnp.int32(0))
    st1, hist1, mhist1, epochs1, final1, _ = jax.device_get(
        engine.run(Xp, stp, src, key, cfg._replace(shards=R),
                          valid=valid))
    np.testing.assert_array_equal(assign, st1.assign[:n], err_msg=kind)
    np.testing.assert_array_equal(cnt, st1.cnt, err_msg=kind)
    np.testing.assert_array_equal(D, st1.D, err_msg=kind)
    np.testing.assert_array_equal(mhist, mhist1, err_msg=kind)
    np.testing.assert_allclose(hist, hist1, rtol=1e-5, err_msg=kind)
print("PAD_PARITY_OK")
"""


@pytest.mark.slow
def test_sharded_engine_padded_rows_parity_4dev():
    """Acceptance: n % R != 0 through ShardedEngine.run == the zero-pad +
    validity-mask emulation bit-exactly for graph/dense/probe kinds; padded
    rows contribute nothing to counts, stats, or move histories."""
    r = _run(CODE_ENGINE_PAD_PARITY, devices=4)
    assert "PAD_PARITY_OK" in r.stdout, r.stderr[-3000:]
