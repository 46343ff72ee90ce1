"""IVF index subsystem: kernel exactness (interpret vs. oracle), CSR pack
invariants under build/add/remove, persistence round-trips, and end-to-end
recall of the probe path."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import index as ivf
from repro.data import gmm_blobs
from repro.index import quantize
from repro.kernels import centroid_assign as ca
from repro.kernels import ivf_scan as iv
from repro.kernels import ivf_scan_adc as adc
from repro.kernels import ref


class FakeResult:
    """Stands in for GKMeansResult in build_ivf."""
    def __init__(self, assign, centroids, k):
        self.assign, self.centroids, self.k = assign, centroids, k


def small_index(key, n=1024, d=16, k=16, block_rows=32):
    X = gmm_blobs(key, n, d, k)
    C = gmm_blobs(jax.random.fold_in(key, 1), k, d, k)
    a, _ = ref.assign_centroids(X, C)
    return X, ivf.build_ivf(X, FakeResult(a, C, k), block_rows=block_rows)


# ---------------------------------------------------------------------------
# kernel exactness, interpret mode vs. the pure-jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,k,p,bn,bk", [(128, 32, 4, 64, 16),
                                         (256, 48, 8, 64, 16),
                                         (100, 37, 5, 64, 16)])
def test_probe_centroids_matches_ref(n, k, p, bn, bk):
    kk = jax.random.PRNGKey(n + k + p)
    X = gmm_blobs(kk, n, 16, 8)
    C = gmm_blobs(jax.random.fold_in(kk, 1), k, 16, 8)
    ip, dp = ca.probe_centroids_padded(X, C, p, bn=bn, bk=bk, interpret=True)
    ir, dr = ref.probe_centroids(X, C, p)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))
    np.testing.assert_allclose(np.asarray(dp), np.asarray(dr),
                               rtol=1e-4, atol=1e-3)


def test_probe_p1_matches_assign():
    X = gmm_blobs(jax.random.PRNGKey(0), 100, 8, 4)
    C = gmm_blobs(jax.random.PRNGKey(1), 13, 8, 4)
    ip, dp = ref.probe_centroids(X, C, 1)
    ia, da = ref.assign_centroids(X, C)
    np.testing.assert_array_equal(np.asarray(ip[:, 0]), np.asarray(ia))
    np.testing.assert_allclose(np.asarray(dp[:, 0]), np.asarray(da),
                               rtol=1e-5)


def test_assign_centroids_padded_wrapper():
    """Odd n/k no longer trip the tile assert."""
    X = gmm_blobs(jax.random.PRNGKey(3), 100, 16, 4)
    C = gmm_blobs(jax.random.PRNGKey(4), 37, 16, 4)
    ai, di = ca.assign_centroids_padded(X, C, bn=64, bk=16, interpret=True)
    ar, dr = ref.assign_centroids(X, C)
    np.testing.assert_array_equal(np.asarray(ai), np.asarray(ar))
    np.testing.assert_allclose(np.asarray(di), np.asarray(dr),
                               rtol=1e-4, atol=1e-3)


def test_ivf_scan_exact_vs_ref(key):
    """The fused scan returns bit-identical top-k ids to the oracle."""
    X, index = small_index(key)
    nq = 32
    Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 2),
                                         (nq, X.shape[1]))
    cids, _ = ref.probe_centroids(Q, index.centroids, 4)
    tm = ivf.build_tile_map(cids, index.starts, index.caps,
                            max_tiles=index.max_list_tiles,
                            block_rows=index.block_rows,
                            null_tile=index.null_tile)
    ki, kd = iv.ivf_scan(Q, index.vecs, index.ids, tm,
                         block_rows=index.block_rows, topk=10,
                         interpret=True)
    ri, rd = ref.ivf_scan(Q, index.vecs, index.ids, tm,
                          block_rows=index.block_rows, topk=10)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    fin = np.isfinite(np.asarray(rd))
    np.testing.assert_allclose(np.asarray(kd)[fin], np.asarray(rd)[fin],
                               rtol=1e-4, atol=1e-3)


def test_ivf_scan_short_candidates(key):
    """Fewer candidates than topk: tail is id=-1 / d=+inf."""
    X, index = small_index(key, n=64, k=4, block_rows=8)
    Q = X[:4]
    cids, _ = ref.probe_centroids(Q, index.centroids, 1)
    tm = ivf.build_tile_map(cids, index.starts, index.caps,
                            max_tiles=index.max_list_tiles,
                            block_rows=index.block_rows,
                            null_tile=index.null_tile)
    ids, d2 = iv.ivf_scan(Q, index.vecs, index.ids, tm,
                          block_rows=index.block_rows, topk=60,
                          interpret=True)
    ids_n, d_n = np.asarray(ids), np.asarray(d2)
    sizes = index.list_sizes()[np.asarray(cids)[:, 0]]
    for r in range(4):
        assert np.all(ids_n[r, sizes[r]:] == -1)
        assert np.all(np.isinf(d_n[r, sizes[r]:]))
        assert np.all(np.isfinite(d_n[r, : sizes[r]]))


@pytest.mark.parametrize("scan", ["f32", "int8", "pq"])
def test_ivf_scan_query_tiles_bitwise(key, scan):
    """The kernels' query tiling (``bq``, one kernel call per tile of the
    batch, ragged tail padded) is bitwise-neutral: every query is scanned
    independently."""
    X, index = small_index(key, n=512, d=16, k=8, block_rows=16)
    nq = 21                                   # 3 tiles of 8, ragged tail
    Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 13),
                                         (nq, X.shape[1]))
    if scan == "f32":
        cids, _ = ref.probe_centroids(Q, index.centroids, 3)
        tm = ivf.build_tile_map(cids, index.starts, index.caps,
                                max_tiles=index.max_list_tiles,
                                block_rows=index.block_rows,
                                null_tile=index.null_tile)
        run = lambda bq: iv.ivf_scan(Q, index.vecs, index.ids, tm,
                                     block_rows=index.block_rows, topk=10,
                                     interpret=True, bq=bq)
    else:
        index = ivf.quantize_index(index, scan, nsub=4,
                                   key=jax.random.fold_in(key, 24))
        lut, qc, tm = _adc_inputs(index, Q, 3)
        run = lambda bq: adc.ivf_scan_adc(lut, qc, index.vnorm, index.codes,
                                          index.ids, tm,
                                          block_rows=index.block_rows,
                                          topk=10, interpret=True, bq=bq)
    for whole, tiled in zip(run(0), run(8)):
        np.testing.assert_array_equal(np.asarray(tiled), np.asarray(whole))


# ---------------------------------------------------------------------------
# query-grouped scan layout: kernel bitwise-exactness and search parity
# ---------------------------------------------------------------------------

def _group_inputs(index, Q, nprobe, qgroup):
    cids, _ = ref.probe_centroids(Q, index.centroids, nprobe)
    tm = ivf.build_tile_map(cids, index.starts, index.caps,
                            max_tiles=index.max_list_tiles,
                            block_rows=index.block_rows,
                            null_tile=index.null_tile)
    order, union, qmask = ivf.build_group_map(tm, group=qgroup,
                                              null_tile=index.null_tile)
    Qg = Q[jnp.clip(order, 0, Q.shape[0] - 1)]
    return tm, order, union, qmask, Qg


@pytest.mark.parametrize("nq,G,nprobe,topk", [(32, 4, 4, 10),
                                              (33, 8, 3, 5),
                                              (7, 3, 2, 40)])
def test_ivf_scan_grouped_interpret_bitwise_vs_ref(key, nq, G, nprobe, topk):
    """Acceptance: the batched kernel is BITWISE-equal to its oracle —
    ids and distances — including ragged q % G tails."""
    X, index = small_index(key, n=512, d=16, k=8, block_rows=16)
    Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 11),
                                         (nq, X.shape[1]))
    _, order, union, qmask, Qg = _group_inputs(index, Q, nprobe, G)
    ki, kd = iv.ivf_scan_grouped(Qg, index.vecs, index.ids, union, qmask,
                                 block_rows=index.block_rows, topk=topk,
                                 interpret=True)
    ri, rd = ref.ivf_scan_grouped(Qg, index.vecs, index.ids, union, qmask,
                                  block_rows=index.block_rows, topk=topk)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(rd))


def test_group_map_partitions_probed_tiles(key):
    """Union+mask reproduce each query's probed tile set exactly; padding
    rows are fully masked off."""
    X, index = small_index(key, n=512, d=16, k=8, block_rows=16)
    nq, G = 13, 4
    Q = X[:nq]
    tm, order, union, qmask = _group_inputs(index, Q, 3, G)[:4]
    tm, order = np.asarray(tm), np.asarray(order)
    union, qmask = np.asarray(union), np.asarray(qmask)
    null = index.null_tile
    for row, qi in enumerate(order):
        g = row // G
        got = sorted(union[g][qmask[row] > 0])
        if qi >= nq:                       # ragged-tail padding row
            assert got == []
            continue
        assert got == sorted(set(tm[qi]) - {null})
    # real tiles are deduped and ascending, null padding trails
    for g in range(union.shape[0]):
        real = union[g][union[g] != null]
        assert np.all(np.diff(real) > 0)
        tail = union[g][len(real):]
        assert np.all(tail == null)


def test_grouped_search_matches_per_query(key):
    """qgroup search returns identical neighbour ids (distances to float
    rounding) for every grouping width, including G > q."""
    X, index = small_index(key, n=1024, d=16, k=16, block_rows=32)
    nq = 33
    Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 12),
                                         (nq, X.shape[1]))
    i0, d0 = ivf.search(index, Q, topk=10, nprobe=4, force="ref")
    for G in (2, 4, 8, 64):
        i1, d1 = ivf.search(index, Q, topk=10, nprobe=4, force="ref",
                            qgroup=G)
        np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1),
                                      err_msg=f"G={G}")
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d0),
                                   rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# compressed-list ADC scan: kernel exactness and codec search semantics
# ---------------------------------------------------------------------------

def _adc_inputs(index, Q, nprobe):
    cids, _ = ref.probe_centroids(Q, index.centroids, nprobe)
    tm = ivf.build_tile_map(cids, index.starts, index.caps,
                            max_tiles=index.max_list_tiles,
                            block_rows=index.block_rows,
                            null_tile=index.null_tile)
    lut, qc = quantize.build_lut(index.codec, Q)
    return lut, qc, tm


@pytest.mark.parametrize("kind,nq,nprobe,topk", [
    ("int8", 32, 4, 10),
    ("pq", 32, 4, 10),
    ("int8", 1, 2, 5),                  # q=1: ref's pad-to-2 recursion
    ("pq", 7, 3, 40),                   # topk > list sizes: -1/+inf tails
])
def test_ivf_scan_adc_interpret_bitwise_vs_ref(key, kind, nq, nprobe, topk):
    """Acceptance: the fused ADC kernel is BITWISE-equal to its oracle —
    ids, packed-row positions, and (pq) raw partials — for both codecs, with
    tombstoned rows (holes) in the scanned lists.  The int8 partials agree to
    a few ulps: the kernel reads its norms from the lane-dense (1, n) slab
    TPU blocks need, and XLA:CPU then rounds the interpreted dot-plus-norm
    differently from the oracle."""
    X, index = small_index(key, n=512, d=16, k=8, block_rows=16)
    index = ivf.remove(index, np.arange(0, 40))      # punch holes in lists
    index = ivf.quantize_index(index, kind, nsub=4,
                               key=jax.random.fold_in(key, 21))
    Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 22),
                                         (nq, X.shape[1]))
    lut, qc, tm = _adc_inputs(index, Q, nprobe)
    ki, kp, kd = adc.ivf_scan_adc(lut, qc, index.vnorm, index.codes,
                                  index.ids, tm,
                                  block_rows=index.block_rows, topk=topk,
                                  interpret=True)
    ri, rp, rd = ref.ivf_scan_adc(lut, qc, index.vnorm, index.codes,
                                  index.ids, tm,
                                  block_rows=index.block_rows, topk=topk)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))
    if kind == "pq":
        np.testing.assert_array_equal(np.asarray(kd), np.asarray(rd))
    else:
        np.testing.assert_allclose(np.asarray(kd), np.asarray(rd),
                                   rtol=2e-6, atol=0)
    # tombstoned ids never surface; empty slots are -1 pos / +inf part
    ri_n, rp_n, rd_n = np.asarray(ri), np.asarray(rp), np.asarray(rd)
    assert np.all(ri_n[rp_n >= 0] >= 40)
    assert np.all(ri_n[rp_n < 0] == -1) and np.all(np.isinf(rd_n[rp_n < 0]))


def test_ivf_scan_adc_ref_tile_invariance(key):
    """The oracle's autotunable query-axis chunking is bitwise-neutral."""
    X, index = small_index(key, n=512, d=16, k=8, block_rows=16)
    index = ivf.quantize_index(index, "pq", nsub=4,
                               key=jax.random.fold_in(key, 23))
    Q = X[:13]
    lut, qc, tm = _adc_inputs(index, Q, 3)
    base = ref.ivf_scan_adc(lut, qc, index.vnorm, index.codes, index.ids,
                            tm, block_rows=index.block_rows, topk=10)
    for t in (2, 3, 64):
        out = ref.ivf_scan_adc(lut, qc, index.vnorm, index.codes,
                               index.ids, tm, block_rows=index.block_rows,
                               topk=10, tile=t)
        for a, b in zip(base, out):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"tile={t}")


@pytest.mark.parametrize("kind", ["int8", "pq"])
def test_codec_search_rerank_is_exact(key, kind):
    """With the rerank tail on, codec search returns exact squared L2 —
    identical d2 to the f32 path wherever the same neighbour survives —
    and recall can only improve over the codec-only (rerank=0) path."""
    X, index = small_index(key, n=1024, d=16, k=16, block_rows=32)
    index = ivf.quantize_index(index, kind, nsub=8,
                               key=jax.random.fold_in(key, 31))
    nq = 32
    Q = X[:nq] + 0.05 * jax.random.normal(jax.random.fold_in(key, 32),
                                          (nq, X.shape[1]))
    fi, fd = ivf.search(index, Q, topk=10, nprobe=8, force="ref")
    ci, cd = ivf.search(index, Q, topk=10, nprobe=8, force="ref",
                        codec=kind)
    zi, zd = ivf.search(index, Q, topk=10, nprobe=8, force="ref",
                        codec=kind, rerank=0)
    fi_n, fd_n = np.asarray(fi), np.asarray(fd)
    ci_n, cd_n = np.asarray(ci), np.asarray(cd)
    for r in range(nq):
        real = fi_n[r][fi_n[r] >= 0]
        common, fa, ca_ = np.intersect1d(fi_n[r][fi_n[r] >= 0],
                                         ci_n[r][ci_n[r] >= 0],
                                         return_indices=True)
        assert len(common) > 0
        np.testing.assert_array_equal(fd_n[r][fi_n[r] >= 0][fa],
                                      cd_n[r][ci_n[r] >= 0][ca_])
        assert len(real) == len(set(real.tolist()))
    # rerank re-scores a SUPERSET of the codec-only shortlist exactly, so
    # any f32-top-10 hit the codec-only path finds, rerank keeps
    hits = lambda a: float(np.mean((np.asarray(a)[:, :, None]
                                    == fi_n[:, None, :]).any(-1)))
    assert hits(ci) >= hits(zi)
    # rerank=0 distances are to the reconstructions: finite and nonnegative
    zd_n = np.asarray(zd)
    assert np.all(zd_n[np.asarray(zi) >= 0] >= 0.0)
    assert np.all(np.isfinite(zd_n[np.asarray(zi) >= 0]))


def test_group_map_matches_pairwise_reference(key):
    """Regression (satellite): the searchsorted membership build equals the
    old O(G*U*T) pairwise-compare build bit-for-bit — ragged tails and
    duplicate probed tiles included."""
    X, index = small_index(key, n=512, d=16, k=8, block_rows=16)
    null = index.null_tile
    for nq, G, nprobe in ((13, 4, 3), (32, 8, 4), (5, 3, 2), (16, 16, 5)):
        Q = X[:nq]
        cids, _ = ref.probe_centroids(Q, index.centroids, nprobe)
        tm = ivf.build_tile_map(cids, index.starts, index.caps,
                                max_tiles=index.max_list_tiles,
                                block_rows=index.block_rows,
                                null_tile=null)
        order, union, qmask = ivf.build_group_map(tm, group=G,
                                                  null_tile=null)
        order_n, u = np.asarray(order), np.asarray(union)
        tq = np.asarray(tm)[np.clip(order_n, 0, nq - 1)].copy()
        tq[order_n >= nq] = null                          # padding rows
        ngroups = len(order_n) // G
        tqg = tq.reshape(ngroups, G, -1)
        # old membership: member m owns union slot u iff union[g, u] is one
        # of m's real probed tiles (pairwise compare over every slot)
        hit = (tqg[:, :, None, :] == u[:, None, :, None]).any(-1)
        hit &= (u != null)[:, None, :]
        np.testing.assert_array_equal(
            np.asarray(qmask).reshape(ngroups, G, -1),
            hit.astype(np.int32), err_msg=f"nq={nq} G={G} p={nprobe}")


# ---------------------------------------------------------------------------
# query-path edge cases
# ---------------------------------------------------------------------------

def _empty_cell_index(key, n=256, d=8, k=8, block_rows=8):
    """An index where cell 0 has no members (and so zero capacity)."""
    X = gmm_blobs(key, n, d, 4)
    C = gmm_blobs(jax.random.fold_in(key, 1), k, d, 4)
    a, _ = ref.assign_centroids(X, C)
    a = np.asarray(a).copy()
    a[a == 0] = 1                       # evacuate cell 0
    index = ivf.build_ivf(X, FakeResult(jnp.asarray(a), C, k),
                          block_rows=block_rows)
    assert index.list_sizes()[0] == 0 and int(np.asarray(index.caps)[0]) == 0
    return X, index


def test_probe_empty_cell(key):
    """Probing an empty cell contributes nothing — no -1/padding ids leak."""
    X, index = _empty_cell_index(key)
    C0 = np.asarray(index.centroids)[0]
    Q = jnp.asarray(C0[None] + 0.01 * np.ones_like(C0))   # lands on cell 0
    cids, _ = ref.probe_centroids(Q, index.centroids, 2)
    assert 0 in np.asarray(cids)                          # it IS probed
    ids, d2 = ivf.search(index, Q, topk=5, nprobe=2, force="ref")
    ids = np.asarray(ids)
    assert np.all(ids[np.isfinite(np.asarray(d2))] >= 0)
    # grouped layout hits the same edge
    gi, _ = ivf.search(index, Q, topk=5, nprobe=2, force="ref", qgroup=2)
    np.testing.assert_array_equal(ids, np.asarray(gi))


def test_search_single_query(key):
    """q=1 works in both layouts and matches exhaustive on its candidates."""
    X, index = small_index(key, n=256, d=8, k=4, block_rows=8)
    Q = X[:1]
    i0, d0 = ivf.search(index, Q, topk=3, nprobe=4, force="ref")
    assert i0.shape == (1, 3) and int(i0[0, 0]) == 0
    i1, _ = ivf.search(index, Q, topk=3, nprobe=4, force="ref", qgroup=4)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_topk_exceeds_scanned_candidates(key):
    """topk larger than every scanned candidate: tail is -1/+inf and real
    prefix ranks ascending."""
    X, index = small_index(key, n=64, d=8, k=4, block_rows=8)
    Q = X[:3]
    ids, d2 = ivf.search(index, Q, topk=60, nprobe=1, force="ref")
    ids, d2 = np.asarray(ids), np.asarray(d2)
    cids, _ = ref.probe_centroids(Q, index.centroids, 1)
    sizes = index.list_sizes()[np.asarray(cids)[:, 0]]
    for r in range(3):
        assert np.all(ids[r, sizes[r]:] == -1)
        assert np.all(np.isinf(d2[r, sizes[r]:]))
        assert np.all(np.diff(d2[r, : sizes[r]]) >= 0)
    gids, gd2 = ivf.search(index, Q, topk=60, nprobe=1, force="ref",
                           qgroup=2)
    np.testing.assert_array_equal(ids, np.asarray(gids))


def test_nprobe_clamps_to_k(key):
    """nprobe > k no longer trips an assert: it clamps to exhaustive."""
    X, index = small_index(key, n=256, d=8, k=4, block_rows=8)
    Q = X[:8]
    i_over, d_over = ivf.search(index, Q, topk=5, nprobe=999, force="ref")
    i_full, d_full = ivf.search(index, Q, topk=5, nprobe=4, force="ref")
    np.testing.assert_array_equal(np.asarray(i_over), np.asarray(i_full))
    np.testing.assert_array_equal(np.asarray(d_over), np.asarray(d_full))
    assert ivf.scan_fraction(index, Q, nprobe=999, force="ref") <= 1.0


def test_exhaustive_search_matches_brute_force(key):
    """Regression (satellite): exhaustive_search equals brute force — ids
    and distances — instead of trusting the nprobe=k probe round-trip."""
    X, index = small_index(key, n=512, d=16, k=8, block_rows=16)
    nq = 32
    Q = X[:nq] + 0.1 * jax.random.normal(jax.random.fold_in(key, 3),
                                         (nq, X.shape[1]))
    ids, d2 = ivf.exhaustive_search(index, Q, topk=10, force="ref")
    sc = (jnp.sum(X * X, -1)[None] - 2.0 * (Q @ X.T))      # partial form
    gt = jnp.argsort(sc, axis=1)[:, :10]
    gd = jnp.maximum(jnp.take_along_axis(sc, gt, 1)
                     + jnp.sum(Q * Q, -1)[:, None], 0.0)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(gt))
    np.testing.assert_allclose(np.asarray(d2), np.asarray(gd),
                               rtol=1e-4, atol=1e-3)
    # the old routing survives as a cross-check: probing every cell agrees
    i2, _ = ivf.search(index, Q, topk=10, nprobe=index.k, force="ref")
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(i2))


def test_exhaustive_search_all_lists_empty(key):
    """Zero-capacity index (every cell empty) returns -1/+inf, not a crash."""
    X = gmm_blobs(key, 16, 8, 2)
    C = gmm_blobs(jax.random.fold_in(key, 1), 4, 8, 2)
    empty = ivf.build_ivf(X[:0], FakeResult(jnp.zeros((0,), jnp.int32), C, 4),
                          block_rows=8)
    ids, d2 = ivf.exhaustive_search(empty, X[:3], topk=4, force="ref")
    assert np.all(np.asarray(ids) == -1) and np.all(np.isinf(np.asarray(d2)))


def test_search_all_lists_empty(key):
    """search (every layout) on a zero-capacity index: -1/+inf, no crash and
    no unwritten 0-tile kernel buffers."""
    X = gmm_blobs(key, 16, 8, 2)
    C = gmm_blobs(jax.random.fold_in(key, 1), 4, 8, 2)
    empty = ivf.build_ivf(X[:0], FakeResult(jnp.zeros((0,), jnp.int32), C, 4),
                          block_rows=8)
    for kw in ({}, {"qgroup": 2}):
        ids, d2 = ivf.search(empty, X[:3], topk=4, nprobe=2, force="ref",
                             **kw)
        assert np.all(np.asarray(ids) == -1), kw
        assert np.all(np.isinf(np.asarray(d2))), kw


# ---------------------------------------------------------------------------
# pack / add / remove invariants
# ---------------------------------------------------------------------------

def _check_invariants(index, X=None, expect_ids=None):
    ids = np.asarray(index.ids)
    starts = np.asarray(index.starts)
    caps = np.asarray(index.caps)
    bl = index.block_rows
    # tile alignment and disjoint coverage of the packed buffer
    assert np.all(starts % bl == 0) and np.all(caps % bl == 0)
    assert np.all(np.diff(starts) == caps[:-1])
    assert starts[-1] + caps[-1] == index.capacity_rows
    # the null tile is all holes
    assert np.all(ids[index.capacity_rows:] == -1)
    # every live id appears exactly once
    live = ids[ids >= 0]
    assert len(live) == len(set(live.tolist()))
    if expect_ids is not None:
        assert set(live.tolist()) == set(expect_ids)
    # every live row's vector is nearest-centroid-consistent with its list
    if X is not None:
        C = np.asarray(index.centroids)
        vecs = np.asarray(index.vecs)
        for c in range(index.k):
            seg = slice(starts[c], starts[c] + caps[c])
            for r, vid in zip(vecs[seg][ids[seg] >= 0],
                              ids[seg][ids[seg] >= 0]):
                np.testing.assert_allclose(r, np.asarray(X)[vid], rtol=1e-6)


def test_build_invariants(key):
    X, index = small_index(key)
    _check_invariants(index, X, expect_ids=range(X.shape[0]))
    assert index.size == X.shape[0]


def test_add_fills_holes_then_repacks(key):
    X, index = small_index(key, n=512, k=8, block_rows=32)
    rows0 = index.n_rows
    Xn = gmm_blobs(jax.random.fold_in(key, 7), 300, X.shape[1], 8)
    out = ivf.add(index, Xn)
    _check_invariants(out, expect_ids=range(512 + 300))
    assert out.size == 812
    # new vectors are searchable at full probe width
    ids, d2 = ivf.exhaustive_search(out, Xn[:8], topk=1, force="ref")
    assert np.all(np.asarray(ids)[:, 0] >= 512)
    assert float(jnp.max(d2[:, 0])) < 1e-3
    assert out.n_rows >= rows0  # grew (holes alone can't hold 300 adds)


def test_remove_and_repack(key):
    X, index = small_index(key, n=512, k=8, block_rows=32)
    out = ivf.remove(index, np.arange(0, 100))
    _check_invariants(out, expect_ids=range(100, 512))
    assert out.size == 412
    # removed ids are no longer returned even at full probe width
    ids, _ = ivf.exhaustive_search(out, X[:16], topk=5, force="ref")
    assert np.all(np.asarray(ids) >= 100)
    # heavy removal compacts the buffer
    heavy = ivf.remove(index, np.arange(0, 400))
    _check_invariants(heavy, expect_ids=range(400, 512))
    assert heavy.capacity_rows < index.capacity_rows


def test_add_remove_roundtrip_searches_equal(key):
    X, index = small_index(key, n=256, k=4, block_rows=16)
    Xn = gmm_blobs(jax.random.fold_in(key, 3), 32, X.shape[1], 4)
    out = ivf.remove(ivf.add(index, Xn),
                     np.arange(256, 256 + 32))
    assert out.size == 256
    q = X[:16]
    i0, d0 = ivf.search(index, q, topk=5, nprobe=4, force="ref")
    i1, d1 = ivf.search(out, q, topk=5, nprobe=4, force="ref")
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fname", ["index.ivf", "index.npz"])
def test_save_load_roundtrip(key, tmp_path, fname):
    X, index = small_index(key, n=256, k=8, block_rows=16)
    path = os.path.join(tmp_path, fname)
    ivf.save_index(index, path)
    loaded = ivf.load_index(path)
    assert loaded.block_rows == index.block_rows
    for name in ("centroids", "vecs", "ids", "starts", "caps"):
        np.testing.assert_array_equal(np.asarray(getattr(loaded, name)),
                                      np.asarray(getattr(index, name)))
    q = X[:8]
    i0, d0 = ivf.search(index, q, topk=5, nprobe=4, force="ref")
    i1, d1 = ivf.search(loaded, q, topk=5, nprobe=4, force="ref")
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


def test_load_rejects_foreign_files(key, tmp_path):
    """Both formats validate the magic — a foreign npz/binary raises a
    ValueError instead of building a garbage index."""
    p_npz = os.path.join(tmp_path, "foreign.npz")
    np.savez_compressed(p_npz, meta=json.dumps({"magic": "other"}),
                        **{n: np.zeros(2) for n in
                           ("centroids", "vecs", "ids", "starts", "caps")})
    with pytest.raises(ValueError, match="not a repro IVF index"):
        ivf.load_index(p_npz)
    # an npz without a meta entry at all raises the same ValueError
    p_raw = os.path.join(tmp_path, "raw.npz")
    np.savez_compressed(p_raw, a=np.zeros(3))
    with pytest.raises(ValueError, match="not a repro IVF index"):
        ivf.load_index(p_raw)
    p_bin = os.path.join(tmp_path, "foreign.ivf")
    with open(p_bin, "wb") as f:
        f.write(b"\x10" + b"\x00" * 64)
    with pytest.raises(ValueError, match="not a repro IVF index"):
        ivf.load_index(p_bin)


def test_load_mmap_zero_copy(key, tmp_path):
    X, index = small_index(key, n=256, k=8, block_rows=16)
    path = os.path.join(tmp_path, "index.ivf")
    ivf.save_index(index, path)
    mm = ivf.load_index(path, mmap=True)
    assert isinstance(mm.vecs, np.memmap)
    np.testing.assert_array_equal(np.asarray(mm.vecs),
                                  np.asarray(index.vecs))


# ---------------------------------------------------------------------------
# end-to-end probe quality
# ---------------------------------------------------------------------------

def test_multi_probe_recall_increases(key):
    X, index = small_index(key, n=2048, d=24, k=32, block_rows=32)
    nq = 64
    Q = X[:nq] + 0.05 * jax.random.normal(jax.random.fold_in(key, 5),
                                          (nq, X.shape[1]))
    dd = jnp.sum((Q[:, None, :] - X[None]) ** 2, -1)
    gt = jnp.argsort(dd, axis=1)[:, :10]

    recs = []
    for nprobe in (1, 4, 16):
        ids, _ = ivf.search(index, Q, topk=10, nprobe=nprobe, force="ref")
        hits = (ids[:, :, None] == gt[:, None, :]).any(-1)
        recs.append(float(jnp.mean(hits.astype(jnp.float32))))
    assert recs[0] <= recs[1] <= recs[2]
    assert recs[-1] > 0.9
    assert ivf.scan_fraction(index, Q, nprobe=1, force="ref") < \
        ivf.scan_fraction(index, Q, nprobe=16, force="ref") <= 1.0


def test_graph_search_key_threading(blobs):
    """Satellite: explicit seeding is reproducible; default preserved."""
    from repro.core import build_knn_graph, graph_search
    g = build_knn_graph(blobs, 8, xi=32, tau=2, key=jax.random.PRNGKey(0))
    q = blobs[:16]
    i_default, _ = graph_search(blobs, g.ids, q, 5, 32, 16)
    i_zero, _ = graph_search(blobs, g.ids, q, 5, 32, 16,
                             key=jax.random.PRNGKey(0))
    i_other, _ = graph_search(blobs, g.ids, q, 5, 32, 16,
                              key=jax.random.PRNGKey(123))
    np.testing.assert_array_equal(np.asarray(i_default), np.asarray(i_zero))
    # a different seed gives a different (but valid) pool trajectory
    assert i_other.shape == i_default.shape
    assert int(i_other.min()) >= 0
