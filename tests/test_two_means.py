"""2M-tree invariants: exact equal sizes, valid partition, quality."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image has no hypothesis wheel
    from _hyp import given, settings, strategies as st

from repro.core import distortion, pad_plan, two_means_tree
from repro.core.two_means import (_seed_rows_T, _seg_sum_T, _TreeTopo,
                                  two_means_dist)
from repro.data import gmm_blobs


def test_equal_sizes_and_partition(key):
    n, k = 1024, 16
    X = gmm_blobs(key, n, 8, 16)
    a = two_means_tree(X, k, key)
    sizes = jnp.bincount(a, length=k)
    assert int(sizes.min()) == int(sizes.max()) == n // k
    assert int(a.min()) >= 0 and int(a.max()) == k - 1


def test_beats_random_partition(key):
    n, k = 2048, 32
    X = gmm_blobs(key, n, 16, 32)
    a = two_means_tree(X, k, key)
    rand = jax.random.randint(key, (n,), 0, k)
    assert float(distortion(X, a, k)) < 0.6 * float(distortion(X, rand, k))


@settings(deadline=None, max_examples=50)
@given(st.integers(1, 10_000_000), st.integers(1, 1_000_000))
def test_pad_plan(n, k):
    n2, k2 = pad_plan(n, k)
    assert k2 >= k and (k2 & (k2 - 1)) == 0
    assert n2 >= n and n2 % k2 == 0
    assert n2 - n < k2  # minimal padding


def test_deterministic_given_key(key):
    X = gmm_blobs(key, 512, 8, 8)
    a1 = two_means_tree(X, 8, key)
    a2 = two_means_tree(X, 8, key)
    assert jnp.array_equal(a1, a2)


def test_non_pow2_n_divisible_by_k(key):
    """The flat level-scan only needs k | n, not n a power of two."""
    n, k = 96 * 8, 8
    X = gmm_blobs(key, n, 8, 8)
    a = two_means_tree(X, k, key)
    sizes = jnp.bincount(a, length=k)
    assert int(sizes.min()) == int(sizes.max()) == n // k


def test_two_means_scan_inside_outer_trace(key):
    """two_means_scan composes into an outer jit/scan (the graph builder's
    tau-round loop) — traced keys, one trace, same result as the wrapper."""
    from repro.core.two_means import two_means_scan
    X = gmm_blobs(key, 512, 8, 8)

    @jax.jit
    def outer(key):
        return jax.lax.scan(
            lambda c, t: (c, two_means_scan(X, 8, jax.random.fold_in(key, t))),
            0, jnp.arange(2))[1]

    a = outer(key)
    assert a.shape == (2, 512)
    want = two_means_tree(X, 8, jax.random.fold_in(key, 1))
    assert jnp.array_equal(a[1], want)


# ---------------------------------------------------------------------------
# two_means_dist: the distributed tree's sums and seed rows, at shards=1 and
# the shards=4 single-device emulation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shards", [1, 4])
def test_two_means_dist_equal_sizes(key, shards):
    n, d, k = 1024, 16, 16
    X = gmm_blobs(key, n, d, 16)
    row_ids = jnp.arange(n, dtype=jnp.int32)
    a = np.asarray(two_means_dist(X, row_ids, k, key, shards=shards))
    np.testing.assert_array_equal(np.bincount(a, minlength=k),
                                  np.full(k, n // k))


@pytest.mark.parametrize("shards", [1, 4])
def test_seg_sum_T_matches_float64_segment_sum(key, shards):
    """The (d, k) cluster sums, combined over shards in fixed order, equal a
    float64 segment sum to f32 rounding."""
    n, d, k = 1024, 16, 16
    X = gmm_blobs(key, n, d, 16)
    seg = jax.random.randint(jax.random.fold_in(key, 1), (n,), 0, k)
    got = np.asarray(_TreeTopo(shards, None).fsum_blocks(
        lambda xb, sb: _seg_sum_T(xb, sb, k), X, seg))
    want = np.zeros((k, d))
    np.add.at(want, np.asarray(seg), np.asarray(X, np.float64))
    assert got.shape == (d, k)
    scale = np.abs(np.asarray(X, np.float64)).sum()
    np.testing.assert_allclose(got, want.T, rtol=1e-5, atol=1e-6 * scale / k)


@pytest.mark.parametrize("shards", [1, 4])
def test_seed_rows_T_gathers_owned_rows_exactly(key, shards):
    """Every shard contributes its owned seed rows and zeros elsewhere, so
    the owner psum (here a plain sum over the shards) returns X[pos] bit for
    bit; an empty cluster's sentinel id gathers zeros."""
    n, d, k = 1024, 16, 16
    X = gmm_blobs(key, n, d, 16)
    row_ids = jnp.arange(n, dtype=jnp.int32).astype(jnp.uint32)
    pos = jax.random.randint(jax.random.fold_in(key, 2), (k,), 0, n
                             ).astype(jnp.uint32)
    pos = pos.at[-1].set(jnp.uint32(0xFFFFFFFF))
    B = n // shards
    got = sum(_seed_rows_T(X[r * B:(r + 1) * B], row_ids[r * B:(r + 1) * B],
                           pos) for r in range(shards))
    want = np.asarray(X)[np.asarray(pos[:-1], np.int64)].T
    np.testing.assert_array_equal(np.asarray(got)[:, :-1], want)
    np.testing.assert_array_equal(np.asarray(got)[:, -1], np.zeros(d))


_DOT_RE = re.compile(
    r"stablehlo\.dot_general .*contracting_dims = \[([\d, ]*)\] x "
    r"\[([\d, ]*)\].*: \(tensor<([\dx]+)x\w+>, tensor<([\dx]+)x\w+>\)")


def test_two_means_dist_has_no_k_wide_dot_over_rows(key):
    """The tree's per-cluster sums are segment sums: no dot_general in the
    lowered program contracts the B rows against a k-wide operand (the
    one-hot matmul over all k leaves)."""
    B, d, k = 4096, 16, 64
    X = jnp.zeros((B, d), jnp.float32)
    row_ids = jnp.arange(B, dtype=jnp.int32)
    text = jax.jit(lambda X, r, kk: two_means_dist(X, r, k, kk)).lower(
        X, row_ids, key).as_text()
    dots = _DOT_RE.findall(text)
    assert dots, "no dot_general parsed: the radix select's tril dot is gone"
    for lhs_c, rhs_c, lhs_t, rhs_t in dots:
        for cdims, shape in ((lhs_c, lhs_t), (rhs_c, rhs_t)):
            dims = [int(s) for s in shape.split("x")]
            contract = [dims[int(c)] for c in cdims.split(",") if c.strip()]
            assert not (k in dims and B in contract), (dims, contract)
