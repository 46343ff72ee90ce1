"""Two-means (2M) tree — equal-size recursive bisection (paper Alg. 1).

TPU adaptation (DESIGN.md §2): instead of popping the largest cluster, the tree
is built *level-synchronously*: every level bisects all current clusters in
parallel.  Clusters are contiguous blocks of a permutation array, so each level
is one gather + a segmented 2-means + one lexicographic sort — all static
shapes.  The paper's "adjust to equal size" step is realised exactly by the
median split on the two-means discriminant ``||x - c1||^2 - ||x - c2||^2``.

The level loop is a ``lax.scan`` over a *flat* layout (``two_means_scan``):
each level's clusters are the contiguous length-``m`` blocks of the
permutation, identified by ``segment = position // m`` — every per-level step
(centroid seeding, the equal-size refinement, the median split) is expressed
with segment reductions and one stable multi-key ``lax.sort``, so the shapes
are level-independent and the whole tree is ONE scan instead of ``log2 k``
Python-unrolled trace copies.  This is what lets the KNN-graph builder
(``core.graph_build``) run the tree inside its device-resident tau-round
scan.

Requires k to be a power of two and n divisible by k (see ``pad_plan``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ref import HIGHEST
from repro.obs.timing import layer_scope


def _is_pow2(v: int) -> bool:
    return v > 0 and (v & (v - 1)) == 0


def pad_plan(n: int, k: int) -> Tuple[int, int]:
    """Return (n_padded, k_rounded): k rounded up to a power of two, n padded
    up to a multiple of k_rounded.  Callers pad X by repeating rows and drop
    phantom rows from the result (see knn_graph.py / gkmeans.py)."""
    k2 = 1
    while k2 < k:
        k2 *= 2
    n2 = ((n + k2 - 1) // k2) * k2
    return n2, k2


def two_means_scan(X: jax.Array, k: int, key: jax.Array,
                   refine_iters: int = 4) -> jax.Array:
    """Equal-size 2M-tree partition of X (n, d) into k clusters; assign (n,).

    The un-jitted level-scanned implementation — safe to call inside an outer
    trace (the graph builder's tau-round scan does).  k must be a power of
    two and divide n (use ``pad_plan`` otherwise).
    """
    n, d = X.shape
    assert _is_pow2(k), f"k={k} must be a power of two (see pad_plan)"
    assert n % k == 0, f"n={n} must be divisible by k={k} (see pad_plan)"
    levels = k.bit_length() - 1
    pos = jnp.arange(n, dtype=jnp.int32)
    if levels == 0:
        return jnp.zeros((n,), jnp.int32)
    Xf = X.astype(jnp.float32)

    def level(perm, lvl):
        # blocks at this level: contiguous runs of m slots, segment = pos // m
        m = jnp.int32(n) // (jnp.int32(1) << lvl)
        seg = pos // m
        Xp = Xf[perm]                                        # (n, d)
        tot = jax.ops.segment_sum(Xp, seg, num_segments=k)   # (k, d)

        kl = jax.random.fold_in(key, lvl)
        k1, k2 = jax.random.split(kl)
        safe_m = jnp.maximum(m, 1)
        i1 = jax.random.randint(k1, (k,), 0, safe_m)
        i2 = (i1 + 1 + jax.random.randint(k2, (k,), 0,
                                          jnp.maximum(m - 1, 1))) % safe_m
        start = jnp.arange(k, dtype=jnp.int32) * m
        c1 = Xp[jnp.clip(start + i1, 0, n - 1)]              # (k, d)
        c2 = Xp[jnp.clip(start + i2, 0, n - 1)]

        def delta(c1, c2):
            # ||x-c1||^2 - ||x-c2||^2 = 2 x.(c2-c1) + ||c1||^2 - ||c2||^2
            a = c2[seg] - c1[seg]                            # (n, d)
            off = (jnp.sum(c1 * c1, -1) - jnp.sum(c2 * c2, -1))[seg]
            return 2.0 * jnp.sum(Xp * a, -1) + off

        def left_mask(dlt):
            # left = the m/2 smallest-delta slots of each block (median split)
            _, _, srt = jax.lax.sort((seg, dlt, pos), num_keys=2,
                                     is_stable=True)
            half = (pos % safe_m) < (m // 2)
            return jnp.zeros((n,), bool).at[srt].set(half)

        def refine(_, carry):
            c1, c2 = carry
            w = left_mask(delta(c1, c2)).astype(jnp.float32)
            s1 = jax.ops.segment_sum(Xp * w[:, None], seg, num_segments=k)
            n1 = jax.ops.segment_sum(w, seg, num_segments=k)
            mf = m.astype(jnp.float32)
            c1n = s1 / jnp.maximum(n1, 1.0)[:, None]
            c2n = (tot - s1) / jnp.maximum(mf - n1, 1.0)[:, None]
            return c1n, c2n

        c1, c2 = jax.lax.fori_loop(0, refine_iters, refine, (c1, c2))
        # final equal split: stable lexicographic (segment, delta) sort — the
        # first/last m/2 slots of each block become the two children
        _, _, perm = jax.lax.sort((seg, delta(c1, c2), perm), num_keys=2,
                                  is_stable=True)
        return perm, None

    perm, _ = jax.lax.scan(level, pos, jnp.arange(levels, dtype=jnp.int32))
    block = n // k
    return jnp.zeros((n,), jnp.int32).at[perm].set(pos // block)


@functools.partial(jax.jit, static_argnums=(1, 3))
def two_means_tree(X: jax.Array, k: int, key: jax.Array,
                   refine_iters: int = 4) -> jax.Array:
    """Partition X (n, d) into k equal-size clusters; returns assign (n,).

    k must be a power of two and divide n (use ``pad_plan`` otherwise).
    Jitted wrapper of ``two_means_scan``.
    """
    return two_means_scan(X, k, key, refine_iters)


# ---------------------------------------------------------------------------
# distributed equal-size bisection — histogram medians, O(k) replicated state
# ---------------------------------------------------------------------------
#
# ``two_means_scan`` realises the equal split with a stable global sort over
# the full (n,) permutation, which a sharded build can only run replicated.
# ``two_means_dist`` is the same level-synchronous bisection re-expressed so
# rows stay sharded and the only replicated state is O(k):
#
#   seeds     two random members per cluster, picked by a per-level salted
#             integer hash of the GLOBAL row id (min-hash with row-id
#             tie-break — min reductions are order-invariant, so the psum
#             combine is exact); their vectors are gathered from the owning
#             shard's rows (zeros elsewhere), and the psum reduces owner +
#             zeros.
#   refine    plain 2-means Lloyd steps on the discriminant sign (the paper
#             runs 2-means first and adjusts to equal size after); per-
#             cluster sums are O(B * d) segment sums (``_seg_sum_T``, never a
#             one-hot matmul over all k leaves) that travel transposed as
#             (d, k) per-shard partials combined in FIXED shard order
#             (all-gather + ordered sum), so both topologies add the same
#             blocks in the same order.
#   split     the paper's "adjust to equal size": an EXACT distributed
#             median — 8-round radix select over the composite 64-bit key
#             (monotone-u32(delta) ‖ row id) using (k, 256) int32 histogram
#             psums.  The composite key is unique per row, so every cluster
#             splits exactly in half, deterministically, with no sort.
#
# Every cross-shard combine is either order-invariant (int sums, mins) or
# explicitly ordered (float block sums), so a single-device caller that
# blocks its rows the same way (``shards=R, data_axes=None``) reproduces the
# mesh result bit-exactly — the graph builder's topology-parity contract.

_MASK8 = jnp.uint32(0xFF)
_UMAX = jnp.uint32(0xFFFFFFFF)


def _mix32(x):
    """murmur3 fmix32 — a cheap per-row hash of (global row id ^ salt)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _monotone_u32(f):
    """Order-preserving f32 -> u32 key (IEEE-754 total order trick)."""
    b = jax.lax.bitcast_convert_type(f, jnp.uint32)
    return jnp.where((b >> 31) == 0, b | jnp.uint32(0x80000000), ~b)


class _TreeTopo:
    """Cross-shard combines of the distributed tree, emulation-aware.

    ``data_axes`` set -> real collectives inside shard_map; None -> the
    single-device emulation of an R-way mesh (rows blocked contiguously the
    way the row sharding would slice them).  Int sums and mins are
    order-invariant, so the emulation computes them globally; float sums go
    through ``fsum_blocks`` which materialises the SAME (R, d, k) stacked
    partials in both topologies and reduces them in shard order.
    """

    def __init__(self, shards, data_axes):
        self.R = shards
        self.axes = tuple(data_axes) if data_axes else None

    def isum(self, x):
        if self.axes:
            return jax.lax.psum(x, self.axes)
        return x

    def umin(self, x):
        if self.axes:
            return jax.lax.pmin(x, self.axes)
        return x

    def seg_min(self, vals, seg, k):
        return self.umin(jax.ops.segment_min(vals, seg, num_segments=k))

    def seg_isum(self, vals, seg, k):
        return self.isum(jax.ops.segment_sum(vals, seg, num_segments=k))

    def fsum_blocks(self, partial_fn, *rows):
        """Ordered float combine of per-shard (d, k) partials."""
        if self.axes:
            g = p = partial_fn(*rows)
            for ax in self.axes:
                g = jax.lax.all_gather(g, ax, tiled=False)
            g = g.reshape((-1,) + p.shape)
            return jnp.sum(g, axis=0)
        if self.R == 1:
            return partial_fn(*rows)
        blocked = [a.reshape((self.R, -1) + a.shape[1:]) for a in rows]
        return jnp.sum(jax.vmap(partial_fn)(*blocked), axis=0)

    def owner_fsum(self, x):
        """psum whose every element is owner-value + zeros (exact)."""
        if self.axes:
            return jax.lax.psum(x, self.axes)
        return x


def _seg_sum_T(rows, seg, k):
    """Per-cluster sums of rows (B, d) by seg (B,), transposed to (d, k)."""
    return jax.ops.segment_sum(rows, seg, num_segments=k).T


def _radix_left(ukey, pos_u, seg, k, r, active, topo: _TreeTopo):
    """Exact per-cluster rank select: mark the r[c] smallest composite keys.

    Composite key = (ukey ‖ pos_u), processed high byte first over 8 rounds
    of (256, k) int32 histogram psums — digit-major, so the replicated
    radix state never carries a (k, ·) leading dim.  Row ids are unique, so
    the key is a total order and exactly r[c] rows of every cluster come
    back True.
    """
    left = jnp.zeros(seg.shape, bool)
    for rnd in range(8):
        word = ukey if rnd < 4 else pos_u
        shift = jnp.uint32(8 * (3 - (rnd % 4)))
        digit = ((word >> shift) & _MASK8).astype(jnp.int32)
        flat = digit * k + seg
        hist = jnp.zeros((256 * k,), jnp.int32).at[flat].add(
            active.astype(jnp.int32)).reshape(256, k)
        hist = topo.isum(hist)
        # running count via a lower-triangular dot, NOT jnp.cumsum: XLA
        # lowers a major-axis cumsum through reduce_window in the (k, 256)
        # orientation, rematerialising exactly the k-leading replicated
        # shapes this layout avoids.  f32 accumulation is exact for counts
        # below 2^24 (n_glob is asserted against that bound).
        tri = jnp.tril(jnp.ones((256, 256), jnp.float32))
        cum = jnp.matmul(tri, hist.astype(jnp.float32),
                         precision=HIGHEST).astype(jnp.int32)
        dstar = jnp.argmax(cum > r[None, :], axis=0).astype(jnp.int32)
        below = jnp.take_along_axis(cum - hist, dstar[None, :], 0)[0]
        ds_row = dstar[seg]
        left = left | (active & (digit < ds_row))
        active = active & (digit == ds_row)
        r = r - below
    return left


def _seed_pos(h, pos_u, seg, k, topo: _TreeTopo, exclude=None):
    """Global row id of the min-hash member per cluster (row-id tie-break)."""
    hx = h if exclude is None else jnp.where(pos_u == exclude[seg], _UMAX, h)
    hmin = topo.seg_min(hx, seg, k)
    cand = jnp.where(hx == hmin[seg], pos_u, _UMAX)
    if exclude is not None:
        cand = jnp.where(pos_u == exclude[seg], _UMAX, cand)
    return topo.seg_min(cand, seg, k)


def _seed_rows_T(Xf, pos_u, pos_c):
    """This shard's share of the seed rows, transposed to (d, k): row
    ``pos_c[c]`` where this shard owns it, zeros elsewhere.  A shard's rows
    are the contiguous global ids ``pos_u[0] + [0, B)``."""
    idx = pos_c - pos_u[0]
    owned = idx < jnp.uint32(Xf.shape[0])    # a negative offset wraps high
    rows = Xf[jnp.where(owned, idx, 0).astype(jnp.int32)]      # (k, d)
    return jnp.where(owned[:, None], rows, 0.0).T


def two_means_dist(X_loc: jax.Array, row_ids: jax.Array, k: int,
                   key: jax.Array, *, shards: int = 1, data_axes=None,
                   refine_iters: int = 4) -> jax.Array:
    """Distributed equal-size 2M tree over row-sharded data.

    X_loc (B, d) / row_ids (B,) are this shard's rows of the padded layout
    (``data_axes`` set, inside shard_map) or the full array (``data_axes``
    None; ``shards=R`` emulates the R-way mesh bit-exactly, ``shards=1`` is
    the plain single-device tree).  Returns the local assign (B,) into k
    equal-size clusters.  k must be a power of two and divide the GLOBAL
    row count; every level's replicated state is O(k * 256) ints and
    (d, k) floats — no global sort, no (n,) replicated array.
    """
    assert _is_pow2(k), f"k={k} must be a power of two (see pad_plan)"
    topo = _TreeTopo(shards, data_axes)
    n_glob = X_loc.shape[0] * (topo.R if data_axes else 1)
    assert n_glob % k == 0, f"padded n={n_glob} must be divisible by k={k}"
    assert n_glob < 2 ** 24, \
        f"n={n_glob} overflows the radix select's f32-exact count range"
    levels = k.bit_length() - 1
    Xf = X_loc.astype(jnp.float32)
    pos_u = row_ids.astype(jnp.uint32)
    if levels == 0:
        return jnp.zeros(row_ids.shape, jnp.int32)

    def level(seg, lvl):
        m = jnp.int32(n_glob) >> lvl
        half = m >> 1
        tot_T = topo.fsum_blocks(
            lambda xb, sb: _seg_sum_T(xb, sb, k), Xf, seg)    # (d, k)
        cntc = topo.seg_isum(jnp.ones(seg.shape, jnp.int32), seg, k)

        kl = jax.random.fold_in(key, lvl)
        salts = jax.random.bits(kl, (2,), dtype=jnp.uint32)
        pos1 = _seed_pos(_mix32(pos_u ^ salts[0]), pos_u, seg, k, topo)
        pos2 = _seed_pos(_mix32(pos_u ^ salts[1]), pos_u, seg, k, topo,
                         exclude=pos1)
        c1_T = topo.owner_fsum(_seed_rows_T(Xf, pos_u, pos1))
        c2_T = topo.owner_fsum(_seed_rows_T(Xf, pos_u, pos2))

        def delta_of(c1_T, c2_T):
            # ||x-c1||² - ||x-c2||² = 2 x.(c2-c1) + ||c1||² - ||c2||²;
            # the direction stays in the untracked (d, k) layout and is
            # gathered per row along its minor axis (never a (k, d) operand)
            dir_rows = jnp.take(c2_T - c1_T, seg, axis=1).T  # (B, d)
            off = jnp.sum(c1_T * c1_T, 0) - jnp.sum(c2_T * c2_T, 0)
            return 2.0 * jnp.sum(Xf * dir_rows, -1) + off[seg]

        r_half = jnp.broadcast_to(half, (k,)).astype(jnp.int32)
        all_rows = jnp.ones(seg.shape, bool)

        def refine(_, carry):
            # the same equal-size median split as the final one (mirrors
            # ``two_means_scan``'s refine, which re-splits at the median
            # every iteration): new means of the exact halves
            c1_T, c2_T = carry
            ukey = _monotone_u32(delta_of(c1_T, c2_T))
            w = _radix_left(ukey, pos_u, seg, k, r_half, all_rows, topo
                            ).astype(jnp.float32)
            s1_T = topo.fsum_blocks(
                lambda xb, sb, wb: _seg_sum_T(xb * wb[:, None], sb, k),
                Xf, seg, w)
            n1 = topo.seg_isum(w.astype(jnp.int32), seg, k)
            n1f = jnp.maximum(n1, 1).astype(jnp.float32)
            n2f = jnp.maximum(cntc - n1, 1).astype(jnp.float32)
            return s1_T / n1f[None, :], (tot_T - s1_T) / n2f[None, :]

        c1_T, c2_T = jax.lax.fori_loop(0, refine_iters, refine,
                                       (c1_T, c2_T))
        ukey = _monotone_u32(delta_of(c1_T, c2_T))
        left = _radix_left(ukey, pos_u, seg, k, r_half, all_rows, topo)
        return seg * 2 + jnp.where(left, 0, 1), None

    seg0 = jnp.zeros(row_ids.shape, jnp.int32)
    with layer_scope("graph", "tree"):
        seg, _ = jax.lax.scan(level, seg0,
                              jnp.arange(levels, dtype=jnp.int32))
    return seg
