"""Pallas TPU kernel: fused nearest-centroid assignment (flash-argmin).

The Lloyd / 2-means assignment step computes argmin_r ||x - C_r||^2 over all k
centroids.  Materialising the (n, k) distance matrix in HBM costs n*k*4 bytes
of traffic; this kernel streams centroid tiles through VMEM and carries a
running (min, argmin) per sample tile, so HBM traffic is O(n*d + k*d + n).

Grid: (n / bn, k / bk), centroid axis innermost; the output block depends only
on the sample tile index, so it acts as the accumulator across centroid tiles
(standard Pallas revisiting pattern).  Scores are laid out TRANSPOSED —
(bk, bn) centroid-major, samples on the 128-wide lane axis — so the per-sample
reductions run over sublanes and the results land lane-dense in (1, bn) /
(p, bn) output blocks.  The row-major (bn, bk) layout, whose per-sample
results must be relaid from sublanes into a (bn,) block, never finished
compiling for v5e (minutes at any size); this one compiles in seconds.
"""
# autotune: exempt(assign_centroids): fixed (bn, bk) streaming grid — the
#   running-argmin accumulator revisits one output block per sample tile, so
#   there is no row-tile knob to sweep (bn/bk are VMEM-capacity constants).
# autotune: exempt(probe_centroids): same streaming grid as assign_centroids
#   (top-p generalisation); no sweepable row tile.
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# default tiles: a (BK, BN) f32 score tile is 128 vregs, which keeps the
# probe kernel's unrolled top-p selection small
BN, BK = 256, 512


def _scores_t(x_ref, c_ref):
    """(bk, bn) partial distances ||c||^2 - 2 c.x (the ||x||^2 term is added
    outside the kernel)."""
    x = x_ref[...].astype(jnp.float32)        # (bn, d)
    c = c_ref[...].astype(jnp.float32)        # (bk, d)
    dots = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)   # (bk, bn)
    csq = jnp.sum(c * c, axis=-1, keepdims=True)
    return csq - 2.0 * dots


def _kernel(x_ref, c_ref, amin_ref, dmin_ref, *, bk: int):
    j = pl.program_id(1)
    part = _scores_t(x_ref, c_ref)                                # (bk, bn)
    row = jax.lax.broadcasted_iota(jnp.int32, part.shape, 0)
    loc_min = jnp.min(part, axis=0, keepdims=True)                # (1, bn)
    # first minimum (jnp.argmin's tie-break), as a masked min over rows
    loc_arg = jnp.min(jnp.where(part == loc_min, row, bk), axis=0,
                      keepdims=True) + j * bk

    @pl.when(j == 0)
    def _init():
        dmin_ref[...] = loc_min
        amin_ref[...] = loc_arg

    @pl.when(j > 0)
    def _update():
        better = loc_min < dmin_ref[...]
        dmin_ref[...] = jnp.where(better, loc_min, dmin_ref[...])
        amin_ref[...] = jnp.where(better, loc_arg, amin_ref[...])


def _select_topk(d: jax.Array, ids: jax.Array, k: int, axis: int = -1):
    """Stable iterative top-k along ``axis`` (Pallas-safe: no gather/sort).

    d, ids: 2-D -> (d, ids) with ``axis`` cut to k, ascending.  Ties
    resolve to the lowest position, so results are deterministic in
    concatenation order.
    """
    L = d.shape[axis]
    pos = jax.lax.broadcasted_iota(jnp.int32, d.shape, axis % 2)
    out_d, out_i = [], []
    for _ in range(k):
        m = jnp.min(d, axis=axis, keepdims=True)
        hit = (d == m) & (pos == jnp.min(jnp.where(d == m, pos, L),
                                         axis=axis, keepdims=True))
        out_d.append(m)
        out_i.append(jnp.sum(jnp.where(hit, ids, 0), axis=axis,
                             keepdims=True))
        # retire the winner: d -> inf so it can't repeat, id -> -1 so that
        # exhausted rows (fewer candidates than k) yield id=-1, not a dupe
        d = jnp.where(hit, jnp.inf, d)
        ids = jnp.where(hit, -1, ids)
    return (jnp.concatenate(out_d, axis=axis),
            jnp.concatenate(out_i, axis=axis))


def _probe_kernel(x_ref, c_ref, pid_ref, pd_ref, *, bk: int, p: int):
    j = pl.program_id(1)
    part = _scores_t(x_ref, c_ref)                                # (bk, bn)
    tile_ids = jax.lax.broadcasted_iota(jnp.int32, part.shape, 0) + j * bk
    # the tile's own top-p first, then a (2p, bn) merge with the running
    # list: ties still resolve running-list first, then by tile position
    d_t, i_t = _select_topk(part, tile_ids, p, axis=0)            # (p, bn)

    @pl.when(j == 0)
    def _init():
        pd_ref[...] = d_t
        pid_ref[...] = i_t

    @pl.when(j > 0)
    def _update():
        d = jnp.concatenate([pd_ref[...], d_t], axis=0)
        ids = jnp.concatenate([pid_ref[...], i_t], axis=0)
        d1, i1 = _select_topk(d, ids, p, axis=0)
        pd_ref[...] = d1
        pid_ref[...] = i1


@functools.partial(jax.jit, static_argnames=("p", "bn", "bk", "interpret"))
def probe_centroids(X: jax.Array, C: jax.Array, p: int, *, bn: int = BN,
                    bk: int = BK, interpret: bool = False):
    """Top-p nearest centroids per sample (IVF coarse probing).

    X: (n, d), C: (k, d) -> (ids (n, p) int32 ascending by distance,
    d2 (n, p) float32).  Same flash-argmin streaming as `assign_centroids`,
    but the revisited output block carries a running top-p per sample.
    n must be a multiple of bn and k of bk; p <= bk (wrappers pad).
    """
    n, d = X.shape
    k = C.shape[0]
    bn = min(bn, n)
    bk = min(bk, k)
    assert n % bn == 0 and k % bk == 0, (n, bn, k, bk)
    assert p <= bk <= k, (p, bk, k)
    # the running list is p8 = p rounded up to the 8-row sublane tile (the
    # extra slots select the next-nearest cells and are sliced off)
    p8 = min(-(-p // 8) * 8, bk)
    pid, pd = pl.pallas_call(
        functools.partial(_probe_kernel, bk=bk, p=p8),
        grid=(n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((p8, bn), lambda i, j: (0, i)),
            pl.BlockSpec((p8, bn), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p8, n), jnp.int32),
            jax.ShapeDtypeStruct((p8, n), jnp.float32),
        ],
        interpret=interpret,
    )(X, C)
    xsq = jnp.sum(X.astype(jnp.float32) ** 2, axis=-1)
    return pid[:p].T, jnp.maximum(pd[:p].T + xsq[:, None], 0.0)


# ---------------------------------------------------------------------------
# padding wrappers: arbitrary (n, k) -> tile multiples
# ---------------------------------------------------------------------------

PAD_SENTINEL = 3e18  # centroid coordinate whose distance dominates everything


def pad_tiles(X: jax.Array, C: jax.Array, bn: int, bk: int):
    """Pad X rows (zeros) and C rows (huge sentinel) to tile multiples.

    Returns (Xp, Cp, bn', bk') where bn'/bk' are clamped to the padded sizes.
    Sentinel centroids sort behind every real centroid, so any top-p with
    p <= k_real never selects them.
    """
    n = X.shape[0]
    k = C.shape[0]
    bn = min(bn, n)
    bk = min(bk, k)
    n_pad = (-n) % bn
    k_pad = (-k) % bk
    Xp = jnp.pad(X, ((0, n_pad), (0, 0))) if n_pad else X
    Cp = (jnp.pad(C, ((0, k_pad), (0, 0)), constant_values=PAD_SENTINEL)
          if k_pad else C)
    return Xp, Cp, bn, bk


def assign_centroids_padded(X: jax.Array, C: jax.Array, *, bn: int = BN,
                            bk: int = BK, interpret: bool = False):
    """`assign_centroids` for arbitrary n, k (pads, runs, slices)."""
    n = X.shape[0]
    Xp, Cp, bn_, bk_ = pad_tiles(X, C, bn, bk)
    a, d2 = assign_centroids(Xp, Cp, bn=bn_, bk=bk_, interpret=interpret)
    return a[:n], d2[:n]


def probe_centroids_padded(X: jax.Array, C: jax.Array, p: int, *,
                           bn: int = BN, bk: int = BK,
                           interpret: bool = False):
    """`probe_centroids` for arbitrary n, k (pads, runs, slices)."""
    n = X.shape[0]
    k = C.shape[0]
    assert p <= k, (p, k)
    Xp, Cp, bn_, bk_ = pad_tiles(X, C, bn, bk)
    if p > bk_:  # tiny-k edge: one tile must still hold top-p
        bk_ = Cp.shape[0]
    ids, d2 = probe_centroids(Xp, Cp, p, bn=bn_, bk=bk_, interpret=interpret)
    return ids[:n], d2[:n]


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def assign_centroids(X: jax.Array, C: jax.Array, *, bn: int = BN,
                     bk: int = BK, interpret: bool = False):
    """X: (n, d), C: (k, d) -> (assign (n,) int32, d2 (n,) float32).

    n must be a multiple of bn and k a multiple of bk (wrappers pad).
    """
    n, d = X.shape
    k = C.shape[0]
    bn = min(bn, n)
    bk = min(bk, k)
    assert n % bn == 0 and k % bk == 0, (n, bn, k, bk)
    amin, dmin = pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=(n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
            pl.BlockSpec((1, bn), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(X, C)
    xsq = jnp.sum(X.astype(jnp.float32) ** 2, axis=-1)
    return amin[0], jnp.maximum(dmin[0] + xsq, 0.0)
