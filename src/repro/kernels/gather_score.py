"""Pallas TPU kernel: fused candidate-row gather + move scoring, row-tiled.

The clustering engine's hot loop scores every sample of a batch against C
candidate clusters.  The naive formulation gathers the candidates' composite
vectors into a (B, C, d) tensor — at d=512, kappa=50 that is ~100 kB of HBM
traffic *per sample per epoch* just to materialise rows that are immediately
reduced to scalars.  This kernel gathers each candidate row straight from
HBM into VMEM by manual DMA (``memory_space=pl.ANY`` composite matrix, one
``make_async_copy`` per (sample, candidate) row, indices read from the
scalar-prefetched row table) and reduces it in place, so the gathered
tensor never exists in HBM.

Grid: (B // bB,).  Each step starts the tile's bB·(C+1) row copies into a
(bB, Cp, d) VMEM scratch (Cp = C+1 rounded up to the 8-row sublane tile),
waits for all of them, then issues one (bB, d) x (bB, C+1, d) batched
``dot_general`` — the sample axis is the batch dimension — and computes ALL
of the tile's ΔI (mode='bkm', paper Eqn. 3) or candidate-centroid distances
(mode='lloyd') through ``ref.scores_from_dots``.  Per-cluster norms
``||D_k||²`` and counts are gathered once outside the kernel
(bitwise-identical to re-reducing the gathered rows, and O(k·d) instead of
O(B·C·d)).

Row tiling is bitwise-invariant: the batched dot evaluates each sample's
contraction independently, so every ``bB`` (a multiple of 8, the sublane
tile) produces identical float32 scores — pinned by the regression tests in
tests/test_kernels.py.  Tail rows of a ragged batch (``B % bB != 0``) are
padded onto row table entry 0 and their scores sliced off after the call;
batch independence means they cannot perturb valid rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

# VMEM the gathered-row scratch may take: the tile's value copy for the dot
# and the double-buffered (bB, ·) blocks must still fit under the default
# 16 MiB scoped-VMEM limit of a TPU core.
SCRATCH_BYTES = 4 << 20


def _round8(v: int) -> int:
    return -(-max(v, 1) // 8) * 8


def lane_rows(a: jax.Array) -> jax.Array:
    """(N, nl·128) -> (N·nl, 128): one 128-lane chunk per row, the only
    source shape whose single-row slices Mosaic can DMA (a row of a wider
    (8, 128)-tiled array spans several tiles)."""
    return a.reshape(-1, 128)


def gather_rows(rows_ref, src_hbm, dst_ref, sem, base, bB: int, C: int):
    """DMA-gather the tile's bB x C rows ``src[rows[base + b*C + c]]``.

    ``src_hbm`` is the ``lane_rows`` view (N·nl, 128) of the row source and
    ``dst_ref`` a (nl, bB, Cp, 128) VMEM scratch: chunk l of the row for
    (b, c) lands in ``dst[l, b, c]``.  Every copy is started before any is
    waited on; each wait retires one chunk-sized completion on the shared
    semaphore.
    """
    nl = dst_ref.shape[0]

    def chunk_copy(j, r, lane):
        return pltpu.make_async_copy(
            src_hbm.at[pl.ds(r * nl + lane, 1)],
            dst_ref.at[lane, j // C, pl.ds(j % C, 1)], sem)

    def start(j, carry):
        r = rows_ref[base + j]
        for lane in range(nl):
            chunk_copy(j, r, lane).start()
        return carry

    def wait(j, carry):
        chunk_copy(0, 0, 0).wait()
        return carry

    jax.lax.fori_loop(0, bB * C, start, 0)
    jax.lax.fori_loop(0, bB * C * nl, wait, 0)


def gathered(dst_ref, C: int, d0: int) -> jax.Array:
    """The (bB, C, d0) value of a ``gather_rows`` scratch: lane chunks
    concatenated back along the feature axis, padding sliced off."""
    nl = dst_ref.shape[0]
    parts = [dst_ref[lane, :, :C, :] for lane in range(nl)]
    return (parts[0] if nl == 1 else jnp.concatenate(parts, -1))[..., :d0]


def batched_rowdot(x: jax.Array, Y: jax.Array) -> jax.Array:
    """``out[b, c] = x[b] . Y[b, c]`` as one batched MXU ``dot_general``
    (Mosaic needs a non-contracting lhs dim, hence the unit axis)."""
    return jax.lax.dot_general(
        x[:, None, :], Y, (((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[:, 0, :]


def row_tile(B: int, rows: int, d: int) -> int:
    """Largest 8-aligned row tile whose scratch of ``rows`` gathered rows
    per sample fits ``SCRATCH_BYTES`` (never more than the 8-padded
    batch)."""
    per_sample = _round8(rows) * (-(-d // 128) * 128) * 4
    return min(max(SCRATCH_BYTES // per_sample // 8 * 8, 8), _round8(B))


def _kernel(rows_ref, x_ref, nv_ref, dsq_ref, D_hbm, out_ref, R_ref, sem, *,
            bB: int, C: int, d0: int, mode: str):
    i = pl.program_id(0)
    gather_rows(rows_ref, D_hbm, R_ref, sem, i * bB * (C + 1), bB, C + 1)
    # contract over the NATIVE d0 lanes only: the blocks are zero-padded to
    # full lanes for the memory layout, but reduction length changes float32
    # bits on XLA, so the arithmetic must match ref.py's unpadded reductions
    # exactly
    x = x_ref[...].astype(jnp.float32)[:, :d0]          # (bB, d0)
    R = gathered(R_ref, C + 1, d0)                      # (bB, C+1, d0)
    dots = batched_rowdot(x, R)                         # (bB, C+1)
    xsq = jnp.sum(x * x, axis=-1)                       # (bB,)
    out_ref[...] = _ref.scores_from_dots(dots, nv_ref[...], dsq_ref[...],
                                         xsq, mode)


@functools.partial(jax.jit, static_argnames=("mode", "bB", "interpret"))
def gather_score(x: jax.Array, u: jax.Array, cand: jax.Array, D: jax.Array,
                 cnt: jax.Array, *, mode: str = "bkm", bB: int = 8,
                 interpret: bool = False) -> jax.Array:
    """Score a batch against its candidate clusters without a (B, C, d) gather.

    x: (B, d) samples; u: (B,) int32 current cluster; cand: (B, C) int32
    candidate cluster ids; D: (k, d) float32 composite vectors; cnt: (k,)
    float32 counts.  ``bB`` is the row-tile size (0 = the whole batch),
    rounded up to a multiple of 8 and capped by ``row_tile``'s VMEM budget.

    Returns (B, C) float32: the ΔI of moving each sample to each candidate
    (mode='bkm', self-moves NOT masked — callers mask ``cand == u``), or the
    squared candidate-centroid distance minus ||x||^2, +inf for empty
    candidates (mode='lloyd').  Bitwise-equal to ``ref.gather_score`` in
    interpret mode, at every tile size.
    """
    assert mode in ("bkm", "lloyd"), mode
    B, d = x.shape
    C = cand.shape[1]
    assert cand.shape[0] == B and u.shape == (B,), (x.shape, u.shape,
                                                    cand.shape)
    bB = min(_round8(bB or B), row_tile(B, C + 1, d))
    # the cluster norms reduce over the NATIVE d (before lane-padding) to
    # match ref.py's unpadded reduction bitwise
    dsq_k = jnp.sum(D.astype(jnp.float32) * D.astype(jnp.float32),
                    axis=-1)                            # (k,) cluster norms
    # pad the feature dim to full TPU lanes for the VMEM layout only; the
    # in-kernel contraction slices back to d0 (see _kernel)
    d0 = d
    d_pad = (-d) % 128
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
        D = jnp.pad(D, ((0, 0), (0, d_pad)))
        d = d + d_pad
    # rows[i, 0] = source cluster, rows[i, 1..C] = candidates; ragged tail
    # rows gather row-table entry 0 and are sliced off below
    rows = jnp.concatenate([u[:, None], cand], axis=1).astype(jnp.int32)
    nt = -(-B // bB)
    Bp = nt * bB
    if Bp != B:
        x = jnp.pad(x, ((0, Bp - B), (0, 0)))
        rows = jnp.pad(rows, ((0, Bp - B), (0, 0)))
    nv = cnt.astype(jnp.float32)[rows]                  # (Bp, C+1)
    dsq = dsq_k[rows]                                   # (Bp, C+1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((bB, d), lambda i, rows: (i, 0)),
            pl.BlockSpec((bB, C + 1), lambda i, rows: (i, 0)),
            pl.BlockSpec((bB, C + 1), lambda i, rows: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bB, C), lambda i, rows: (i, 0)),
        scratch_shapes=[pltpu.VMEM((d // 128, bB, _round8(C + 1), 128),
                                   jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bB=bB, C=C, d0=d0, mode=mode),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Bp, C), jnp.float32),
        interpret=interpret,
    )(rows.reshape(-1), x, nv, dsq, lane_rows(D.astype(jnp.float32)))
    return out[:B]
