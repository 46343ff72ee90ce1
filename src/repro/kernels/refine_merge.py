"""Pallas TPU kernel: fused candidate-distance + top-κ merge, row-tiled.

The graph builder's refinement hot loop (``core.graph_build``) compares every
row against C candidate rows (its cluster co-members, Alg. 3, or its
NN-Descent candidate set) and folds the exact distances into the row's sorted
top-κ list.  The naive formulation materialises a (B, C, d) candidate gather
and a (B, C) distance matrix in HBM, then runs a three-argsort dedupe merge
(``knn_graph.merge_topk``) over (B, κ + C).  This kernel gathers each
candidate row straight from HBM into VMEM by manual DMA (the same
``gather_score.gather_rows`` loop over the scalar-prefetched row table) —
neither the gathered tensor nor the distance matrix ever exists in HBM, and
the merge costs O(κ(κ+C)) lane ops instead of three sorts.

Grid: (B // bB,).  Each step DMA-gathers the tile's bB x C candidate rows
into VMEM, computes all bB x C distances at once in MXU form — one
(bB, d) x (bB, C, d) batched ``dot_general`` (sample axis = batch dim) plus
hoisted source norms, ``max(||y||² + ||x||² − 2·x·y, 0)`` — and runs the
vectorised merge (``ref.merge_lists``: repeated first-minimum with
retire-all-copies of the selected id) over the whole (bB, κ+C) tile.  Row
tiling is bitwise-invariant (batch dims evaluate per-row; the merge is
elementwise per row), so every ``bB`` (a multiple of 8) matches the
whole-batch oracle exactly; ragged tails pad the row table with entry 0 and
slice the results off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.gather_score import (_round8, batched_rowdot,
                                        gather_rows, gathered, lane_rows,
                                        row_tile)


def _kernel(rows_ref, x_ref, ysq_ref, oldi_ref, oldd_ref, candi_ref, X_hbm,
            outi_ref, outd_ref, Y_ref, sem, *, bB: int, C: int, kappa: int,
            d0: int):
    i = pl.program_id(0)
    gather_rows(rows_ref, X_hbm, Y_ref, sem, i * bB * C, bB, C)
    # contract over the NATIVE d0 lanes only — blocks are lane-padded for
    # the memory layout, but the arithmetic must match ref.py's unpadded
    # reductions bitwise (see gather_score._kernel)
    x = x_ref[...].astype(jnp.float32)[:, :d0]          # (bB, d0)
    dots = batched_rowdot(x, gathered(Y_ref, C, d0))    # (bB, C)
    xsq = jnp.sum(x * x, axis=-1)                       # (bB,)
    cd = jnp.maximum(ysq_ref[...] + xsq[:, None] - 2.0 * dots, 0.0)
    oi, od = _ref.merge_lists(oldi_ref[...], oldd_ref[...].astype(jnp.float32),
                              candi_ref[...], cd, kappa)
    outi_ref[...] = oi
    outd_ref[...] = od


@functools.partial(jax.jit, static_argnames=("bB", "interpret"))
def refine_merge(x: jax.Array, rows: jax.Array, cand_ids: jax.Array,
                 old_ids: jax.Array, old_d: jax.Array, Xsrc: jax.Array, *,
                 bB: int = 8, interpret: bool = False):
    """Merge C candidates into each row's top-κ list without an HBM gather.

    x: (B, d) row vectors; rows: (B, C) int32 indices into Xsrc (pre-clamped
    >= 0); cand_ids: (B, C) int32 neighbour ids (-1 = invalid); old_ids /
    old_d: (B, κ) current lists (-1/inf padded); Xsrc: (N, d).  ``bB`` is
    the row-tile size (0 = the whole batch), rounded up to a multiple of 8
    and capped by ``row_tile``'s VMEM budget.

    Returns (ids (B, κ) int32, d (B, κ) float32) ascending by distance,
    id-deduped, -1/inf padded — bitwise-equal to ``ref.refine_merge`` in
    interpret mode, at every tile size.
    """
    B, d = x.shape
    C = rows.shape[1]
    kappa = old_ids.shape[1]
    assert rows.shape == cand_ids.shape == (B, C), (rows.shape, cand_ids.shape)
    assert old_ids.shape == old_d.shape == (B, kappa)
    bB = min(_round8(bB or B), row_tile(B, C, d))
    # the source norms reduce over the NATIVE d (before lane-padding) to
    # match ref.py's unpadded reduction bitwise
    Xn = Xsrc.astype(jnp.float32)
    ysq_src = jnp.sum(Xn * Xn, axis=-1)                 # (N,) hoisted norms
    # pad the feature dim to full TPU lanes for the VMEM block layout only;
    # the in-kernel contraction slices back to d0 (see _kernel)
    d0 = d
    d_pad = (-d) % 128
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
        Xsrc = jnp.pad(Xsrc, ((0, 0), (0, d_pad)))
        d = d + d_pad
    rows = rows.astype(jnp.int32)
    cand_ids = cand_ids.astype(jnp.int32)
    old_ids = old_ids.astype(jnp.int32)
    old_d = old_d.astype(jnp.float32)
    nt = -(-B // bB)
    Bp = nt * bB
    if Bp != B:
        # ragged tail: pad onto source row 0 / empty lists, slice off below
        x = jnp.pad(x, ((0, Bp - B), (0, 0)))
        rows = jnp.pad(rows, ((0, Bp - B), (0, 0)))
        cand_ids = jnp.pad(cand_ids, ((0, Bp - B), (0, 0)),
                           constant_values=-1)
        old_ids = jnp.pad(old_ids, ((0, Bp - B), (0, 0)), constant_values=-1)
        old_d = jnp.pad(old_d, ((0, Bp - B), (0, 0)),
                        constant_values=jnp.inf)
    Xf = Xsrc.astype(jnp.float32)
    ysq = ysq_src[rows]                                 # (Bp, C)

    row = lambda w: pl.BlockSpec((bB, w), lambda i, rows: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nt,),
        in_specs=[row(d), row(C), row(kappa), row(kappa), row(C),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(row(kappa), row(kappa)),
        scratch_shapes=[pltpu.VMEM((d // 128, bB, _round8(C), 128),
                                   jnp.float32),
                        pltpu.SemaphoreType.DMA(())],
    )
    oi, od = pl.pallas_call(
        functools.partial(_kernel, bB=bB, C=C, kappa=kappa, d0=d0),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((Bp, kappa), jnp.int32),
                   jax.ShapeDtypeStruct((Bp, kappa), jnp.float32)),
        interpret=interpret,
    )(rows.reshape(-1), x, ysq, old_ids, old_d, cand_ids, lane_rows(Xf))
    return oi[:B], od[:B]
