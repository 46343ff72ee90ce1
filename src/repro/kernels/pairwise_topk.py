"""Pallas TPU kernel: batched within-cluster squared-L2 distance matrices.

This is the compute hot-spot of the paper's KNN-graph refinement (Alg. 3,
lines 8-14): clusters have a fixed capacity m (a power of two, MXU-aligned),
so the whole refinement is a dense batched (B, m, m) distance computation.

Tiling: one grid step per cluster tile of ``bB`` clusters; the (bB, m, d)
member tiles live in VMEM and the bB Gram matrices are produced by one
batched MXU matmul with fp32 accumulation (cluster axis = batch dimension).
For d > D_TILE the feature dimension is streamed in VMEM-sized chunks via an
inner loop over a second grid axis, accumulating into the output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# VMEM the double-buffered blocks of one grid step may take, under the
# default 16 MiB scoped-VMEM limit of a TPU core
BLOCK_BYTES = 8 << 20


def row_tile(m: int, d_tile: int) -> int:
    """Clusters per grid step whose double-buffered (bB, m, d_tile) input
    pair and (bB, m, m) output block fit ``BLOCK_BYTES``."""
    return max(BLOCK_BYTES // (2 * 4 * (2 * m * d_tile + m * m)), 1)


def _kernel(x_ref, xt_ref, out_ref):
    """Grid: (B // bB, d // d_tile). Accumulates -2*X@X^T + norms."""
    j = pl.program_id(1)
    nd = pl.num_programs(1)
    x = x_ref[...].astype(jnp.float32)        # (bB, m, d_tile)
    xt = xt_ref[...].astype(jnp.float32)      # (bB, m, d_tile)

    dots = jax.lax.dot_general(
        x, xt, (((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)   # (bB, m, m)
    sq = jnp.sum(x * x, axis=-1)              # (bB, m)
    partial = sq[:, :, None] + sq[:, None, :] - 2.0 * dots

    @pl.when(j == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += partial

    @pl.when(j == nd - 1)
    def _relu():
        out_ref[...] = jnp.maximum(out_ref[...], 0.0)


@functools.partial(jax.jit, static_argnames=("d_tile", "bB", "interpret"))
def pairwise_sq(Xb: jax.Array, *, d_tile: int = 512, bB: int = 1,
                interpret: bool = False) -> jax.Array:
    """Batched squared-L2 distances. Xb: (B, m, d) -> (B, m, m) float32.

    ``bB`` clusters are processed per grid step as one batched dot
    (autotuned via ``kernels.autotune``; 0 = all clusters in one step),
    capped by ``row_tile``'s VMEM budget.
    m should be a multiple of 8 and d a multiple of 128 for TPU lanes; other
    shapes work (Pallas pads) but waste tiles.
    """
    B, m, d = Xb.shape
    d_tile = min(d_tile, d)
    bB = max(1, min(bB if bB else B, B, row_tile(m, d_tile)))
    nd = pl.cdiv(d, d_tile)
    return pl.pallas_call(
        _kernel,
        grid=(pl.cdiv(B, bB), nd),
        in_specs=[
            pl.BlockSpec((bB, m, d_tile), lambda b, j: (b, 0, j)),
            pl.BlockSpec((bB, m, d_tile), lambda b, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((bB, m, m), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, m, m), jnp.float32),
        interpret=interpret,
    )(Xb, Xb)
