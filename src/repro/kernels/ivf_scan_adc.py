"""Pallas TPU kernel: fused asymmetric-distance scan over compressed lists.

Same scalar-prefetch tile streaming and running top-k as `ivf_scan` (whole-
array (q, topk) output blocks, row i rewritten by grid row i), but the
candidate payload is u8 codes (`index/quantize.py`) instead of f32 rows: the
per-query distance LUT (q, M, W) is computed ONCE per batch on the host side
of the trace, its flattened (1, 1, M·W) block stays resident in VMEM for the
whole query (the index map ignores the tile step), and only codes +
reconstruction norms stream from HBM — (M + 4) bytes per candidate row
instead of 4·d, the whole point of the codec.

One kernel serves both codecs through the LUT width W (see `ref.adc_expand`):
W=256 (pq) one-hot-expands each code so the table lookup becomes a single
MXU ``dot_general`` against the flattened LUT; W=1 (int8) skips the one-hot
and contracts the cast codes directly.  The query-side affine constant is
rank-invariant, so it rides outside the kernel (``qconst``) and is added to
the selected partials after the top-k — keeping the contraction length
exactly M on both codecs.

The top-k payload is the PACKED ROW POSITION (-1 at invalid slots), not the
id: the exact-rerank tail gathers the original f32 rows by position — no
decode — and re-scores only the survivors.  Ids are recovered by one (q, k)
gather outside the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref
from repro.kernels.ivf_scan import map_query_tiles, q_tile, running_topk


def _kernel(tile_map_ref, lut_ref, vn_ref, code_ref, id_ref, opos_ref,
            od_ref, *, block_rows: int, topk: int, width: int, n_t: int):
    i = pl.program_id(0)
    t = pl.program_id(1)
    lut = lut_ref[...].astype(jnp.float32)      # (1, M, W), VMEM-resident
    lf = lut.reshape(1, lut.shape[1] * width)
    vn = vn_ref[...]                            # (1, bl) f32
    ids = id_ref[...]                           # (1, bl) int32, -1 = padding

    ex = _ref.adc_expand(code_ref[...], width)  # (bl, M*W)
    cross = jax.lax.dot_general(
        lf, ex, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # (1, bl)
    part = vn + cross                           # ||v̂||² - 2 q.v̂
    part = jnp.where(ids < 0, jnp.inf, part)

    tile = tile_map_ref[i * n_t + t]            # scalar prefetch: SMEM read
    pos = (tile * block_rows
           + jax.lax.broadcasted_iota(jnp.int32, (1, block_rows), 1))
    pos = jnp.where(ids < 0, -1, pos)           # (1, bl) packed positions
    running_topk(od_ref, opos_ref, pl.ds(i, 1), t == 0, part, pos, topk)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "topk", "interpret", "bq"))
def ivf_scan_adc(lut: jax.Array, qconst: jax.Array, vnorm: jax.Array,
                 codes: jax.Array, pids: jax.Array, tile_map: jax.Array, *,
                 block_rows: int, topk: int = 10, interpret: bool = False,
                 bq: int = 0):
    """Scan each query's probed tiles of the CODE slab via its VMEM LUT.

    lut: (q, M, W) f32 per-query table and qconst: (q,) per-query constant
    (`index.quantize.build_lut`); vnorm: (n_pad,) f32 reconstruction norms;
    codes: (n_pad, M) u8 packed codes; pids: (n_pad,) int32 ids (-1 =
    padding); tile_map: (q, T) int32.  ``qconst`` is identical for every
    candidate of a query, hence rank-invariant: the kernel selects on the
    LUT partials alone and the constant is added to the selected values
    outside the grid (same op order as the ref oracle).  ``bq`` is the
    query tile, as in `ivf_scan` (the LUT block is per query, so only the
    (·, topk) outputs and the tile map count against its budgets).

    Returns (ids (q, topk) int32, pos (q, topk) int32 packed-row positions,
    part (q, topk) f32 RAW partials ascending, +inf at empty slots) — the
    caller applies `finalize_d2` or the exact-rerank tail; shards merge on
    the raw partials exactly as with `ivf_scan(raw=True)`.
    """
    nq, m, w = lut.shape
    assert codes.shape[0] % block_rows == 0, (codes.shape, block_rows)
    assert codes.shape[1] == m and vnorm.shape[0] == codes.shape[0]
    assert tile_map.shape[0] == nq
    T = tile_map.shape[1]
    bq = min(-(-(bq or nq) // 8) * 8, q_tile(nq, 0, topk, T))

    def scan(lt, tm):                           # one tile of queries
        tiled_row = pl.BlockSpec((1, block_rows),
                                 lambda i, t, tm: (0, tm[i * T + t]))
        whole = pl.BlockSpec((lt.shape[0], topk), lambda i, t, tm: (0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(lt.shape[0], T),
            in_specs=[
                pl.BlockSpec((1, m, w), lambda i, t, tm: (i, 0, 0)),
                tiled_row,
                pl.BlockSpec((block_rows, m),
                             lambda i, t, tm: (tm[i * T + t], 0)),
                tiled_row,
            ],
            out_specs=[whole, whole],
        )
        return pl.pallas_call(
            functools.partial(_kernel, block_rows=block_rows, topk=topk,
                              width=w, n_t=T),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((lt.shape[0], topk), jnp.int32),
                jax.ShapeDtypeStruct((lt.shape[0], topk), jnp.float32),
            ],
            interpret=interpret,
        )(tm.reshape(-1), lt, vnorm[None, :], codes,
          pids.astype(jnp.int32)[None, :])

    opos, od = map_query_tiles(scan, bq, lut.astype(jnp.float32),
                               tile_map.astype(jnp.int32))
    ids = jnp.where(opos < 0, -1, pids.astype(jnp.int32)[jnp.clip(opos, 0)])
    return ids, opos, jnp.where(opos < 0, jnp.inf, od + qconst[:, None])
