"""Pure-jnp oracles for the Pallas kernels (the correctness reference)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Every matmul of the system's float32 statistics asks for full float32
# precision: XLA:TPU would otherwise run it as one bfloat16 pass (the ΔI
# move scores cancel heavily).  XLA:CPU computes float32 either way.
HIGHEST = jax.lax.Precision.HIGHEST


def pairwise_sq(Xb: jax.Array, *, tile: int = 0) -> jax.Array:
    """Batched squared-L2 distance matrix.

    Xb: (B, m, d)  ->  (B, m, m) float32, D[b,i,j] = ||x_i - x_j||^2.
    ``tile`` chunks the cluster axis (a ``lax.map`` over cluster tiles,
    bounding the working set to tile*(m*d + m*m) floats); each cluster's
    Gram matrix is an independent batched dot, so chunking never changes
    the result.
    """
    B = Xb.shape[0]

    def block(Xf):
        sq = jnp.sum(Xf * Xf, axis=-1)                     # (B', m)
        dots = jnp.einsum("bid,bjd->bij", Xf, Xf,
                          precision=HIGHEST)              # (B', m, m)
        d2 = sq[:, :, None] + sq[:, None, :] - 2.0 * dots
        return jnp.maximum(d2, 0.0)

    Xf = Xb.astype(jnp.float32)
    if not tile or tile >= B:
        return block(Xf)
    nt = -(-B // tile)
    pad = nt * tile - B
    Xp = jnp.pad(Xf, ((0, pad), (0, 0), (0, 0)))
    Xp = Xp.reshape(nt, tile, *Xb.shape[1:])
    out = jax.lax.map(block, Xp)
    return out.reshape(nt * tile, Xb.shape[1], Xb.shape[1])[:B]


def stable_topk(d: jax.Array, ids: jax.Array, k: int):
    """Iterative top-k over the last axis, ties to the lowest position.

    Matches the selection order of the Pallas kernels' running top-k exactly
    (jnp.argmin also returns the first minimum).
    d, ids: (..., L) -> (d (..., k) ascending, ids (..., k)).
    """
    out_d, out_i = [], []
    for _ in range(k):
        a = jnp.argmin(d, axis=-1)
        hit = jnp.arange(d.shape[-1]) == a[..., None]
        out_d.append(jnp.take_along_axis(d, a[..., None], -1)[..., 0])
        out_i.append(jnp.take_along_axis(ids, a[..., None], -1)[..., 0])
        # retire the winner (id -> -1: exhausted rows yield -1, not a dupe)
        d = jnp.where(hit, jnp.inf, d)
        ids = jnp.where(hit, -1, ids)
    return jnp.stack(out_d, axis=-1), jnp.stack(out_i, axis=-1)


@functools.partial(jax.jit, static_argnames=("p",))
def probe_centroids(X: jax.Array, C: jax.Array, p: int):
    """Top-p nearest centroids per sample.

    X: (n, d), C: (k, d) -> (ids (n, p) int32 ascending by distance,
    d2 (n, p) float32 with the ||x||^2 term included).

    Jitted so the scores match the mesh-sharded serving path bitwise: the
    sharded IVF trace computes this replicated probe inside jit, and
    XLA:CPU's jitted fusion rounds differently than op-by-op eager mode.
    """
    Xf = X.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    csq = jnp.sum(Cf * Cf, axis=-1)
    part = csq[None, :] - 2.0 * jnp.matmul(Xf, Cf.T, precision=HIGHEST)
    d, ids = stable_topk(part, jnp.broadcast_to(
        jnp.arange(C.shape[0], dtype=jnp.int32), part.shape), p)
    xsq = jnp.sum(Xf * Xf, axis=-1)
    return ids, jnp.maximum(d + xsq[:, None], 0.0)


def finalize_d2(ids: jax.Array, od: jax.Array, Q: jax.Array):
    """Raw partial scan distances -> exact squared L2 for callers.

    ids: (q, t) selected ids (-1 = empty slot); od: (q, t) partials
    (``||v||^2 - 2 q.v``, +inf at empty slots); Q: (q, d).  EVERY scan exit
    path — per-query kernel/ref, grouped kernel/ref, the sharded merge —
    must apply this one transform in this op order: the cross-topology
    bit-exactness guarantees rest on the selected partials going through
    identical arithmetic everywhere.
    """
    qsq = jnp.sum(Q.astype(jnp.float32) ** 2, axis=-1)
    d2 = jnp.maximum(od + qsq[:, None], 0.0)
    # empty slots carry id -1 (fewer candidates than topk); their distance
    # is +inf for callers
    return ids, jnp.where(ids < 0, jnp.inf, d2)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "topk", "raw", "tile"))
def ivf_scan(Q: jax.Array, vecs: jax.Array, pids: jax.Array,
             tile_map: jax.Array, *, block_rows: int, topk: int = 10,
             raw: bool = False, tile: int = 0):
    """Inverted-list scan oracle over the packed layout.

    Gathers every probed tile's rows per query (same traversal order as the
    Pallas kernel) and selects top-k with the same stable tie-break.
    ``raw=True`` returns the partial distances (``||v||^2 - 2 q.v``, without
    the ``||q||^2`` term or the >=0 clamp, +inf at invalid slots) — the form
    mesh shards merge on before the final monotone transform, so cross-shard
    selection is bit-identical to a single-device scan.  Jitted for the same
    cross-topology bitwise reason as ``probe_centroids``: the per-candidate
    scores must round identically inside the sharded trace and out here.

    ``tile`` chunks the QUERY axis (a ``lax.map`` over query tiles, bounding
    the gathered working set to tile * T * block_rows rows) — each query's
    scores are an independent batch element of the einsum, so every tile
    size is bitwise-identical (see ``batched_gather_dots``; the chunk is
    clamped >= 2 for the same batch-1 strength-reduction reason).
    """
    nq = Q.shape[0]
    Qf = Q.astype(jnp.float32)

    def chunk(args):
        qf, tm = args                                       # (c, d), (c, T)
        pos = (tm[:, :, None] * block_rows
               + jnp.arange(block_rows, dtype=jnp.int32))   # (c, T, bl)
        pos = pos.reshape(qf.shape[0], -1)                  # (c, L)
        cids = pids[pos]                                    # (c, L)
        cv = vecs[pos].astype(jnp.float32)                  # (c, L, d)
        vsq = jnp.sum(cv * cv, axis=-1)                     # (c, L)
        dots = jnp.einsum("qd,qld->ql", qf, cv, precision=HIGHEST)
        part = jnp.where(cids < 0, jnp.inf, vsq - 2.0 * dots)
        return stable_topk(part, cids, topk)

    if not tile or tile >= nq:
        d, ids = chunk((Qf, tile_map))
    else:
        t = max(tile, 2)
        nt = -(-nq // t)
        pad = nt * t - nq
        Qp = jnp.pad(Qf, ((0, pad), (0, 0))).reshape(nt, t, Qf.shape[1])
        tp = jnp.pad(tile_map, ((0, pad), (0, 0))).reshape(
            nt, t, tile_map.shape[1])
        d, ids = jax.lax.map(chunk, (Qp, tp))
        d = d.reshape(nt * t, topk)[:nq]
        ids = ids.reshape(nt * t, topk)[:nq]
    if raw:
        return ids, jnp.where(ids < 0, jnp.inf, d)
    return finalize_d2(ids, d, Q)


@functools.partial(jax.jit, static_argnames=("block_rows", "topk", "raw"))
def ivf_scan_grouped(Qg: jax.Array, vecs: jax.Array, pids: jax.Array,
                     union_tiles: jax.Array, qmask: jax.Array, *,
                     block_rows: int, topk: int = 10, raw: bool = False):
    """Query-grouped inverted-list scan oracle (the batched kernel's twin).

    Qg: (ngroups * G, d) queries already permuted into probe-locality groups;
    union_tiles: (ngroups, U) int32 deduped tile indices per group (padding
    slots point at the all-hole null tile); qmask: (ngroups * G, U) nonzero
    where query i of the group probed union slot s.  Each group streams each
    union tile ONCE and scores all G member queries against it; a query only
    accumulates candidates from tiles it actually probed (mask -> id=-1/inf,
    exactly as the kernel does).

    To stay bitwise-equal to the Pallas kernel in interpret mode the per-tile
    scores go through the same (G, d) x (bl, d) ``dot_general`` the kernel
    issues (a lax.map over union slots, not one big einsum) — and the whole
    oracle is jitted, because XLA:CPU fuses the dot with the following
    subtract differently under jit than op-by-op, and interpret-mode Pallas
    bodies execute inside the enclosing jit trace.
    """
    ngroups, U = union_tiles.shape
    G = Qg.shape[0] // ngroups
    Qf = Qg.astype(jnp.float32).reshape(ngroups, G, -1)
    mask = qmask.reshape(ngroups, G, U)

    def group_scores(args):
        qf, tiles = args                                    # (G, d), (U,)

        def slot_scores(t):
            pos = t * block_rows + jnp.arange(block_rows, dtype=jnp.int32)
            cv = vecs[pos].astype(jnp.float32)              # (bl, d)
            dots = jax.lax.dot_general(
                qf, cv, (((1,), (1,)), ((), ())), precision=HIGHEST,
                preferred_element_type=jnp.float32)         # (G, bl)
            vsq = jnp.sum(cv * cv, axis=-1)                 # (bl,)
            return vsq[None, :] - 2.0 * dots, pids[pos]

        return jax.lax.map(slot_scores, tiles)              # (U, G, bl)

    part, cids = jax.lax.map(group_scores, (Qf, union_tiles))
    part = part.transpose(0, 2, 1, 3).reshape(ngroups, G, U * block_rows)
    cids = cids.reshape(ngroups, U * block_rows)
    # mask out candidates from tiles a query did not probe, and padding
    # rows, as id=-1/inf — identically to the kernel
    ok = (jnp.repeat(mask, block_rows, axis=-1)             # (ngroups, G, U*bl)
          & (cids[:, None, :] >= 0))
    ids = jnp.where(ok, cids[:, None, :], -1)
    part = jnp.where(ids < 0, jnp.inf, part)
    d, ids = stable_topk(part.reshape(ngroups * G, -1),
                         ids.reshape(ngroups * G, -1), topk)
    if raw:
        # partial distances for cross-shard merges (see ivf_scan's raw)
        return ids, jnp.where(ids < 0, jnp.inf, d)
    return finalize_d2(ids, d, Qg)


def adc_expand(codes: jax.Array, width: int) -> jax.Array:
    """u8 codes (..., M) -> f32 "expanded" codes (..., M * width).

    The shared kernel/ref body of the ADC contraction: with ``width == 1``
    (int8 codec) the LUT "lookup" is a plain multiply, so the expansion is
    just the f32 cast; with ``width == 256`` (pq) each code becomes a one-hot
    row, turning the table lookup ``sum_m lut[m, c[m]]`` into one MXU
    ``dot_general`` against the flattened (M * width) LUT.  The one-hot adds
    exact zeros, so the contraction's float32 result per candidate is the
    gathered sum itself — same arithmetic on both sides, bitwise.
    """
    ci = codes.astype(jnp.int32)
    if width == 1:
        return ci.astype(jnp.float32)
    # one lane-aligned (..., width) one-hot block per code column: Mosaic
    # cannot fold an (M, width) pair of axes into one lane axis by reshape
    iota = jax.lax.broadcasted_iota(jnp.int32, ci.shape[:-1] + (width,),
                                    ci.ndim - 1)
    return jnp.concatenate(
        [(ci[..., m:m + 1] == iota).astype(jnp.float32)
         for m in range(ci.shape[-1])], axis=-1)


@functools.partial(jax.jit, static_argnames=("block_rows", "topk", "tile"))
def ivf_scan_adc(lut: jax.Array, qconst: jax.Array, vnorm: jax.Array,
                 codes: jax.Array, pids: jax.Array, tile_map: jax.Array, *,
                 block_rows: int, topk: int = 10, tile: int = 0):
    """Asymmetric-distance scan oracle over compressed packed lists.

    lut: (q, M, W) per-query distance table and qconst: (q,) per-query
    constant (`index.quantize.build_lut`); vnorm: (n_pad,) f32
    reconstruction norms; codes: (n_pad, M) u8; pids/tile_map as in
    ``ivf_scan``.  Scores are the same partial-distance convention as
    ``ivf_scan`` (``||v̂||² - 2 q.v̂``, v̂ the reconstruction):
    ``part = vnorm + sum_m lut[m, code[m]]`` via the ``adc_expand`` one-hot
    contraction — identical arithmetic to the Pallas kernel, which streams
    tiles in the same slot order (the ``lax.map`` below mirrors its grid).
    ``qconst`` is rank-invariant, so the top-k selects on the kernel's
    partials and the constant is added to the SELECTED values only — the
    same op order as the kernel wrapper, keeping parity bitwise.

    Returns (ids (q, topk) int32, pos (q, topk) int32 PACKED ROW positions
    (-1 at empty slots — the payload the exact-rerank tail gathers f32
    originals with, no decode), part (q, topk) f32 raw partials, +inf at
    empty slots).  Callers finalize via ``finalize_d2`` or rerank.

    ``tile`` chunks the query axis exactly like ``ivf_scan``'s (bitwise-
    invariant, clamp >= 2); the per-slot streaming bounds the one-hot
    working set to chunk * block_rows * M * W floats either way.
    """
    nq, M, W = lut.shape
    if nq == 1:
        # batch-1 dot_general strength-reduces on XLA:CPU (last-ulp drift);
        # pad to 2 identical queries, same clamp as batched_gather_dots
        two = lambda a: jnp.concatenate([a, a], axis=0)
        ids, pos, part = ivf_scan_adc(two(lut), two(qconst), vnorm, codes,
                                      pids, two(tile_map),
                                      block_rows=block_rows, topk=topk,
                                      tile=0)
        return ids[:1], pos[:1], part[:1]
    lflat = lut.reshape(nq, M * W).astype(jnp.float32)
    T = tile_map.shape[1]

    def chunk(args):
        lf, qc, tm = args                            # (c, MW), (c,), (c, T)
        c = lf.shape[0]

        def slot(s):
            pos = (tm[:, s][:, None] * block_rows
                   + jnp.arange(block_rows, dtype=jnp.int32))   # (c, bl)
            ex = adc_expand(codes[pos], W)                  # (c, bl, MW)
            cross = jax.lax.dot_general(
                lf, ex, (((1,), (2,)), ((0,), (0,))), precision=HIGHEST,
                preferred_element_type=jnp.float32)         # (c, bl)
            return cross, pos

        cross, pos = jax.lax.map(slot, jnp.arange(T))       # (T, c, bl) x2
        cross = cross.transpose(1, 0, 2).reshape(c, -1)     # (c, L)
        pos = pos.transpose(1, 0, 2).reshape(c, -1)         # (c, L)
        cids = pids[pos]
        part = jnp.where(cids < 0, jnp.inf, vnorm[pos] + cross)
        ppos = jnp.where(cids < 0, -1, pos)
        d, psel = stable_topk(part, ppos, topk)
        ids = jnp.where(psel < 0, -1, pids[jnp.clip(psel, 0)])
        return ids, psel, jnp.where(psel < 0, jnp.inf, d + qc[:, None])

    if not tile or tile >= nq:
        return chunk((lflat, qconst, tile_map))
    t = max(tile, 2)
    nt = -(-nq // t)
    pad = nt * t - nq
    lp = jnp.pad(lflat, ((0, pad), (0, 0))).reshape(nt, t, M * W)
    qp = jnp.pad(qconst, (0, pad)).reshape(nt, t)
    tp = jnp.pad(tile_map, ((0, pad), (0, 0))).reshape(nt, t, T)
    ids, psel, d = jax.lax.map(chunk, (lp, qp, tp))
    return (ids.reshape(nt * t, topk)[:nq],
            psel.reshape(nt * t, topk)[:nq],
            d.reshape(nt * t, topk)[:nq])


def batched_gather_dots(xf: jax.Array, rows: jax.Array, src: jax.Array,
                        tile: int = 0) -> jax.Array:
    """``dots[i, j] = xf[i] . src[rows[i, j]]`` with the sample axis batched.

    The per-sample dot is issued as ONE ``dot_general`` whose batch dimension
    is the sample axis — every sample's contraction is independent, so
    chunking the batch with ``tile`` (a ``lax.map`` over row tiles, bounding
    the gathered working set to (tile, C, d)) is bitwise invariant: every
    tile size, including the row-tiled Pallas kernels' ``bB``, produces
    identical float32 scores.  ``tile=0`` (or >= B) runs one whole-batch dot.
    (tile — and a B=1 batch — is clamped/padded to >= 2 rows: XLA:CPU
    strength-reduces a batch-1 dot_general to a plain matvec whose reduction
    order differs in the last ulp, the same clamp as the Pallas ``bB``.)
    """
    B = xf.shape[0]
    if B == 1:
        xf = jnp.concatenate([xf, xf], axis=0)
        rows = jnp.concatenate([rows, rows], axis=0)
        return jax.lax.dot_general(
            xf, src[rows], (((1,), (2,)), ((0,), (0,))), precision=HIGHEST,
            preferred_element_type=jnp.float32)[:1]
    if not tile or tile >= B:
        return jax.lax.dot_general(
            xf, src[rows], (((1,), (2,)), ((0,), (0,))), precision=HIGHEST,
            preferred_element_type=jnp.float32)
    tile = max(tile, 2)
    nt = -(-B // tile)
    pad = nt * tile - B
    xp = jnp.pad(xf, ((0, pad), (0, 0))).reshape(nt, tile, xf.shape[1])
    rp = jnp.pad(rows, ((0, pad), (0, 0))).reshape(nt, tile, rows.shape[1])

    def one(args):
        xt, rt = args
        return jax.lax.dot_general(
            xt, src[rt], (((1,), (2,)), ((0,), (0,))), precision=HIGHEST,
            preferred_element_type=jnp.float32)

    dots = jax.lax.map(one, (xp, rp))
    return dots.reshape(nt * tile, rows.shape[1])[:B]


def scores_from_dots(dots: jax.Array, nv: jax.Array, dsq: jax.Array,
                     xsq: jax.Array, mode: str) -> jax.Array:
    """Move scores from precomputed inner products (shared kernel/ref body).

    dots/nv/dsq: (B, C+1) with slot 0 = the source cluster u and slots 1..C
    the candidates (x·D[row], cnt[row], ||D[row]||² per slot); xsq: (B,).
    Every op is elementwise per row, so the scores are invariant to how the
    batch was tiled when computing ``dots`` — the tiled Pallas kernels call
    this exact function per row tile and match the whole-batch oracle
    bitwise.
    """
    nv_c, dsq_c, xd_c = nv[:, 1:], dsq[:, 1:], dots[:, 1:]
    if mode == "lloyd":
        inv = 1.0 / jnp.maximum(nv_c, 1.0)
        d2 = dsq_c * (inv * inv) - 2.0 * (xd_c * inv)
        return jnp.where(nv_c > 0, d2, jnp.inf)
    nu, dsq_u, xd_u = nv[:, 0], dsq[:, 0], dots[:, 0]
    gain = (dsq_c + 2.0 * xd_c + xsq[:, None]) / (nv_c + 1.0)
    gain = gain - jnp.where(nv_c > 0, dsq_c / jnp.maximum(nv_c, 1.0), 0.0)
    num_u = dsq_u - 2.0 * xd_u + xsq
    resid = jnp.where(nu > 1, num_u / jnp.maximum(nu - 1.0, 1.0), 0.0)
    loss_u = resid - dsq_u / jnp.maximum(nu, 1.0)
    return gain + loss_u[:, None]


@functools.partial(jax.jit, static_argnames=("mode", "tile"))
def gather_score(x: jax.Array, u: jax.Array, cand: jax.Array, D: jax.Array,
                 cnt: jax.Array, *, mode: str = "bkm",
                 tile: int = 0) -> jax.Array:
    """Candidate-move scoring oracle (the engine's hot loop), MXU-shaped.

    x: (B, d), u: (B,) int32 source clusters, cand: (B, C) int32 candidate
    clusters, D: (k, d) composite vectors, cnt: (k,) counts.

    mode='bkm': ΔI of moving x from u to each candidate (paper Eqn. 3;
    self-moves not masked).  mode='lloyd': squared distance to each candidate
    centroid minus ||x||^2, +inf for empty candidates.

    The inner products go through one batched ``dot_general`` (sample axis =
    batch dim) over the gathered (B, C+1, d) composite rows, with the
    per-cluster norms ``||D_k||²`` precomputed once — this is what makes the
    scoring hot path fast on every backend.  ``tile`` chunks the batch (see
    ``batched_gather_dots``) to bound the gather working set; every tile size
    is bitwise-identical, so the autotuner is free to pick.  Jitted for the
    same cross-topology fusion-rounding reason as ``ivf_scan_grouped``.

    Every reduction runs over the NATIVE feature dim: lane-padding belongs to
    the memory layout, not the arithmetic, so the CPU path never pays gather
    traffic for zero lanes (4x at d=32).  The Pallas kernel pads only its
    VMEM blocks to full 128-wide TPU lanes and slices the contraction back
    to ``d`` — reduction length changes float32 bits on XLA even when the
    extra lanes are zero, so both sides must contract exactly ``d`` lanes
    for the bitwise contract to hold.
    """
    xf = x.astype(jnp.float32)
    Df = D.astype(jnp.float32)
    rows = jnp.concatenate([u[:, None], cand], axis=1).astype(jnp.int32)
    dsq_k = jnp.sum(Df * Df, axis=-1)                   # (k,)
    dots = batched_gather_dots(xf, rows, Df, tile)      # (B, C+1)
    nv = cnt.astype(jnp.float32)[rows]
    dsq = dsq_k[rows]
    xsq = jnp.sum(xf * xf, axis=-1)
    return scores_from_dots(dots, nv, dsq, xsq, mode)


def gather_score_rowwise(x: jax.Array, u: jax.Array, cand: jax.Array,
                         D: jax.Array, cnt: jax.Array, *,
                         mode: str = "bkm") -> jax.Array:
    """Pre-tiling per-row oracle (elementwise reductions over a (B, C, d)
    gather) — kept as the bench baseline the row-tiled path must beat.

    Reduction order differs from the dot-based ``gather_score`` (the ΔI
    terms cancel heavily, so the two disagree in the last few ulps); the
    row-tiling regression test pins the NEW arithmetic across tile sizes
    instead, and this function pins what the old per-row kernels computed.
    """
    d_pad = (-x.shape[1]) % 128
    if d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad)))
        D = jnp.pad(D, ((0, 0), (0, d_pad)))
    xf = x.astype(jnp.float32)
    Dv = D.astype(jnp.float32)[cand]                    # (B, C, d)
    nv = cnt[cand].astype(jnp.float32)                  # (B, C)
    if mode == "lloyd":
        inv = 1.0 / jnp.maximum(nv, 1.0)
        cc = Dv * inv[..., None]
        d2 = (jnp.sum(cc * cc, axis=-1)
              - 2.0 * jnp.sum(xf[:, None, :] * cc, axis=-1))
        return jnp.where(nv > 0, d2, jnp.inf)
    Du = D.astype(jnp.float32)[u]                       # (B, d)
    nu = cnt[u].astype(jnp.float32)                     # (B,)
    xsq = jnp.sum(xf * xf, axis=-1)                     # (B,)
    du_sq = jnp.sum(Du * Du, axis=-1)
    x_du = jnp.sum(xf * Du, axis=-1)
    dv_sq = jnp.sum(Dv * Dv, axis=-1)                   # (B, C)
    x_dv = jnp.sum(xf[:, None, :] * Dv, axis=-1)
    gain = (dv_sq + 2.0 * x_dv + xsq[:, None]) / (nv + 1.0)
    gain = gain - jnp.where(nv > 0, dv_sq / jnp.maximum(nv, 1.0), 0.0)
    num_u = du_sq - 2.0 * x_du + xsq
    resid = jnp.where(nu > 1, num_u / jnp.maximum(nu - 1.0, 1.0), 0.0)
    loss_u = resid - du_sq / jnp.maximum(nu, 1.0)
    return gain + loss_u[:, None]


def merge_lists(old_ids: jax.Array, old_d: jax.Array, cand_ids: jax.Array,
                cd: jax.Array, kappa: int):
    """Top-κ merge of candidate distances into sorted lists (kernel/ref body).

    old_ids/old_d: (B, κ) current lists; cand_ids/cd: (B, C) candidates with
    id -1 = invalid.  Iterative first-minimum selection with
    retire-all-copies of the selected id (the dedupe) — every op is
    elementwise per row, so the merge is invariant to row tiling and the
    tiled Pallas kernel reuses this exact function per tile.
    """
    kappa_old, C = old_ids.shape[-1], cand_ids.shape[-1]
    L = kappa_old + C
    ent_d = jnp.concatenate([old_d.astype(jnp.float32),
                             cd.astype(jnp.float32)], axis=-1)
    ent_i = jnp.concatenate([old_ids, cand_ids], axis=-1).astype(jnp.int32)
    ent_d = jnp.where(ent_i < 0, jnp.inf, ent_d)
    # 2-D iota (broadcast over rows): legal inside Pallas TPU bodies too
    col = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    out_d, out_i = [], []
    for j in range(kappa):
        mv = jnp.min(ent_d, axis=-1)                       # (B,)
        hit = ent_d == mv[:, None]
        pos = jnp.min(jnp.where(hit, col, L), axis=-1)     # first minimum
        at = col == pos[:, None]
        sid = jnp.sum(jnp.where(at, ent_i, 0), axis=-1)
        valid = mv < jnp.inf
        out_d.append(jnp.where(valid, mv, jnp.inf))
        out_i.append(jnp.where(valid, sid, -1))
        # retire the winner and every other copy of its id (dedupe)
        ent_d = jnp.where((ent_i == sid[:, None]) | at, jnp.inf, ent_d)
    return jnp.stack(out_i, axis=-1), jnp.stack(out_d, axis=-1)


@functools.partial(jax.jit, static_argnames=("tile",))
def refine_merge(x: jax.Array, rows: jax.Array, cand_ids: jax.Array,
                 old_ids: jax.Array, old_d: jax.Array, Xsrc: jax.Array, *,
                 tile: int = 0):
    """Fused candidate-distance + top-κ merge oracle (graph-build hot loop).

    x: (B, d) row vectors; rows: (B, C) int32 gather indices into Xsrc
    (pre-clamped >= 0); cand_ids: (B, C) int32 neighbour ids with -1 =
    invalid; old_ids/old_d: (B, κ) current lists (-1/inf padded);
    Xsrc: (N, d) candidate vector source.

    Returns (ids (B, κ) int32, d (B, κ) float32): squared distances to the
    candidates merged into the old lists — ascending by distance, id-deduped
    (duplicates keep their best distance), -1/inf padded.  Distances use the
    MXU form ``||y||² + ||x||² − 2·x·y`` (clamped >= 0, like ``pairwise_sq``)
    with the source norms hoisted out of the gather and the dots batched over
    the sample axis — ``tile`` chunks the batch bitwise-invariantly (see
    ``batched_gather_dots``).  Reductions run over the NATIVE feature dim
    (see ``gather_score``: the Pallas kernel lane-pads only its VMEM blocks
    and slices the contraction back to ``d``), and the selection order
    matches the tiled kernel exactly (bitwise-matching outputs in interpret
    mode).
    """
    kappa = old_ids.shape[1]
    xf = x.astype(jnp.float32)
    Xf = Xsrc.astype(jnp.float32)
    ysq = jnp.sum(Xf * Xf, axis=-1)[rows]                  # (B, C)
    xsq = jnp.sum(xf * xf, axis=-1)                        # (B,)
    dots = batched_gather_dots(xf, rows.astype(jnp.int32), Xf, tile)
    cd = jnp.maximum(ysq + xsq[:, None] - 2.0 * dots, 0.0)
    return merge_lists(old_ids.astype(jnp.int32), old_d, cand_ids, cd, kappa)


def refine_merge_rowwise(x: jax.Array, rows: jax.Array, cand_ids: jax.Array,
                         old_ids: jax.Array, old_d: jax.Array,
                         Xsrc: jax.Array):
    """Pre-tiling per-row oracle (``sum((x−y)²)`` over a (B, C, d) gather) —
    kept as the bench baseline the row-tiled path must beat.  Same merge,
    different distance reduction order than ``refine_merge`` (last-ulp
    disagreement on the distances)."""
    d_pad = (-x.shape[1]) % 128
    kappa = old_ids.shape[1]
    xf = x.astype(jnp.float32)
    Y = Xsrc[rows].astype(jnp.float32)                     # (B, C, d)
    if d_pad:
        xf = jnp.pad(xf, ((0, 0), (0, d_pad)))
        Y = jnp.pad(Y, ((0, 0), (0, 0), (0, d_pad)))
    diff = Y - xf[:, None, :]
    cd = jnp.sum(diff * diff, axis=-1)                     # (B, C)
    return merge_lists(old_ids.astype(jnp.int32), old_d, cand_ids, cd, kappa)


def assign_centroids(X: jax.Array, C: jax.Array):
    """Nearest-centroid assignment.

    X: (n, d), C: (k, d) -> (assign (n,) int32, d2 (n,) float32 with the
    ||x||^2 term included).
    """
    Xf = X.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    csq = jnp.sum(Cf * Cf, axis=-1)
    part = csq[None, :] - 2.0 * jnp.matmul(Xf, Cf.T, precision=HIGHEST)
    a = jnp.argmin(part, axis=-1).astype(jnp.int32)
    d2 = jnp.min(part, axis=-1) + jnp.sum(Xf * Xf, axis=-1)
    return a, jnp.maximum(d2, 0.0)
