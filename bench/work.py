"""The algorithm's work per kernel, counted from a cell's shapes.

Operations and bytes are what the algorithm needs for the call, not what a
kernel happens to move: no padded tiles, no lane padding, no DMA
descriptors.  A change that replaces a kernel, or removes padding, is
judged against the same work.  Operations count one multiply and one add
per term of a distance dot (2·d per pair); bytes count each f32 operand
row read once per use, the candidate ids read and the results written.

Every function returns ``(flops, bytes)`` for one call of the named work.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def refine(rows: int, cands: int, d: int, kappa: int):
    """Refine step: each of ``rows`` rows scores ``cands`` candidate rows
    and merges them into its κ-list (``kernels/refine_merge.py``)."""
    flops = 2.0 * rows * cands * d
    nbytes = (rows * cands * d * F32          # gathered candidate rows
              + rows * d * F32                # the row itself
              + rows * cands * 2 * I32        # candidate row and id
              + 2 * rows * kappa * (I32 + F32))  # list read and written
    return flops, float(nbytes)


def graph_build(n: int, d: int, kappa: int, tau: int, cap: int, spill: int):
    """One build: the random init refine (κ candidates per row) and τ
    rounds of the partition refine (cap + spill candidates per row)."""
    f0, b0 = refine(n, kappa, d, kappa)
    f1, b1 = refine(n, cap + spill, d, kappa)
    return f0 + tau * f1, b0 + tau * b1


def engine_scoring(rows: int, cands: int, d: int):
    """Engine scoring: each row against its own cluster and ``cands``
    candidate clusters, composite rows gathered
    (``kernels/gather_score.py``): rows × (C + 1) × d."""
    flops = 2.0 * rows * (cands + 1) * d
    nbytes = (rows * (cands + 1) * d * F32    # gathered composite rows
              + rows * d * F32                # the row itself
              + rows * (cands + 1) * 2 * F32  # counts and norms per slot
              + rows * cands * (I32 + F32))   # candidate ids in, scores out
    return flops, float(nbytes)


def ivf_scan(scanned_rows: int, queries: int, d: int, topk: int):
    """f32 list scan: every live row of every probed list against its
    query (``kernels/ivf_scan.py``)."""
    flops = 2.0 * scanned_rows * d
    nbytes = (scanned_rows * (d * F32 + I32)  # rows and their ids
              + queries * d * F32              # the queries
              + queries * topk * (I32 + F32))  # results
    return flops, float(nbytes)


def ivf_scan_adc(scanned_rows: int, queries: int, nsub: int, width: int,
                 depth: int):
    """ADC scan: nsub code bytes per live probed row plus a (nsub, width)
    f32 LUT per query (``kernels/ivf_scan_adc.py``)."""
    flops = 2.0 * scanned_rows * nsub
    nbytes = (scanned_rows * (nsub + F32 + I32)   # codes, norm, id
              + queries * nsub * width * F32       # LUTs
              + queries * depth * (2 * I32 + F32))  # results
    return flops, float(nbytes)


def roofline_share(flops: float, nbytes: float, seconds: float, peaks):
    """(share in %, bound) — the least time the chip could take over the
    measured time; the bound is whichever peak sets that least time."""
    t_flops = flops / peaks.flops
    t_bytes = nbytes / peaks.hbm_bw
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
