"""Kernel micro-benchmarks: hot-spot ops vs their jnp references (CPU runs
the reference path; on TPU the same harness times the Pallas kernels).

Emits ``BENCH_kernels.json`` — a ``repro.bench.v1`` run record whose
``metrics["kernels"]`` entries carry the measured microseconds AND the shape
arguments of the matching ``launch.roofline.KERNEL_INVENTORY`` entry, so
``launch/obs_report.py`` can join measured time against the analytic
flops/HBM model without re-deriving shapes.

Timing hygiene: every number comes from ``common.timed_stats`` — the first
call (jit compile + first dispatch) is timed separately as ``compile_us``
and never pollutes the reported steady-state median-of-N ``us``.

Row-tiled kernels (``gather_score``, ``refine_merge``, ``pairwise_sq``)
additionally report:

  ``tile``        the row-tile the dispatcher resolved (explicit override >
                  checked-in ``kernels/autotune_table.json`` > untiled);
  ``us_rowwise``  the legacy per-row oracle (``ref.gather_score_rowwise`` /
                  ``ref.refine_merge_rowwise``: materialised (B, C, d)
                  gather + elementwise reductions — the arithmetic the
                  per-row Pallas grid used) timed at the same shape, so the
                  tiled-vs-per-row speedup is pinned in the record.  Only
                  measured in ``--quick`` (the full-size gather is ~17 GB).

``--autotune`` sweeps each tunable kernel over ``autotune.SWEEP_TILES`` at
the bench shapes, asserts the winner is no slower than the untiled default,
and writes the winners into the checked-in table consumed by ``kernels.ops``
at dispatch.  Re-run after kernel or shape changes::

    PYTHONPATH=src python benchmarks/kernels_bench.py --autotune --quick
"""
from __future__ import annotations

import argparse

OUT_JSON = "BENCH_kernels.json"


def _cases(quick: bool):
    """Build the benchmark cases once; shared by run() and the sweep.

    Returns a list of dicts: ``kernel``, ``shape`` (KERNEL_INVENTORY arg
    order), ``make(tile)`` -> jitted zero-compile-state fn + args (tile=None
    = table dispatch), and optional ``rowwise`` () -> (fn, args) legacy
    per-row oracle at the same shape.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.graph_build import _refine_rows
    from repro.data import gmm_blobs
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)
    cases = []

    B, m, d = (256, 64, 128) if quick else (2048, 64, 512)
    Xb = gmm_blobs(key, B * m, d, 8).reshape(B, m, d)
    cases.append(dict(
        kernel="pairwise_sq", shape={"B": B, "m": m, "d": d},
        make=lambda t: (jax.jit(lambda x: ops.pairwise_sq(x, tile=t)), (Xb,)),
    ))

    n, k = (65536, 4096) if quick else (1_000_000, 10_000)
    X = gmm_blobs(key, n, d, 8)
    C = gmm_blobs(jax.random.fold_in(key, 1), k, d, 8)
    cases.append(dict(
        kernel="assign_centroids", shape={"n": n, "k": k, "d": d},
        make=lambda t: (jax.jit(lambda x, c: ops.assign_centroids(x, c)[0]),
                        (X, C)),
    ))

    # IVF coarse probe / engine probe candidates: top-p flash-argmin at the
    # serving nprobe (completes the registry — every pallas_call is benched)
    p = 8
    cases.append(dict(
        kernel="probe_centroids", shape={"n": n, "k": k, "d": d, "p": p},
        make=lambda t: (jax.jit(lambda x, c: ops.probe_centroids(x, c, p)[0]),
                        (X, C)),
    ))

    # engine move-step scoring: gather + ΔI without the (B, C, d) tensor
    Bg, Cg = (8192, 16) if quick else (65536, 50)
    kk = jax.random.fold_in(key, 2)
    xg = gmm_blobs(kk, Bg, d, 8)
    u = jax.random.randint(jax.random.fold_in(kk, 1), (Bg,), 0, k)
    cand = jax.random.randint(jax.random.fold_in(kk, 2), (Bg, Cg), 0, k)
    D = gmm_blobs(jax.random.fold_in(kk, 3), k, d, 8)
    cnt = jnp.ones((k,), jnp.float32) * 4
    cases.append(dict(
        kernel="gather_score", shape={"B": Bg, "C": Cg, "d": d},
        make=lambda t: (jax.jit(lambda *a: ops.gather_score(*a, tile=t)),
                        (xg, u, cand, D, cnt)),
        rowwise=lambda: (jax.jit(lambda *a: ref.gather_score_rowwise(*a)),
                         (xg, u, cand, D, cnt)),
    ))

    # the engine's per-batch scoring shape (engine_bench quick: bs=1024,
    # κ=16 graph candidates, d=32) — recorded separately so the engine's
    # dispatch hits an exact-shape tile instead of the nearest big-batch one
    Be, Ce, de = (1024, 16, 32) if quick else (1024, 16, 64)
    ke = jax.random.fold_in(key, 5)
    xe = gmm_blobs(ke, Be, de, 8)
    ue = jax.random.randint(jax.random.fold_in(ke, 1), (Be,), 0, k)
    ce = jax.random.randint(jax.random.fold_in(ke, 2), (Be, Ce), 0, k)
    De = gmm_blobs(jax.random.fold_in(ke, 3), k, de, 8)
    cases.append(dict(
        kernel="gather_score", shape={"B": Be, "C": Ce, "d": de},
        make=lambda t: (jax.jit(lambda *a: ops.gather_score(*a, tile=t)),
                        (xe, ue, ce, De, cnt)),
        rowwise=lambda: (jax.jit(lambda *a: ref.gather_score_rowwise(*a)),
                         (xe, ue, ce, De, cnt)),
    ))

    # graph-build refinement: fused candidate-distance + top-κ merge, timed
    # through the chunked production entry point (chunking bounds the
    # gathered working set — ~17 GB at the full sizes if materialised)
    Br, Cr, kap = (4096, 64, 16) if quick else (65536, 128, 32)
    kr = jax.random.fold_in(key, 3)
    xr = gmm_blobs(kr, Br, d, 8)
    rws = jax.random.randint(jax.random.fold_in(kr, 1), (Br, Cr), 0, n)
    gi = jnp.full((Br, kap), -1, jnp.int32)
    gd = jnp.full((Br, kap), jnp.inf, jnp.float32)

    def make_rm(t):
        if t is None:   # production path: chunked driver, table dispatch
            return (jax.jit(lambda x, rw, a, b, Xs: _refine_rows(
                x, rw, rw, a, b, Xs, 4096, None)), (xr, rws, gi, gd, X))
        return (jax.jit(lambda x, rw, a, b, Xs: ops.refine_merge(
            x, rw, rw, a, b, Xs, tile=t)), (xr, rws, gi, gd, X))

    cases.append(dict(
        kernel="refine_merge", shape={"B": Br, "C": Cr, "d": d, "kappa": kap},
        make=make_rm,
        rowwise=lambda: (jax.jit(lambda *a: ref.refine_merge_rowwise(*a)[0]),
                         (xr, rws, rws, gi, gd, X)),
    ))

    # serving scan path: synthesized packed layout at the anns_ivf_bench
    # quick shapes (n=32768, d=64, block_rows=128, nq=256, topk=10) — the
    # layout is random-but-valid so the kernel cost is isolated from the
    # index build
    ni, di, bl = (32768, 64, 128) if quick else (262144, 128, 128)
    nq, topk, T = 256, 10, 8
    ki = jax.random.fold_in(key, 4)
    vecs = gmm_blobs(ki, ni, di, 8)
    pids = jnp.arange(ni, dtype=jnp.int32)
    Q = gmm_blobs(jax.random.fold_in(ki, 1), nq, di, 8)
    tmap = jax.random.randint(jax.random.fold_in(ki, 2), (nq, T),
                              0, ni // bl).astype(jnp.int32)
    cases.append(dict(
        kernel="ivf_scan",
        shape={"q": nq, "rows": T * bl, "d": di, "topk": topk},
        make=lambda t: (jax.jit(lambda *a: ops.ivf_scan(
            *a, block_rows=bl, topk=topk, tile=t)[0]), (Q, vecs, pids, tmap)),
    ))

    # compressed-list ADC scan (pq codec: M=8 code columns, W=256 LUT) at a
    # deliberately small query batch — the reference's one-hot expansion is
    # O(chunk * bl * M * W) floats, and the sweep's tile=0 leg runs the whole
    # batch as one chunk
    from repro.index import quantize
    nqa, Ta = 64, 4
    pq = quantize.train_pq(vecs[:4096], 8, key=jax.random.fold_in(ki, 5),
                           iters=2)
    codes, vnorm = quantize.pack_codes(pq, vecs)
    lut, qconst = quantize.build_lut(pq, Q[:nqa])
    tmap_a = tmap[:nqa, :Ta]
    cases.append(dict(
        kernel="ivf_scan_adc",
        shape={"q": nqa, "rows": Ta * bl, "m": pq.nsub, "w": 256,
               "topk": topk},
        make=lambda t: (jax.jit(lambda *a: ops.ivf_scan_adc(
            *a, block_rows=bl, topk=topk, tile=t)[0]),
            (lut, qconst, vnorm, codes, pids, tmap_a)),
    ))

    # query-grouped variant: G probe-local queries share each union tile
    G, U = 8, 16
    ng = nq // G
    union = jax.random.randint(jax.random.fold_in(ki, 3), (ng, U),
                               0, ni // bl).astype(jnp.int32)
    qmask = jax.random.bernoulli(jax.random.fold_in(ki, 4), 0.5, (nq, U))
    cases.append(dict(
        kernel="ivf_scan_grouped",
        shape={"q": nq, "rows": U * bl, "d": di, "topk": topk, "G": G},
        make=lambda t: (jax.jit(lambda *a: ops.ivf_scan_grouped(
            *a, block_rows=bl, topk=topk)[0]),
            (Q, vecs, pids, union, qmask)),
    ))
    return cases


def run(quick: bool = True, entries=None):
    """Time the kernels; append structured entries to ``entries`` if given."""
    import jax

    try:
        from benchmarks.common import timed_stats
    except ImportError:       # run directly: benchmarks/ itself is sys.path
        from common import timed_stats
    from repro.kernels import autotune
    from repro.launch.roofline import KERNEL_INVENTORY

    backend = jax.default_backend()
    rows = []
    for case in _cases(quick):
        kernel, shape = case["kernel"], case["shape"]
        fn, args = case["make"](None)
        stats = timed_stats(fn, *args)
        entry = {"kernel": kernel, "us": stats["us"], "shape": dict(shape),
                 "compile_us": stats["compile_us"], "iters": stats["iters"]}
        if kernel in autotune.SWEEP_TILES:
            entry["tile"] = autotune.best_tile(kernel, backend, shape)
        if quick and "rowwise" in case:
            rfn, rargs = case["rowwise"]()
            entry["us_rowwise"] = timed_stats(rfn, *rargs)["us"]
        flops = KERNEL_INVENTORY[kernel]["flops"](*shape.values())
        dims = ",".join(f"{k}={v}" for k, v in shape.items())
        derived = f"gflops={flops / entry['us'] / 1e3:.1f}"
        if "us_rowwise" in entry:
            derived += f" vs_rowwise={entry['us_rowwise'] / entry['us']:.2f}x"
        rows.append((f"kernel/{kernel}({dims})", entry["us"], derived))
        if entries is not None:
            entries.append(entry)
    return rows


def run_autotune(quick: bool = True):
    """Sweep the tunable kernels over tile sizes; update the checked-in table.

    For each (kernel, bench shape): time every tile in
    ``autotune.SWEEP_TILES[kernel]``, assert the winner is no slower than the
    untiled default (tile=0 is always in the sweep, so this can only trip on
    timing noise — it guards against recording a regression), and record the
    winner into ``kernels/autotune_table.json``.
    """
    import jax

    try:
        from benchmarks.common import timed_stats
    except ImportError:
        from common import timed_stats
    from repro.kernels import autotune

    backend = jax.default_backend()
    entries = list(autotune.load_table())
    rows = []
    for case in _cases(quick):
        kernel, shape = case["kernel"], case["shape"]
        tiles = autotune.SWEEP_TILES.get(kernel)
        if tiles is None:
            continue
        timings = {}
        for t in tiles:
            fn, args = case["make"](t)
            timings[t] = timed_stats(fn, *args)["us"]
            dims = ",".join(f"{k}={v}" for k, v in shape.items())
            rows.append((f"sweep/{kernel}({dims})[tile={t}]", timings[t], ""))
        best = min(timings, key=timings.get)
        us_default = timings[0]   # tile=0 (untiled) is in every sweep grid
        assert timings[best] <= us_default, (
            f"{kernel}: sweep winner tile={best} ({timings[best]:.1f}us) "
            f"slower than untiled default ({us_default:.1f}us)")
        autotune.record(entries, kernel, backend, dict(shape), best,
                        timings[best], us_default)
    autotune.save(entries)
    print(f"wrote {autotune.TABLE_FILE} ({len(entries)} entries)")
    return rows


def run_and_emit(quick: bool = True):
    """Time the kernels and write the ``BENCH_kernels.json`` run record."""
    from repro.obs import run_record, write_json
    entries = []
    rows = run(quick, entries=entries)
    write_json(OUT_JSON, run_record(
        "kernels",
        shapes={"quick": quick},
        config={},
        metrics={"kernels": entries},
    ))
    return rows


def main():
    ap = argparse.ArgumentParser()
    size = ap.add_mutually_exclusive_group()
    size.add_argument("--quick", dest="quick", action="store_true",
                      default=True)
    size.add_argument("--full", dest="quick", action="store_false")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep tile sizes and update the checked-in table "
                         "instead of emitting the bench record")
    args = ap.parse_args()
    from repro.launch import runtime
    runtime.init()
    if args.autotune:
        rows = run_autotune(args.quick)
    else:
        rows = run_and_emit(args.quick)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
