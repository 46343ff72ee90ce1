"""Smoke test of the clustering -> index -> search path on a TPU chip.

    python chip_smoke.py [--seed 0] [--n N] [--tau T]   # one chip
    python chip_smoke.py --chips 4                      # the sharded path

One chip: the paper's SIFT1M job (``configs.gkmeans_paper.SIFT1M``: n = 1M,
d = 128, k = 10,000, kappa = 50, xi = 64) on GMM data made from ``--seed``.
``gk_means`` builds the KNN graph and runs the engine, ``build_ivf`` and
``quantize_index(..., "pq")`` index the result, and held-out query batches
go through ``ivf.search`` with f32 and with pq lists.  Before that, each
Pallas kernel is checked against its ``kernels.ref`` oracle on a small input
on the chip itself.

``--chips 4``: the sharded graph build, then ``ShardedEngine.run`` and
``ShardedIvf.search`` on a four-chip mesh, each against its one-chip
baseline on the same data, and no other phase.

Every phase prints its wall time split into compile and run, and the
kernels its compiled program holds.  Any failed check exits non-zero.  The
last line of standard output is one JSON object naming the device.  The
script fails — it never falls back — where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro import index as ivf  # noqa: E402
from repro.configs.gkmeans_paper import SIFT1M  # noqa: E402
from repro.core import (GraphBuildConfig, engine, gk_means,  # noqa: E402
                        graph_build, two_means_tree)
from repro.core.distributed import (ShardedEngine, ShardedIvf,  # noqa: E402
                                    sharded_graph_builder)
from repro.data import gmm_blobs  # noqa: E402
from repro.kernels import (centroid_assign, ivf_scan,  # noqa: E402
                           ivf_scan_adc, ops, ref)
from repro.launch import runtime  # noqa: E402
from repro.launch.mesh import data_mesh  # noqa: E402

NQ, Q_BATCH = 1024, 256        # held-out queries, queries per search batch
TOPK, NPROBE = 10, 16
# pq exact-rerank depth.  A held-out query from an isotropic GMM blob sits
# almost equidistant from its blob's rows, so the ADC order needs a deeper
# rerank than the default 4 * TOPK (CPU reference, n = 16,384: recall@10
# 0.52 at depth 40, 0.98 at depth 100).
PQ_RERANK = 100
COMPONENTS_PER_CLUSTER = 1     # GMM components per requested cluster


class Check(Exception):
    """A failed smoke check."""


def check(ok: bool, what: str) -> None:
    print(f"[check] {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise Check(what)


# ---------------------------------------------------------------------------
# phase timing: wall time split into compile (lowering + XLA compile, from
# JAX's own monitoring events) and run (the rest, Python tracing included:
# nested jits report overlapping trace events, so tracing is not separable)
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, out: dict):
    c0, t0 = clock.seconds, time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    out[name] = {"wall_s": wall, "compile_s": comp, "run_s": wall - comp}
    print(f"[phase] {name}: wall {wall:.2f}s = compile {comp:.2f}s + run "
          f"{wall - comp:.2f}s", flush=True)


@contextlib.contextmanager
def record_calls(targets):
    """Record the arguments of jitted programs while a phase runs.

    ``targets`` maps a label to (module, attribute) of a jitted function;
    afterwards ``calls[label]`` holds the last call's (args, kwargs), so the
    phase's own compiled program can be fetched (an in-memory cache hit)
    and inspected."""
    calls, saved = {}, []
    for label, (mod, attr) in targets.items():
        fn = getattr(mod, attr)
        saved.append((mod, attr, fn))

        def rec(*a, _fn=fn, _label=label, **k):
            calls[_label] = (_fn, a, k)
            return _fn(*a, **k)
        setattr(mod, attr, rec)
    try:
        yield calls
    finally:
        for mod, attr, fn in saved:
            setattr(mod, attr, fn)


def check_kernels(calls, expect) -> None:
    """Each program's compiled HLO holds a Mosaic kernel (tpu_custom_call)
    of the expected name."""
    for label, kernel in expect.items():
        check(label in calls, f"{label}: program ran")
        fn, a, k = calls[label]
        t0 = time.perf_counter()
        txt = fn.lower(*a, **k).compile().as_text()
        ok = "tpu_custom_call" in txt and kernel in txt
        check(ok, f"{label}: compiled HLO holds tpu_custom_call {kernel!r} "
                  f"(fetched in {time.perf_counter() - t0:.2f}s)")


# ---------------------------------------------------------------------------
# the plain reference: blockwise brute-force top-k
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def brute_topk(Q, X, k: int, block: int):
    """Exact top-k ids of Q's rows among X's rows, X streamed in blocks."""
    n = X.shape[0]
    nb = -(-n // block)
    Xb = jnp.pad(X, ((0, nb * block - n), (0, 0))).reshape(nb, block, -1)
    qsq = jnp.sum(Q * Q, axis=1, keepdims=True)

    def body(carry, i):
        bd, bi = carry
        xb = Xb[i]
        d2 = (qsq + jnp.sum(xb * xb, axis=1)[None, :]
              - 2.0 * jnp.dot(Q, xb.T, precision=jax.lax.Precision.HIGHEST))
        ids = i * block + jnp.arange(block, dtype=jnp.int32)
        d2 = jnp.where(ids[None, :] < n, d2, jnp.inf)
        d = jnp.concatenate([bd, d2], axis=1)
        cand = jnp.concatenate([bi, jnp.broadcast_to(ids, d2.shape)], axis=1)
        neg, pos = jax.lax.top_k(-d, k)
        return (-neg, jnp.take_along_axis(cand, pos, axis=1)), None

    init = (jnp.full((Q.shape[0], k), jnp.inf, jnp.float32),
            jnp.full((Q.shape[0], k), -1, jnp.int32))
    (_, ids), _ = jax.lax.scan(body, init, jnp.arange(nb))
    return ids


def recall(ids, gt) -> float:
    ids, gt = np.asarray(ids), np.asarray(gt)
    return float(np.mean([len(set(a) & set(b)) / gt.shape[1]
                          for a, b in zip(ids.tolist(), gt.tolist())]))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def kernel_parity(key) -> None:
    """Each Pallas kernel against its ref.py oracle, small inputs, on chip."""
    ks = jax.random.split(key, 8)
    B, d, k, C = 1024, 128, 4096, 50
    x = jax.random.normal(ks[0], (B, d)) * 2
    u = jax.random.randint(ks[1], (B,), 0, k)
    cand = jax.random.randint(ks[2], (B, C), 0, k)
    D = jax.random.normal(ks[3], (k, d)) * 5
    cnt = jax.random.randint(ks[4], (k,), 1, 6).astype(jnp.float32)
    for mode in ("bkm", "lloyd"):
        got = np.asarray(ops.gather_score(x, u, cand, D, cnt, mode=mode))
        want = np.asarray(ref.gather_score(x, u, cand, D, cnt, mode=mode))
        err = np.max(np.abs(got - want)) / np.max(np.abs(want))
        check(err < 1e-5, f"gather_score[{mode}] vs ref: max rel err {err:.2e}")

    N, kap, Cr = 65536, 50, 136
    Xs = jax.random.normal(ks[5], (N, d)) * 3
    rows = jax.random.randint(ks[6], (B, Cr), 0, N)
    old_i = jnp.full((B, kap), -1, jnp.int32)
    old_d = jnp.full((B, kap), jnp.inf, jnp.float32)
    gi, gd = ops.refine_merge(x, rows, rows, old_i, old_d, Xs)
    ri, rd = ref.refine_merge(x, rows, rows, old_i, old_d, Xs)
    agree = float(np.mean(np.asarray(gi) == np.asarray(ri)))
    check(agree > 0.99, f"refine_merge vs ref: {agree:.4f} of ids equal")

    Xc = gmm_blobs(ks[7], 16384, d, 256)
    C_ = Xc[:256]
    pi, _ = ops.probe_centroids(Xc[:1024], C_, 8)
    qi, _ = ref.probe_centroids(Xc[:1024], C_, 8)
    agree = float(np.mean(np.asarray(pi) == np.asarray(qi)))
    check(agree > 0.99, f"probe_centroids vs ref: {agree:.4f} of ids equal")

    class Clustering:
        assign, centroids, k = ref.assign_centroids(Xc, C_)[0], C_, 256
    index = ivf.quantize_index(ivf.build_ivf(Xc, Clustering), "pq",
                               key=ks[0])
    Q = Xc[:256] + 0.05
    # the f32 scan in query tiles of 64 (4 kernel calls), as a serving batch
    # larger than one VMEM tile runs
    cids, _ = ref.probe_centroids(Q, C_, 4)
    tm = ivf.build_tile_map(cids, index.starts, index.caps,
                            max_tiles=index.max_list_tiles,
                            block_rows=index.block_rows,
                            null_tile=index.null_tile)
    scan = functools.partial(ops.ivf_scan, Q, index.vecs, index.ids, tm,
                             block_rows=index.block_rows, topk=TOPK)
    agree = float(np.mean(np.asarray(scan(tile=64)[0])
                          == np.asarray(scan(force="ref")[0])))
    check(agree > 0.99, f"ivf_scan in query tiles of 64 vs ref: {agree:.4f} "
                        f"of ids equal")
    for codec in ("f32", "pq"):
        a, _ = ivf.search(index, Q, topk=TOPK, nprobe=4, codec=codec)
        b, _ = ivf.search(index, Q, topk=TOPK, nprobe=4, codec=codec,
                          force="ref")
        agree = float(np.mean(np.asarray(a) == np.asarray(b)))
        check(agree > 0.99, f"ivf search[{codec}] kernels vs ref: "
                            f"{agree:.4f} of ids equal")


def job_size(args):
    """(n, k, tau) of the SIFT1M job after the cuts asked for, each cut
    printed.  n is cut only with k, keeping the config's n/k (the cluster
    size); d, kappa and xi are never cut."""
    cfg = SIFT1M
    n = args.n or cfg.n
    k = cfg.k if n == cfg.n else max(n * cfg.k // cfg.n, 1)
    tau = args.tau or cfg.tau
    if n != cfg.n:
        print(f"[cut] n {cfg.n} -> {n}, k {cfg.k} -> {k} (n/k kept)")
    if tau != cfg.tau:
        print(f"[cut] tau {cfg.tau} -> {tau}")
    return n, k, tau


def one_chip(args, clock, times) -> None:
    cfg = SIFT1M
    n, k, tau = job_size(args)
    print(f"[config] {cfg.name}: n={n} d={cfg.d} k={k} kappa={cfg.kappa} "
          f"xi={cfg.xi} tau={tau} seed={args.seed}", flush=True)
    key = jax.random.PRNGKey(args.seed)
    kd, kp, kc, kq = jax.random.split(key, 4)

    with phase("kernel_parity", clock, times):
        kernel_parity(kp)

    with phase("data", clock, times):
        XQ = gmm_blobs(kd, n + NQ, cfg.d, COMPONENTS_PER_CLUSTER * k)
        X, Q = XQ[:n], XQ[n:]            # queries held out of the index
        X.block_until_ready()

    with record_calls({"graph": (graph_build, "_build_single"),
                       "engine": (engine, "run")}) as calls:
        with phase("gk_means", clock, times):
            res = gk_means(X, k, kappa=cfg.kappa, xi=cfg.xi, tau=tau,
                           key=kc)
    print(f"[gk_means] total {res.seconds['total']:.2f}s")
    print(f"[gk_means] k rounded to {res.k}; {len(res.history)} epochs; "
          f"moves {res.moves}")
    print(f"[gk_means] distortion {res.distortion_init:.4f} -> "
          f"{res.distortion:.4f}")
    check(np.isfinite(res.distortion) and
          res.distortion < res.distortion_init, "distortion decreased")
    a = np.asarray(res.assign)
    sizes = np.bincount(a, minlength=res.k)
    check(a.shape == (n,) and a.min() >= 0 and a.max() < res.k
          and sizes.sum() == n,
          f"every row assigned: {n} rows in {res.k} clusters, "
          f"{int(np.sum(sizes == 0))} empty, largest {int(sizes.max())}")
    check_kernels(calls, {"graph": "refine_merge", "engine": "gather_score"})

    with phase("index_build", clock, times):
        index = ivf.build_ivf(X, res)
        index_pq = ivf.quantize_index(index, "pq", key=kq)
        index_pq.codes.block_until_ready()
    print(f"[index] {index.k} lists, {index.n_rows} packed rows, "
          f"{index.max_list_tiles} tiles max per list; pq nsub="
          f"{index_pq.codec.nsub}")

    with phase("brute_force", clock, times):
        gt = np.asarray(brute_topk(Q, X, TOPK, 65536))

    for codec, idx in (("f32", index), ("pq", index_pq)):
        targets = {"probe": (centroid_assign, "probe_centroids"),
                   "scan": ((ivf_scan, "ivf_scan") if codec == "f32"
                            else (ivf_scan_adc, "ivf_scan_adc"))}
        ids = []
        with record_calls(targets) as calls:
            for b in range(NQ // Q_BATCH):
                with phase(f"search_{codec}_batch{b}", clock, times):
                    out, _ = ivf.search(idx, Q[b * Q_BATCH:(b + 1) * Q_BATCH],
                                        topk=TOPK, nprobe=NPROBE, codec=codec,
                                        rerank=PQ_RERANK)
                    ids.append(np.asarray(out))
        r = recall(np.concatenate(ids), gt)
        times[f"recall_{codec}"] = r
        print(f"[search] {codec}: recall@{TOPK} {r:.4f} on {NQ} held-out "
              f"queries, nprobe={NPROBE}", flush=True)
        check_kernels(calls, {"probe": "probe_centroids",
                              "scan": ("ivf_scan" if codec == "f32"
                                       else "ivf_scan_adc")})
    check(times["recall_f32"] >= 0.9,
          f"f32 recall@{TOPK} {times['recall_f32']:.4f} >= 0.9")
    check(times["recall_pq"] >= times["recall_f32"] - 0.05,
          f"pq recall@{TOPK} {times['recall_pq']:.4f} within 0.05 of f32")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def four_chips(args, clock, times) -> None:
    R = 4
    cfg = SIFT1M
    n, k, tau = job_size(args)
    k2 = 1 << (k - 1).bit_length()               # gk_means' power of two
    print(f"[config] {cfg.name} on {R} chips: n={n} d={cfg.d} k={k2} "
          f"kappa={cfg.kappa} xi={cfg.xi} tau={tau} seed={args.seed}",
          flush=True)
    mesh = data_mesh(R)
    key = jax.random.PRNGKey(args.seed)
    kd, kg, ki, ke, kq = jax.random.split(key, 5)

    with phase("data", clock, times):
        XQ = gmm_blobs(kd, n + NQ, cfg.d, COMPONENTS_PER_CLUSTER * k)
        X, Q = XQ[:n], XQ[n:]
        X.block_until_ready()

    gcfg = GraphBuildConfig(kappa=cfg.kappa, xi=cfg.xi, tau=tau)
    with phase("graph_sharded", clock, times):
        g4, _ = sharded_graph_builder(mesh, gcfg).build(X, kg)
        g4.ids.block_until_ready()
    fill = float(np.mean(np.asarray(g4.ids) >= 0))
    check(fill > 0.99, f"sharded graph: {fill:.4f} of list slots filled")

    G = jnp.maximum(g4.ids, 0)
    st = engine.init_state(X, two_means_tree(X, k2, ki), k2)
    ecfg = engine.EngineConfig(batch_size=1024, iters=20, min_move_frac=1e-4)
    with phase("engine_sharded", clock, times):
        out4 = ShardedEngine(mesh, ecfg._replace(batch_size=1024 // R)).run(
            X, G, st.assign, st.D, st.cnt, ke)
        d4 = float(out4[6])
    with phase("engine_one_chip", clock, times):
        # the graph came back sharded over the mesh: the baseline's inputs
        # go to one chip (a Pallas kernel is never auto-partitioned)
        X1, G1, st1 = jax.device_put((X, G, st), jax.devices()[0])
        out1 = engine.run(X1, st1, engine.graph_source(G1), ke, ecfg)
        d1 = float(out1[4])
    print(f"[engine] final distortion: {R} chips {d4:.4f}, one chip "
          f"{d1:.4f} ({int(out4[5])} / {int(out1[3])} epochs)")
    check(abs(d4 - d1) <= 0.05 * d1,
          "sharded distortion within 5% of one chip")

    class Clustering:
        assign, k = out1[0].assign, k2
        centroids = out1[0].D / jnp.maximum(out1[0].cnt, 1.0)[:, None]
    with phase("index_build", clock, times):
        index = ivf.build_ivf(X, Clustering)
        sivf = ShardedIvf(mesh, index)
    with phase("brute_force", clock, times):
        gt = np.asarray(brute_topk(Q, X, TOPK, 65536))
    ids1, ids4 = [], []
    for b in range(NQ // Q_BATCH):
        qb = Q[b * Q_BATCH:(b + 1) * Q_BATCH]
        with phase(f"search_sharded_batch{b}", clock, times):
            ids4.append(np.asarray(sivf.search(qb, topk=TOPK,
                                               nprobe=NPROBE)[0]))
        with phase(f"search_one_chip_batch{b}", clock, times):
            ids1.append(np.asarray(ivf.search(index, qb, topk=TOPK,
                                              nprobe=NPROBE)[0]))
    r4 = recall(np.concatenate(ids4), gt)
    r1 = recall(np.concatenate(ids1), gt)
    print(f"[search] recall@{TOPK}: ShardedIvf {r4:.4f}, one chip {r1:.4f}")
    check(abs(r4 - r1) <= 0.01,
          "ShardedIvf recall@10 within 0.01 of one-chip search")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=0,
                    help="cut the row count (k follows, keeping n/k)")
    ap.add_argument("--tau", type=int, default=0,
                    help="cut the graph-build rounds")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    runtime.init()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, "
              f"{len(jax.devices())} found", file=sys.stderr)
        return 2
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    clock, times = CompileClock(), {}
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args, clock, times)
    except Check as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    print(f"[total] {time.perf_counter() - t0:.1f}s; phases "
          f"{json.dumps(times)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
