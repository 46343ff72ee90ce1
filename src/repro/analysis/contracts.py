"""Layer 2 — compiled-trace contract auditor for the device-resident claims.

Each entry point that carries a performance claim (PR 3/5/6) gets a
*declared contract*: the auditor compiles it at small static shapes on a
4-virtual-device CPU mesh and inspects the lowered StableHLO and the
optimized (SPMD per-partition) HLO to assert, statically:

  * **host transfers**: the trace contains NO mid-trace host callbacks /
    infeed / outfeed — every device->host byte moves at the trace boundary,
    which is exactly the "1 host sync per engine run / graph build / query
    batch" contract the runtime ``obs.syncs`` tests measure;
  * **collectives**: the while-trip-weighted collective counts (parsed with
    ``launch.roofline.collective_bytes_corrected``) equal the declared
    budget — e.g. "X all-gathered ONCE per graph build", "one all-gather
    per query batch";
  * **dtypes**: no ``f64`` anywhere; ``bf16`` only in the sparse-update
    wire-payload trace (``payload_bf16``) and never inside a dot — wire
    compression, not reduced-precision compute;
  * **telemetry**: the ``(iters, 8)``/``(iters, 4)`` accumulator slots
    appear in the optimized HLO exactly when telemetry is on (the PR 6
    zero-HLO-when-off claim);
  * **replication report**: every operand in the per-partition program
    whose leading dim is a *global* problem size (n, n_pad, k, k0, q) is a
    replicated tensor inside the shard_map body — the ROADMAP's
    "no replicated O(n·d)/O(k·d) state" metric.  Entries are compared
    EXACTLY against ``baseline.json``: a new replication fails the build,
    and fixing one forces the baseline to shrink (stale entries fail too).

The audit result is emitted as a ``repro.analysis.v1`` record
(``ANALYSIS_static.json``) via ``obs.emit`` so the replicated-state
footprint is tracked like a bench.  CLI: ``python -m repro.analysis audit``
(the ``__main__`` shim forces a 4-device host platform before jax loads).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

# problem sizes: distinct so a leading dim identifies its role in the
# replication scan (n_loc = 96 at 4 shards; d+1 = 17 stays un-confusable)
N, D, K, Q, ITERS, KAPPA, TAU = 384, 16, 40, 28, 3, 8, 2
DEVICES = 4

_CALLBACK_TOKENS = ("pure_callback", "io_callback", "debug_callback",
                    "host_callback", "infeed", "outfeed", "SendToHost",
                    "RecvFromHost")


@dataclass
class AuditResult:
    name: str
    problems: List[str] = field(default_factory=list)
    collectives: Dict[str, int] = field(default_factory=dict)
    replication: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.problems


def _collective_counts(hlo: str) -> Dict[str, int]:
    """While-trip-weighted collective op counts by kind (nonzero only)."""
    from repro.launch.roofline import collective_bytes_corrected
    stats = collective_bytes_corrected(hlo)
    return {k: int(round(v["count"])) for k, v in stats.items()
            if isinstance(v, dict) and v["count"]}


def _replication_scan(hlo: str, dim_roles: Dict[int, str],
                      min_minor: int) -> List[str]:
    """Payload-bearing replicated operands in the per-partition program.

    Flags 2D shape tokens whose LEADING dim is a global problem size (n,
    n_pad, k, k0, q — sizes that should be sharded, so their full-size
    appearance in the per-shard program means replication) and whose minor
    dim is at least the feature dim (``min_minor``) — i.e. (n, d)/(k, d)
    -class state, not scalar-per-row bookkeeping.  Dims render symbolically
    (``f32[q,d]``) so baseline entries survive audit-shape changes.
    """
    from repro.launch.roofline import _SHAPE_RE
    names = dict(dim_roles)
    names.setdefault(D, "d")
    names.setdefault(D + 1, "d+1")
    found = set()
    for dtype, dims in _SHAPE_RE.findall(hlo):
        parts = [int(x) for x in dims.split(",")] if dims else []
        if len(parts) != 2 or parts[0] not in dim_roles:
            continue
        if parts[1] < min_minor:
            continue
        sym = ",".join(names.get(p, str(p)) for p in parts)
        found.add(f"{dtype}[{sym}]")
    return sorted(found)


def audit_trace(name: str, lowered, *, collectives: Dict[str, int],
                allow_bf16: bool = False,
                require: Tuple[str, ...] = (),
                forbid: Tuple[str, ...] = (),
                dim_roles: Optional[Dict[int, str]] = None,
                host_transfer_budget: int = 0) -> AuditResult:
    """Run every static assertion for one lowered entry point."""
    res = AuditResult(name)
    stable = lowered.as_text()
    mid_trace = [t for t in _CALLBACK_TOKENS if t in stable]
    if len(mid_trace) > host_transfer_budget:
        res.problems.append(
            f"mid-trace host transfer primitives {mid_trace} exceed the "
            f"declared budget {host_transfer_budget} — breaks the "
            "one-sync-per-run contract")
    hlo = lowered.compile().as_text()
    if "f64[" in hlo:
        res.problems.append("f64 in optimized HLO (contract: no f64)")
    has_bf16 = "bf16[" in hlo
    if has_bf16 and not allow_bf16:
        res.problems.append("bf16 in optimized HLO outside a declared "
                            "payload path")
    if allow_bf16:
        if not has_bf16:
            res.problems.append("declared bf16 payload path compiled to "
                                "no bf16 at all (claim is stale)")
        dots_bf16 = [ln.strip()[:120] for ln in hlo.splitlines()
                     if ("dot(" in ln or "dot-" in ln) and "bf16[" in ln]
        if dots_bf16:
            res.problems.append(
                f"bf16 inside dot ops {dots_bf16[:2]} — payload_bf16 is "
                "wire compression only, compute must stay f32")
    res.collectives = _collective_counts(hlo)
    if res.collectives != collectives:
        res.problems.append(
            f"collective counts {res.collectives} != declared budget "
            f"{collectives}")
    for tok in require:
        if tok not in hlo:
            res.problems.append(f"required HLO token missing: {tok!r}")
    for tok in forbid:
        if tok in hlo:
            res.problems.append(f"forbidden HLO token present: {tok!r}")
    if dim_roles:
        res.replication = [f"{name}: {e}" for e in
                           _replication_scan(hlo, dim_roles, min_minor=D)]
    return res


# --------------------------------------------------------------------------
# the declared contracts
# --------------------------------------------------------------------------


def _data(key, n, d, k):
    import jax
    import jax.numpy as jnp

    from repro.data import gmm_blobs
    X = gmm_blobs(key, n, d, 8)
    G = jax.random.randint(jax.random.fold_in(key, 1), (n, KAPPA), 0, n,
                           dtype=jnp.int32)
    assign = jax.random.randint(jax.random.fold_in(key, 2), (n,), 0, k,
                                dtype=jnp.int32)
    return X, G, assign


def contract_engine_run() -> List[AuditResult]:
    """engine.run (single device): no collectives, no f64/bf16, telemetry
    slots in the HLO iff cfg.telemetry — the PR 3/6 single-device claims."""
    import jax

    from repro.core import engine
    from repro.obs import telemetry as obs_tel
    key = jax.random.PRNGKey(0)
    X, G, assign = _data(key, N, D, K)
    state = engine.init_state(X, assign, K)
    src = engine.graph_source(G)
    slots = (f"s32[{ITERS},{obs_tel.N_I32}]", f"f32[{ITERS},{obs_tel.N_F32}]")
    out = []
    for tel in (False, True):
        cfg = engine.EngineConfig(batch_size=96, iters=ITERS, telemetry=tel)
        low = engine.run.lower(X, state, src, key, cfg)
        out.append(audit_trace(
            f"engine.run[telemetry={'on' if tel else 'off'}]", low,
            collectives={},
            require=slots if tel else (),
            forbid=() if tel else slots))
    return out


def contract_engine_sharded() -> List[AuditResult]:
    """ShardedEngine.run at 4 shards: the whole epoch loop in ONE trace with
    the declared collective budget (PR 3), plus the payload_bf16 variant
    (bf16 on the sparse-update wire only)."""
    import jax
    import jax.numpy as jnp

    from repro.core.distributed import ShardedEngine
    from repro.core.engine import EngineConfig
    key = jax.random.PRNGKey(0)
    X, G, assign = _data(key, N, D, K)
    D0 = jnp.zeros((K, D), jnp.float32)
    cnt = jnp.zeros((K,), jnp.float32)
    from repro.launch.mesh import data_mesh
    mesh = data_mesh(DEVICES)
    nb = N // DEVICES // 96          # per-shard batches per epoch
    roles = {N: "n", K: "k"}
    out = []

    # dense moves with the CLUSTER-SHARDED D: the (k, d) stats live as
    # per-shard (k_loc, d) blocks, so the graph lookup costs the s32[n]
    # assignment all-gather per epoch, and each batch pays the bounded
    # candidate-row exchange (gathered candidate ids + (rows, d+1)
    # composite payload) instead of a replicated f32[k,d] psum.
    cfg = EngineConfig(batch_size=96, iters=ITERS)
    se = ShardedEngine(mesh, cfg, kind="graph")
    low = se._run.lower(*se._pad(K, X, G, assign)[:3], D0, cnt, key,
                        *se._pad(K, X, G, assign)[3:])
    out.append(audit_trace(
        "sharded_run_body[dense]", low,
        collectives=_ENGINE_DENSE_BUDGET,
        dim_roles=roles))

    # sparse moves + bf16 wire payload: per batch 3 extra index all-gathers
    # (gx/gu/gv, each s32[n]) plus the gathered X-rows payload as bf16
    # (u16[n,d] on the wire); the dense stats psums collapse to the single
    # s32[] moves counter per epoch.
    cfgs = EngineConfig(batch_size=96, iters=ITERS, sparse_updates=True,
                        payload_bf16=True)
    ses = ShardedEngine(mesh, cfgs, kind="graph")
    lows = ses._run.lower(*ses._pad(K, X, G, assign)[:3], D0, cnt, key,
                          *ses._pad(K, X, G, assign)[3:])
    out.append(audit_trace(
        "sharded_run_body[sparse,bf16]", lows,
        collectives=_ENGINE_SPARSE_BUDGET,
        allow_bf16=True,
        dim_roles=roles))
    return out


def contract_graph_build() -> List[AuditResult]:
    """GraphBuilder.build at 4 shards: X all-gathered ONCE per build, the
    tau-round loop in one trace (PR 4) — the 2M tree runs the distributed
    histogram-median bisection and the member table is built shard-locally,
    so no (k0, d)/(k0, cap) replicated state remains for the report to
    pin."""
    import jax

    from repro.core.distributed import sharded_graph_builder
    from repro.core.graph_build import GraphBuildConfig, _plan
    key = jax.random.PRNGKey(0)
    X, _, _ = _data(key, N, D, K)
    cfg = GraphBuildConfig(kappa=KAPPA, tau=TAU, chunk=96)
    k0, n_pad = _plan(N, cfg)
    from repro.launch.mesh import data_mesh
    mesh = data_mesh(DEVICES)
    gb = sharded_graph_builder(mesh, cfg)
    low = gb._make_program(N).lower(X, key)
    roles = {N: "n", K: "k"}
    if n_pad != N:
        roles[n_pad] = "n_pad"
    roles.setdefault(k0, "k0")
    return [audit_trace(
        "GraphBuilder.build[partition]", low,
        collectives=_GRAPH_BUILD_BUDGET,
        dim_roles=roles)]


def contract_ivf_search() -> List[AuditResult]:
    """ShardedIvf.search at 4 shards: ONE cross-shard merge point per query
    batch — the coarse probe exchanges per-shard owned-cell rankings and
    the scan merge exchanges per-shard candidate ids + raw distances, all
    on that single sync (PR 5); telemetry adds the two scan-counter psums
    on the same sync (PR 6).  The coarse quantizer is sharded by cell owner
    (cslab/ccid slabs), so no replicated f32[k, d] centroid matrix remains
    — queries stay replicated (they are the broadcast work).

    The codec'd search (pq / int8 compressed slabs through `ivf_scan_adc` +
    per-shard exact rerank) must keep the IDENTICAL collective schedule:
    the LUT is built replicated from the replicated queries, codes stay
    sharded, and only the post-rerank (q, topk) locals cross shards — same
    two all-gathers, no new collectives (PR 9)."""
    import jax

    from repro import index as ivf
    from repro.core.distributed import ShardedIvf
    from repro.data import gmm_blobs
    from repro.kernels import ref

    class _Result:
        def __init__(self, assign, centroids, k):
            self.assign, self.centroids, self.k = assign, centroids, k

    key = jax.random.PRNGKey(0)
    X = gmm_blobs(key, N, D, 8)
    C = gmm_blobs(jax.random.fold_in(key, 1), K, D, 8)
    a, _ = ref.assign_centroids(X, C)
    index = ivf.build_ivf(X, _Result(a, C, K), block_rows=16)
    from repro.launch.mesh import data_mesh
    mesh = data_mesh(DEVICES)
    sivf = ShardedIvf(mesh, index)
    Qr = X[:Q]
    p = sivf.parts
    roles = {N: "n", K: "k", Q: "q"}
    out = []
    for tel, coll in ((False, _IVF_BUDGET),
                      (True, {**_IVF_BUDGET,
                              "all-reduce": _IVF_BUDGET.get("all-reduce", 0)
                              + 2})):
        coll = {k_: v for k_, v in coll.items() if v}
        prog = sivf._prog(10, 4, None, tel, "f32", None)
        low = prog.lower(Qr, p.vecs, p.ids, p.starts, p.caps, sivf.cslab,
                         sivf.ccid)
        out.append(audit_trace(
            f"ShardedIvf.search[telemetry={'on' if tel else 'off'}]", low,
            collectives=coll, dim_roles=roles))

    # codec'd variants: pq nsub=4 (dsub = D/4) and int8, rerank tail on —
    # the compressed scan + per-shard rerank must not add collectives
    for kind, kw in (("pq", {"nsub": 4}), ("int8", {})):
        qix = ivf.quantize_index(index, kind, key=jax.random.fold_in(key, 2),
                                 **kw)
        sq = ShardedIvf(mesh, qix)
        pc = sq.parts
        prog = sq._prog(10, 4, None, False, kind, None)
        low = prog.lower(Qr, pc.vecs, pc.ids, pc.starts, pc.caps,
                         sq.cslab, sq.ccid, pc.codes, pc.vnorm, sq.codec)
        out.append(audit_trace(
            f"ShardedIvf.search[codec={kind}]", low,
            collectives=_IVF_BUDGET, dim_roles=roles))
    return out


# Declared collective budgets (while-trip-weighted).  A mismatch means the
# communication pattern changed — re-derive each term from the trace
# decomposition, don't just bump the number.

_NB = N // DEVICES // 96     # per-shard batches per epoch at the audit shapes

# Dense moves over the CLUSTER-SHARDED D (no replicated f32[k,d] anywhere):
# per epoch one s32[n] assignment all-gather (graph lookup) and per batch
# one s32[n, kappa+1] candidate-cluster-id all-gather; all-reduces are the
# 2 pre-loop scalar psums (n, ||x||^2 totals), per batch the candidate-row
# payload psum (rows, kappa+1, d) + two f32[k] count/weight partials + the
# transposed f32[d, k] centroid-sum psum, per epoch the s32[] moves counter
# + the distortion psum, plus the final distortion psum after the loop.
_ENGINE_DENSE_BUDGET: Dict[str, int] = {
    "all-gather": ITERS * (1 + _NB * 1),
    "all-reduce": 2 + ITERS * (_NB * 4 + 2) + 1,
}

# Sparse moves + bf16 wire: the per-batch exchange adds 2 index all-gathers
# and the u16[n, d] row payload on top of the candidate-id gather; the
# dense per-batch stats psums collapse to the single candidate-row payload
# psum (scatter updates stay local), keeping the moves + distortion psums
# per epoch and the same 2+1 pre/post scalars.
_ENGINE_SPARSE_BUDGET: Dict[str, int] = {
    "all-gather": ITERS * (1 + _NB * 4),
    "all-reduce": 2 + ITERS * (_NB * 1 + 2) + 1,
}

# ShardedIvf.search: the coarse probe exchanges per-shard owned-cell
# rankings (top-min(nprobe, k_slab) distances + ids in the (L, q) layout —
# 2 all-gathers) and the scan result merges per-shard candidate ids +
# distances on the same sync (2 more).  Telemetry adds its 2 scan-counter
# psums; the codec'd scans must keep this schedule unchanged.
_IVF_BUDGET: Dict[str, int] = {"all-gather": 4}

# GraphBuilder.build at the audit shapes: k0 = 8 -> _LEVELS = 3 bisection
# levels, _REFINE = 4 exact-median refine iterations per level
# (two_means_dist defaults).  all-gathers: X ONCE per build (the PR 4
# claim); the guided pass — a lax.cond branch, so the parser counts its ops
# once, matching the round-0 skip — pays the s32[n_pad] assignment + 2
# sparse index gathers + the s32[n_pad, kappa+1] candidate ids + one
# (R, d, k0) guided-stats segment-sum partial (5); the tree pays one
# (R, d, k0) tot_T segment-sum partial per level plus one s1_T partial per
# refine iteration; the member table pays the (cap, k0) table + spill-list
# gathers per round.
# all-reduces: per level per round 1 cntc seg-psum + 4 seed pmins + 2
# (d, k0) gathered seed-row psums + _REFINE * (8 radix histogram psums + 1 n1
# seg-psum) + 8 final-split radix psums; the guided branch pays its
# candidate-row payload psum + k0-counts psum + moves psum (3); the member
# table 1 overflow psum per round.  collective-permute: the 2 (chunk,
# kappa) candidate-ring rotations (f32 distances + s32 ids).
_LEVELS, _REFINE = 3, 4
_GRAPH_BUILD_BUDGET: Dict[str, int] = {
    "all-gather": 1 + 5 + TAU * (_LEVELS * (1 + _REFINE) + 2),
    "all-reduce": (TAU * _LEVELS * (1 + 4 + 2 + _REFINE * 9 + 8)
                   + 3 + TAU * 1),
    "collective-permute": 2,
}

CONTRACTS: Dict[str, Callable[[], List[AuditResult]]] = {
    "engine_run": contract_engine_run,
    "engine_sharded": contract_engine_sharded,
    "graph_build": contract_graph_build,
    "ivf_search": contract_ivf_search,
}


def run_audit(names: Optional[List[str]] = None) -> List[AuditResult]:
    results: List[AuditResult] = []
    for name, fn in CONTRACTS.items():
        if names and name not in names:
            continue
        try:
            results.extend(fn())
        except Exception as e:        # a contract that cannot compile fails
            results.append(AuditResult(
                name, problems=[f"contract raised: {type(e).__name__}: {e}"]))
    return results


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    import jax

    from repro.analysis import baseline as bl
    from repro.obs import emit

    ap = argparse.ArgumentParser(
        description="compiled-trace contract auditor (repro.analysis "
                    "layer 2)")
    ap.add_argument("--baseline", default=None,
                    help="baseline JSON (default: the checked-in one)")
    ap.add_argument("--out", default="ANALYSIS_static.json",
                    help="repro.analysis.v1 report path ('' disables)")
    ap.add_argument("--contract", nargs="*", default=None,
                    help="subset of contracts to audit")
    args = ap.parse_args(argv)

    if jax.device_count() < DEVICES:
        print(f"audit: need {DEVICES} devices, have {jax.device_count()} "
              "(run via `python -m repro.analysis audit`, which forces a "
              "4-device host platform)")
        return 2

    results = run_audit(args.contract)
    replication = sorted({e for r in results for e in r.replication})
    failures = 0
    for r in results:
        status = "ok" if r.ok else "FAIL"
        print(f"audit: {r.name}: {status} collectives={r.collectives}")
        for p in r.problems:
            print(f"  - {p}")
        failures += not r.ok
    print("audit: replication report (per-partition operands with a global "
          "leading dim):")
    for e in replication:
        print(f"  {e}")

    base = bl.load(args.baseline)
    problems = bl.compare(replication, base.get("replication", []),
                          section="replication")
    for p in problems:
        print(p)

    if args.out:
        rec = emit.run_record(
            "analysis_static",
            schema=emit.ANALYSIS_SCHEMA,
            shapes={"n": N, "d": D, "k": K, "q": Q, "iters": ITERS,
                    "kappa": KAPPA, "tau": TAU, "devices": DEVICES},
            config={"contracts": sorted(CONTRACTS)},
            metrics={
                "contracts_audited": len(results),
                "contracts_failed": failures,
                "replication_entries": len(replication),
                "replication_baseline": len(base.get("replication", [])),
                "collectives": {r.name: r.collectives for r in results},
                "replication": replication,
                "problems": [p for r in results for p in r.problems],
            })
        emit.write_json(args.out, rec)
        print(f"audit: wrote {args.out}")

    if failures or problems:
        print("audit: FAIL")
        return 1
    print("audit: OK")
    return 0
