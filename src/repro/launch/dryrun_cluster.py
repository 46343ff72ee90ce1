import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Dry-run for the PAPER'S OWN workload: one distributed engine epoch at
VLAD10M scale (10M x 512-d -> 1M clusters) on the production meshes, in both
statistic-update modes (dense psum vs sparse all-gather — §Perf) and both
move rules (bkm ΔI / lloyd nearest-candidate — the engine's mode matrix).

  PYTHONPATH=src python -m repro.launch.dryrun_cluster \
      [--workload vlad10m|sift1m] [--mode dense|sparse|both] [--mesh both] \
      [--cluster-mode bkm|lloyd|both]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.distributed import ShardedEngine  # noqa: E402
from repro.core.engine import EngineConfig  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import (PRODUCTION_KIND, data_axes_of,  # noqa: E402
                               make_production_mesh)

WORKLOADS = {
    # n is padded to a 512-device multiple; k, kappa, xi follow the paper
    "vlad10m": dict(n=10_485_760, d=512, k=1 << 20, kappa=50, batch=4096),
    "sift1m": dict(n=1_048_576, d=128, k=16_384, kappa=50, batch=4096),
}


def run_cell(workload: str, mode: str, multi_pod: bool,
             cluster_mode: str = "bkm") -> dict:
    w = WORKLOADS[workload]
    mesh = make_production_mesh(multi_pod=multi_pod)
    # the clustering workload keeps (D, cnt) replicated, so there is no
    # "model" role: rows shard over EVERY mesh axis (§Perf iteration C2 —
    # sharding rows over data only left 16x redundant compute per replica)
    data_axes = (tuple(mesh.axis_names) if mode in ("sparse", "sparse_bf16")
                 else data_axes_of(mesh))
    rec = {"workload": workload, "mode": mode, "cluster_mode": cluster_mode,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    try:
        cfg = EngineConfig(batch_size=w["batch"], mode=cluster_mode,
                           sparse_updates=mode.startswith("sparse"),
                           payload_bf16=(mode == "sparse_bf16"))
        epoch = ShardedEngine(mesh, cfg, data_axes=data_axes).epoch
        row = NamedSharding(mesh, P(data_axes))
        rep = NamedSharding(mesh, P())
        n, d, k, kappa = w["n"], w["d"], w["k"], w["kappa"]
        args = (
            jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=row),
            jax.ShapeDtypeStruct((n, kappa), jnp.int32, sharding=row),
            jax.ShapeDtypeStruct((n,), jnp.int32, sharding=row),
            jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((k,), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        )
        t0 = time.time()
        with mesh:
            lowered = epoch.lower(*args)
            compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 1)
        txt = compiled.as_text()
        coll = rl.collective_bytes_corrected(txt)
        coll_raw = rl.collective_bytes(txt)
        cost = rl.cost_analysis(compiled)
        mem = compiled.memory_analysis()
        # analytic per-chip flops for one epoch: n_loc samples x kappa cands
        import numpy as _np
        shards = int(_np.prod([mesh.shape[a] for a in data_axes]))
        n_loc = n // shards
        fl = 4.0 * n_loc * kappa * d  # dots + norms of gathered candidates
        hb = (n_loc * d * 4                     # local X read
              + k * d * 4                        # D resident read per batch
              * (n_loc / w["batch"]) * (2 if mode == "dense" else 1)
              + n_loc * kappa * d * 4)           # candidate gather traffic
        rec["status"] = "ok"
        rec["flops_analytic"] = fl
        rec["hbm_bytes_analytic"] = hb
        rec["flops_hlo_raw"] = cost.get("flops", 0.0)
        rec["collectives"] = coll
        rec["collectives_raw"] = coll_raw
        rec["memory"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": rl.peak_memory_bytes(mem),
        }
        rec["roofline"] = rl.roofline_terms(fl, hb,
                                            coll["total_wire_bytes"],
                                            device_kind=PRODUCTION_KIND)
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-1500:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="both")
    ap.add_argument("--mode", default="both")
    ap.add_argument("--mesh", default="both")
    ap.add_argument("--cluster-mode", default="bkm",
                    choices=["bkm", "lloyd", "both"])
    ap.add_argument("--out", default="results/dryrun_cluster.json")
    args = ap.parse_args()
    wl = list(WORKLOADS) if args.workload == "both" else [args.workload]
    modes = (["dense", "sparse", "sparse_bf16"] if args.mode == "both"
             else [args.mode])
    cmodes = (["bkm", "lloyd"] if args.cluster_mode == "both"
              else [args.cluster_mode])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    results = []
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for w in wl:
        for m in modes:
            for cm in cmodes:
                for mp in meshes:
                    print(f"[cluster-dryrun] {w}/{m}/{cm}/"
                          f"{'2x16x16' if mp else '16x16'} ...", flush=True)
                    rec = run_cell(w, m, mp, cm)
                    wire = rec.get("collectives", {}).get(
                        "total_wire_bytes", 0)
                    print(f"  -> {rec['status']} "
                          f"compile={rec.get('compile_s')}s "
                          f"wire={wire/1e9:.2f}GB "
                          f"dom={rec.get('roofline', {}).get('bottleneck')}",
                          flush=True)
                    results.append(rec)
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
    bad = sum(r["status"] != "ok" for r in results)
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
