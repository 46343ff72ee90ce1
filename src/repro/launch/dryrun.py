import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell with 512 placeholder devices; record memory/cost analysis + collective
bytes for the roofline table.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun \
      [--arch qwen2-72b|all] [--shape train_4k|all] [--mesh single|multi|both]
      [--out results/dryrun.json] [--skip-done]
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.configs import SHAPES, get_config, list_archs  # noqa: E402
from repro.launch import llm_cost as lc  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch.mesh import PRODUCTION_KIND, make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs  # noqa: E402
from repro.train import make_decode_step, make_prefill, make_train_step  # noqa: E402


def lower_cell(cfg, shape, mesh):
    """Lower one cell. Returns (lowered, out_shardings_desc)."""
    sp = input_specs(cfg, shape, mesh)
    if shape.kind == "train":
        step_fn = make_train_step(cfg)
        fn = jax.jit(step_fn, donate_argnums=(0, 1))
        with mesh:
            lowered = fn.lower(sp["params"], sp["opt_state"], sp["batch"],
                               sp["step"])
        return lowered
    if shape.kind == "prefill":
        fn = jax.jit(make_prefill(cfg, cache_len=shape.seq_len))
        with mesh:
            lowered = fn.lower(sp["params"], sp["batch"])
        return lowered
    fn = jax.jit(make_decode_step(cfg), donate_argnums=(2,),
                 static_argnums=())
    with mesh:
        lowered = fn.lower(sp["params"], sp["tokens"], sp["cache"], None)
    return lowered


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: dict | None = None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.scaled(**overrides)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "kind": shape.kind, "overrides": overrides or {}}
    if not cfg.supports(shape):
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md §5)")
        return rec
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        lowered = lower_cell(cfg, shape, mesh)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        mem = compiled.memory_analysis()
        cost = rl.cost_analysis(compiled)
        txt = compiled.as_text()
        coll_raw = rl.collective_bytes(txt)
        coll = rl.collective_bytes_corrected(txt)
        rec["status"] = "ok"
        rec["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": rl.peak_memory_bytes(mem),
        }
        # raw HLO cost analysis (while bodies counted ONCE — see roofline.py)
        rec["flops_hlo_raw"] = cost.get("flops", 0.0) if cost else 0.0
        rec["hbm_bytes_hlo_raw"] = (cost.get("bytes accessed", 0.0)
                                    if cost else 0.0)
        rec["collectives_raw"] = coll_raw
        rec["collectives"] = coll  # while-trip-count corrected
        chips = 512 if multi_pod else 256
        # analytic (exact matmul count / modeled traffic) per-chip terms
        fl = lc.flops_analytic(cfg, shape, chips)
        hb = lc.hbm_analytic(cfg, shape, chips)
        rec["flops_analytic"] = fl
        rec["hbm_bytes_analytic"] = hb
        terms = rl.roofline_terms(fl, hb, coll["total_wire_bytes"],
                                  device_kind=PRODUCTION_KIND)
        mf = lc.model_flops(cfg, shape)
        terms["model_flops_total"] = mf
        terms["model_flops_per_chip"] = mf / chips
        terms["useful_ratio"] = (mf / chips / fl) if fl else None
        rec["roofline"] = terms
        terms_raw = rl.roofline_terms(rec["flops_hlo_raw"],
                                      rec["hbm_bytes_hlo_raw"],
                                      coll_raw["total_wire_bytes"],
                                      device_kind=PRODUCTION_KIND)
        rec["roofline_hlo_raw"] = terms_raw
    except Exception as e:  # noqa: BLE001 — a failed cell is a recorded bug
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--override", action="append", default=[],
                    help="config override key=value (repeatable) — used by "
                         "the §Perf hillclimb variants")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            v = eval(v)  # noqa: S307 — trusted CLI input (ints/bools/strs)
        except Exception:
            pass
        overrides[k] = v

    assert len(jax.devices()) == 512, (
        "dry-run needs 512 placeholder devices; do not import jax before "
        "this module sets XLA_FLAGS")

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") in ("ok", "skipped")}

    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                key = (arch, shape, "2x16x16" if mp else "16x16")
                if key in done:
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                rec = run_cell(arch, shape, mp, overrides)
                print(f"[dryrun] {key} -> {rec['status']} "
                      f"(lower {rec.get('lower_s', '-')}s, compile "
                      f"{rec.get('compile_s', '-')}s, "
                      f"bottleneck {rec.get('roofline', {}).get('bottleneck', '-')})",
                      flush=True)
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"]) != key]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)

    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {ok} ok, {sk} skipped, {err} errors")
    return 1 if err else 0


if __name__ == "__main__":
    raise SystemExit(main())
