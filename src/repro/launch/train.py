"""Training launcher: deterministic data, checkpoint/restart, elastic mesh.

Fault tolerance (DESIGN.md §4): batches are a pure function of (seed, step),
checkpoints are atomic and carry the step + seed, so any crash/restart —
including onto a different device count — resumes bit-exactly at the step
boundary.  `--simulate-crash N` kills the process at step N to exercise this
(tests/test_checkpoint.py drives it end-to-end).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-72b --preset smoke \
      --steps 50 --ckpt-dir /tmp/ckpt [--resume]
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import token_batch
from repro.launch import sharding as shd
from repro.launch.mesh import data_mesh
from repro.models.model import init_params
from repro.train import make_train_step
from repro.train import checkpoint as ckpt
from repro.train.optimizer import make_optimizer

SMOKE = dict(n_layers=4, d_model=256, n_heads=8, n_kv_heads=4, d_ff=1024,
             vocab=2048, head_dim=32, loss_chunk=256, attn_chunk=256)
# ~100M-param example preset (examples/train_lm.py)
M100 = dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
            vocab=32768, head_dim=64, loss_chunk=512, attn_chunk=512)


def scaled_config(arch: str, preset: str):
    cfg = get_config(arch)
    if preset == "full":
        return cfg
    kw = dict(SMOKE if preset == "smoke" else M100)
    if cfg.family == "ssm":
        kw.pop("n_heads"), kw.pop("n_kv_heads"), kw.pop("d_ff")
        kw.update(ssm_state=64, ssm_head_dim=32, ssd_chunk=64)
    if cfg.family == "moe":
        kw.update(n_experts=8, experts_per_token=2,
                  moe_d_ff=kw["d_ff"] // 4)
    if cfg.family == "hybrid":
        kw.update(n_heads=8, n_kv_heads=1, lru_width=kw["d_model"],
                  window=256, n_layers=5)
    if cfg.family == "audio":
        kw.update(enc_layers=2, frontend_dim=kw["d_model"])
    if cfg.family == "vlm":
        kw.update(frontend_dim=64, n_patches=16)
    return cfg.scaled(**kw)


def make_batch_fn(cfg, batch: int, seq: int, seed: int):
    """(step -> batch) — pure, so restarts regenerate identical data."""
    def fn(step: int):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        b = token_batch(key, batch, seq, cfg.vocab)
        if cfg.family == "audio":
            b["frames"] = jax.random.normal(key, (batch, seq, cfg.d_model),
                                            jnp.bfloat16)
        if cfg.family == "vlm":
            p = cfg.n_patches
            b = {"tokens": b["tokens"][:, : seq - p],
                 "labels": b["labels"][:, : seq - p],
                 "patches": jax.random.normal(
                     key, (batch, p, cfg.frontend_dim), jnp.bfloat16)}
        return b
    return fn


def train(cfg, *, steps: int, batch: int, seq: int, seed: int = 0,
          ckpt_dir: str | None = None, ckpt_every: int = 50,
          resume: bool = False, simulate_crash: int = -1,
          log_every: int = 10):
    mesh = data_mesh()
    data_axes = ("data",)
    key = jax.random.PRNGKey(seed)

    params = init_params(cfg, key)
    opt = make_optimizer(cfg.optimizer)
    opt_state = opt.init(params)
    start = 0

    if resume and ckpt_dir and ckpt.latest_step(ckpt_dir) is not None:
        (params, opt_state), start, extra = ckpt.restore(
            ckpt_dir, (params, opt_state))
        assert extra.get("seed", seed) == seed, "seed mismatch on resume"
        print(f"[train] resumed from step {start}")

    pspecs = shd.tree_specs(params, mesh, data_axes)
    ospecs = shd.tree_specs(opt_state, mesh, data_axes)
    params = jax.device_put(params, shd.to_named(pspecs, mesh))
    opt_state = jax.device_put(opt_state, shd.to_named(ospecs, mesh))

    step_fn = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    batch_fn = make_batch_fn(cfg, batch, seq, seed)
    bspec = shd.to_named(shd.batch_specs(
        jax.eval_shape(lambda: batch_fn(0)), mesh, data_axes), mesh)

    losses = []
    t0 = time.time()
    with mesh:
        for s in range(start, steps):
            if s == simulate_crash:
                print(f"[train] simulating crash at step {s}", flush=True)
                os._exit(42)
            b = jax.device_put(batch_fn(s), bspec)
            params, opt_state, metrics = step_fn(
                params, opt_state, b, jnp.asarray(s, jnp.int32))
            if s % log_every == 0 or s == steps - 1:
                loss = float(metrics["loss"])
                losses.append((s, loss))
                print(f"[train] step {s:5d} loss {loss:.4f} "
                      f"({(time.time()-t0):.1f}s)", flush=True)
            if ckpt_dir and (s + 1) % ckpt_every == 0:
                ckpt.save(ckpt_dir, s + 1, (jax.device_get(params),
                                            jax.device_get(opt_state)),
                          extra={"seed": seed})
    if ckpt_dir:
        ckpt.save(ckpt_dir, steps, (jax.device_get(params),
                                    jax.device_get(opt_state)),
                  extra={"seed": seed})
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--preset", default="smoke",
                    choices=["smoke", "m100", "full"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--simulate-crash", type=int, default=-1)
    args = ap.parse_args()

    cfg = scaled_config(args.arch, args.preset)
    _, losses = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                      seed=args.seed, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, resume=args.resume,
                      simulate_crash=args.simulate_crash)
    if len(losses) >= 2:
        print(f"[train] loss {losses[0][1]:.4f} -> {losses[-1][1]:.4f}")


if __name__ == "__main__":
    main()
