"""End-to-end driver: train a ~100M-param qwen2-family model for a few
hundred steps with checkpointing (assignment deliverable (b)).

    PYTHONPATH=src python examples/train_lm.py [--steps 300] [--arch qwen2-72b]

This uses the same config/launcher/sharding machinery as the full-size
dry-run — only the preset differs.
"""
import argparse

from repro.launch import runtime
from repro.launch.llm_cost import param_counts
from repro.launch.train import scaled_config, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    runtime.init()

    cfg = scaled_config(args.arch, "m100")
    tot, act = param_counts(cfg)
    print(f"[model] {cfg.name} (m100 preset): {tot/1e6:.0f}M params")
    _, losses = train(cfg, steps=args.steps, batch=args.batch, seq=args.seq,
                      ckpt_dir=args.ckpt_dir, ckpt_every=100, resume=True)
    print(f"[done] loss {losses[0][1]:.3f} -> {losses[-1][1]:.3f} "
          f"(checkpoints in {args.ckpt_dir})")


if __name__ == "__main__":
    main()
