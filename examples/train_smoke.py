"""End-to-end training driver: ~100M-param qwen3-style model for a few
hundred steps with checkpoints, prefetch pipeline and straggler fallback.

Run: PYTHONPATH=src python examples/train_smoke.py [--steps 200] [--small]
"""
import argparse

import repro  # noqa: F401
from repro.configs import get_config, get_reduced
from repro.configs.base import ShapeConfig
from repro.launch.cache import use_compile_cache
from repro.train.loop import train


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--small", action="store_true",
                    help="tiny config for a fast smoke run")
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    if args.small:
        cfg = get_reduced("qwen3-1.7b")
        shape = ShapeConfig("smoke", seq_len=64, global_batch=8, kind="train")
    else:
        # ~100M params: qwen3 family scaled
        cfg = get_config("qwen3-1.7b").replace(
            n_layers=8, d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
            head_dim=64, vocab_size=32000)
        shape = ShapeConfig("100m", seq_len=256, global_batch=8, kind="train")

    params, opt, out = train(cfg, shape, steps=args.steps, seed=0,
                             ckpt_dir=args.ckpt, ckpt_every=50,
                             microbatches=2, log_every=10, lr_peak=1e-3)
    h = out["history"]
    print(f"\nloss {h[0]['loss']:.3f} -> {h[-1]['loss']:.3f} over "
          f"{len(h)} steps; straggler skips: {out['straggler_skips']}")
    assert h[-1]["loss"] < h[0]["loss"], "loss should decrease"


if __name__ == "__main__":
    main()
