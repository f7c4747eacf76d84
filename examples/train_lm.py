"""Train a small LM for a few hundred steps with checkpoint/restart.

AVEC is an inference-offload paper, so the required end-to-end driver is
``offload_serving.py``; this example exercises the training substrate
(optimizer + WSD schedule + async checkpointing + crash resume) at a size
this single-core container can push through a few hundred steps (~10M
params).  Scale ``--dim/--layers`` up on real hardware.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
"""
import argparse
import dataclasses
import os
import tempfile

from repro.configs import get_arch
from repro.data.pipeline import make_pipeline
from repro.optim.optimizer import OptimizerConfig
from repro.train.trainer import Trainer
from repro.utils import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = dataclasses.replace(
        get_arch("granite-3-2b"),
        num_layers=args.layers, d_model=args.dim, num_heads=4, num_kv_heads=2,
        head_dim=args.dim // 4, d_ff=args.dim * 4, vocab_size=args.vocab,
        remat=False, param_dtype="float32", compute_dtype="float32")
    n = cfg.param_count()
    print(f"model: {args.layers}L d={args.dim} vocab={args.vocab} "
          f"({n / 1e6:.1f}M params)")

    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                             "repro_train_lm")
    data = make_pipeline(cfg.vocab_size, seq_len=64, global_batch=16, seed=0)
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps,
                           schedule="wsd")
    trainer = Trainer(cfg, ocfg, data, ckpt_dir=ckpt_dir, ckpt_every=50)
    report = trainer.run(args.steps, resume=True)
    if report.resumed_from:
        print(f"resumed from checkpoint step {report.resumed_from}")
    k = max(len(report.losses) // 10, 1)
    for i in range(0, len(report.losses), k):
        print(f"  step {report.steps[i]:4d}  loss {report.losses[i]:.4f}")
    print(f"final loss {report.losses[-1]:.4f}  ({report.wall_s:.1f}s, "
          f"checkpoints in {ckpt_dir})")


if __name__ == "__main__":
    main()
