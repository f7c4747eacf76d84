"""Quickstart: build a model from the assigned-architecture registry, train a
few steps on the synthetic pipeline, then serve a couple of requests THROUGH
the AVEC front door — an in-process destination executor behind
``avec.connect``, exactly the same call path a remote TCP destination uses.

Run:  PYTHONPATH=src python examples/quickstart.py [--arch granite-3-2b]
"""
import argparse
import sys

import jax
import numpy as np

from repro import avec
from repro.configs import get_arch, list_archs, reduced
from repro.core import DestinationExecutor
from repro.core.library import make_model_library
from repro.data.pipeline import make_pipeline
from repro.optim.optimizer import OptimizerConfig
from repro.train.trainer import Trainer
from repro.utils import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()
    enable_compile_cache()

    # reduced() preserves the family (GQA/MoE/SSD/hybrid/...) at CPU scale
    cfg = reduced(get_arch(args.arch))
    print(f"arch={args.arch} family={cfg.family} "
          f"(full config: {get_arch(args.arch).param_count() / 1e9:.1f}B params)")

    data = make_pipeline(cfg.vocab_size, seq_len=32, global_batch=8, seed=0)
    ocfg = OptimizerConfig(name=cfg.optimizer, lr=3e-3, warmup_steps=5,
                           total_steps=args.steps, schedule="wsd")
    trainer = Trainer(cfg, ocfg, data)
    report = trainer.run(args.steps)
    print(f"train: loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f} "
          f"in {report.wall_s:.1f}s")

    if cfg.family in ("encdec",):
        print("serving demo targets decoder LMs; done.")
        return
    params = trainer._final["params"]

    # serve through the facade: connect -> session -> call.  Swapping the
    # in-process executor for "tcp://host:port" is the ONLY change needed
    # to serve from a real edge/cloud destination.
    ex = DestinationExecutor({"lm": make_model_library(cfg, max_cache_len=64)},
                             name="local-dest")
    with avec.connect([ex]) as client:
        sess = client.session(cfg, params, "lm")
        rng = np.random.default_rng(0)
        for i in range(3):
            prompt = rng.integers(0, cfg.vocab_size, 6)[None].astype(np.int32)
            out = sess.call("prefill", {"tokens": prompt})
            toks = [int(np.argmax(out["logits"][0, -1, :cfg.vocab_size]))]
            for _ in range(7):
                out = sess.call("decode", {"tokens": np.asarray(
                    [[toks[-1]]], np.int32)})
                toks.append(int(np.argmax(out["logits"][0, 0,
                                                        :cfg.vocab_size])))
            print(f"serve: req{i} -> {toks}")
        b = sess.profiler.breakdown()
        print(f"profiled {b['cycles']} offload cycles via "
              f"{sess.destination} (GPU {b['gpu_frac'] * 100:.0f}% / "
              f"comm {b['communication_frac'] * 100:.0f}%)")


if __name__ == "__main__":
    sys.exit(main())
