"""Fault tolerance demo (paper future-work ii, implemented): a decode stream
is running against destination A; A dies mid-stream; the NEXT call through
the ``repro.avec`` session detects the death (failed call + failed ping
probe), fails over to destination B restoring the host-side shadow state,
and retries — the stream continues byte-identical to an uninterrupted run,
and the application never handles the re-route.

Run:  PYTHONPATH=src python examples/migration_demo.py
"""
import dataclasses
import time

import jax
import numpy as np

from repro import avec
from repro.core import DestinationExecutor
from repro.configs import get_arch, reduced
from repro.core.library import make_model_library
from repro.core.virtualization import JETSON_TX2
from repro.models import model as M
from repro.serving.engine import generate_sequential
from repro.utils import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    cfg = reduced(get_arch("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    lib = make_model_library(cfg, max_cache_len=32)
    executors = {n: DestinationExecutor({"lm": lib}, name=n)
                 for n in ("edge-a", "edge-b")}

    # one front door: both in-process executors behind calibrated edge specs;
    # shadow_every=1 snapshots the serving state after every call, so a
    # failover can restore the newest KV cache
    targets = [(dataclasses.replace(JETSON_TX2, name=n), ex)
               for n, ex in executors.items()]
    with avec.connect(targets, shadow_every=1) as client:
        sess = client.session(cfg, params, "lm", destination="edge-a")

        prompt = [5, 17, 3, 99, 42, 7]
        want = generate_sequential(cfg, params, prompt, 10, max_len=32)
        print(f"reference stream (uninterrupted): {want}")

        sess.call("prefill", {"tokens": np.asarray([prompt], np.int32)})
        got = [want[0]]
        for step in range(1, 10):
            if step == 4:
                print(">>> killing edge-a mid-stream")
                executors["edge-a"].fail = True
                t0 = time.perf_counter()
            out = sess.call("decode",
                            {"tokens": np.asarray([[got[-1]]], np.int32)})
            if step == 4:
                print(f">>> transparent failover to {sess.destination} in "
                      f"{time.perf_counter() - t0:.3f}s (state from shadow, "
                      f"weights cached="
                      f"{client.migration.migrations[-1]['cached']})")
            got.append(int(np.argmax(out["logits"][0, 0, :cfg.vocab_size])))
        print(f"stream with mid-flight failover:  {got}")
        assert got == want, "failover changed the stream!"
        assert sess.destination == "edge-b"
        print("OK: failover preserved the decode stream exactly — the "
              "application only ever called sess.call()")


if __name__ == "__main__":
    main()
