"""End-to-end driver (the paper's kind: inference offload serving), wired
entirely through the ``repro.avec`` facade — the one front door.

Topology, all real processes-and-sockets on this host:

  [host client]  --TCP-->  [destination A: "edge" executor]
                 --TCP-->  [destination B: "cloud" executor]

``avec.connect`` handshakes both destinations (protocol version, codecs,
pipelining, coalescing), the device-aware scheduler picks one per the
calibrated cost model, weights are transferred once (send-once cache),
batched requests stream through prefill/decode, a stateless ``score`` batch
is sharded across BOTH destinations via ``session.map``, and the profiler
prints the paper's GPU/communication/other cycle breakdown (Figs. 8-9
analogue) plus FPS (Table V analogue).

Run:  PYTHONPATH=src python examples/offload_serving.py
"""
import dataclasses
import time

import jax
import numpy as np

from repro import avec
from repro.configs import get_arch, reduced
from repro.core import DestinationExecutor
from repro.core.costmodel import Workload
from repro.core.library import make_model_library
from repro.core.transport import TCPServer
from repro.core.virtualization import CLOUD_RTX, JETSON_TX2
from repro.utils import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    cfg = reduced(get_arch("granite-3-2b"))
    from repro.models import model as M
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    lib = make_model_library(cfg, max_cache_len=64)

    # two live destinations behind real TCP servers
    specs = {"edge-a": JETSON_TX2, "cloud-b": CLOUD_RTX}
    servers, targets = {}, []
    for name, spec in specs.items():
        ex = DestinationExecutor({"lm": lib}, name=name)
        srv = TCPServer(ex.handle).start()
        servers[name] = srv
        targets.append((dataclasses.replace(spec, name=name),
                        f"tcp://127.0.0.1:{srv.port}"))

    # one front door: handshake + scheduler + runtime tier in one call
    # (state shadowing off: this demo measures the paper's cycle breakdown,
    # and per-call KV snapshots would inflate the wire numbers)
    w = Workload("lm-serve", flops=5e9, bytes_out=2e4, bytes_back=2e4,
                 model_bytes=1e7)
    with avec.connect(targets, shadow_every=0) as client:
        for name in client.destinations:
            caps = client.capabilities(name)
            print(f"[handshake] {name}: protocol v{caps.protocol_version}, "
                  f"runtime {type(client.runtime(name)).__name__}, "
                  f"codec {client.codec_for(name)}")
        sess = client.session(cfg, params, "lm", workload=w)
        print(f"[scheduler] chose {sess.destination} "
              f"(capability + cost-model routed)")

        t0 = time.perf_counter()
        cached = sess.ensure_model()
        print(f"[cache] model transfer: cached={cached} "
              f"{time.perf_counter() - t0:.3f}s (send-once)")

        # batched requests: prefill once, stream decode steps (stateful —
        # stays on the scheduler-picked session)
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size, size=(4, 8)).astype(np.int32)
        out = sess.call("prefill", {"tokens": prompts})
        toks = np.argmax(out["logits"][:, -1, :cfg.vocab_size], axis=-1)
        stream = [toks]
        for _ in range(16):
            out = sess.call("decode",
                            {"tokens": toks[:, None].astype(np.int32)})
            toks = np.argmax(out["logits"][:, 0, :cfg.vocab_size], axis=-1)
            stream.append(toks)
        gen = np.stack(stream, axis=1)
        print(f"[serve] generated {gen.shape} tokens for {gen.shape[0]} "
              f"requests")
        print(f"[serve] req0: {gen[0].tolist()}")

        # stateless scoring shards across ALL healthy destinations
        reqs = {f"r{i}": {"tokens": rng.integers(
            0, cfg.vocab_size, (1, 16)).astype(np.int32),
            "targets": rng.integers(0, cfg.vocab_size, (1, 16))
            .astype(np.int32)} for i in range(8)}
        t0 = time.perf_counter()
        scores = sess.map("score", reqs)
        dt = time.perf_counter() - t0
        print(f"[shard] {len(scores)} score() calls over "
              f"{sess.last_map_stats['assigned']} in {dt:.2f}s")

        b = sess.profiler.breakdown()
        print("[profile] paper Fig-8 style cycle breakdown:")
        print(f"  GPU           {b['gpu_s']:.3f}s ({b['gpu_frac'] * 100:.1f}%)")
        print(f"  Communication {b['communication_s']:.3f}s "
              f"({b['communication_frac'] * 100:.1f}%)")
        print(f"  Other         {b['other_s']:.3f}s")
        print(f"  wire: {b['bytes_sent']} B out / {b['bytes_received']} B back "
              f"over {b['cycles']} cycles")
        print(f"  throughput: {sess.profiler.fps() * gen.shape[0]:.1f} tok/s "
              f"({sess.profiler.fps():.1f} steps/s)")

    for srv in servers.values():
        srv.stop()


if __name__ == "__main__":
    main()
