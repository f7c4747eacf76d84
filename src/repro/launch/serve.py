"""Serving entrypoint: stand up a destination executor (TCP), drive one or
more destinations as an ``avec.connect`` host, or run the continuous-batching
engine locally.

Every role serves the architecture at its published widths; ``--reduced``
swaps in the family-preserving miniature (CPU tests).  The destination owns
the accelerator: it builds no weights (they arrive over the wire), while a
host process pins its own JAX to the CPU so it never takes the chip.

  # destination node (the "edge/cloud GPU server"):
  PYTHONPATH=src python -m repro.launch.serve --role destination --port 9000

  # host node streaming requests at destination(s) through the facade —
  # handshake-negotiated pipelined runtime, scheduler-routed, sharded when
  # several destinations are given (prints the adaptive in-flight window +
  # backpressure counters from the runtime stats):
  PYTHONPATH=src python -m repro.launch.serve --role host \
      --connect 127.0.0.1:9000,127.0.0.1:9001 --requests 32

  # local engine demo:
  PYTHONPATH=src python -m repro.launch.serve --role local --requests 8
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass

import jax
import numpy as np

from repro import avec
from repro.configs import get_arch, list_archs, reduced
from repro.core.executor import DestinationExecutor
from repro.core.library import make_model_library
from repro.core.shm import SharedMemoryServer
from repro.core.transport import TCPServer
from repro.models import model as M
from repro.obs import metrics as obs_metrics
from repro.obs.config import global_config
from repro.obs.trace import emit
from repro.serving.engine import Request, ServingEngine
from repro.utils import enable_compile_cache, pin_host_cpu


@dataclass
class ServedDestination:
    """One destination as ``--role destination`` stands it up: the executor
    plus the listeners in front of it."""
    executor: DestinationExecutor
    server: TCPServer | None = None
    shm_server: SharedMemoryServer | None = None
    metrics_server: obs_metrics.MetricsServer | None = None

    @property
    def address(self) -> str:
        """``tcp://`` URL of the loopback TCP listener (for ``avec.connect``)."""
        return f"tcp://127.0.0.1:{self.server.port}"

    def stop(self) -> None:
        if self.metrics_server is not None:
            self.metrics_server.stop()
        if self.shm_server is not None:
            self.shm_server.stop()
        if self.server is not None:
            self.server.stop()
        self.executor.shutdown()


def start_destination(libraries: dict, *, name: str, device=None,
                      port: int = 0, transport: str = "tcp",
                      shm_path: str | None = None, coalesce: bool = False,
                      tenant_weights: dict | None = None,
                      tenant_max_inflight: int = 0,
                      tenant_max_bytes: float = 0.0,
                      metrics_port: int | None = None) -> ServedDestination:
    """Build a :class:`DestinationExecutor` serving ``libraries`` on
    ``device`` (default: the first JAX device) and start its listeners:
    TCP and/or the shared-memory ring per ``transport``, plus the
    Prometheus listener when the ``metrics_port`` knob resolves above 0."""
    ex = DestinationExecutor(libraries, name=name, device=device,
                             coalesce=coalesce,
                             tenant_weights=tenant_weights or None,
                             tenant_max_inflight=tenant_max_inflight,
                             tenant_max_bytes=tenant_max_bytes)
    dest = ServedDestination(ex)
    if transport in ("tcp", "both"):
        dest.server = TCPServer(ex.handle, port=port).start()
        # the recv-pool lives on the server, not the executor — bind it
        # into the executor's registry so one scrape covers the whole
        # destination
        obs_metrics.bind_server(ex.metrics, dest.server)
    if transport in ("shm", "both"):
        dest.shm_server = SharedMemoryServer(ex.handle, path=shm_path).start()
        # advertised in every ping reply: same-host clients that dialed
        # TCP see the doorbell and silently re-dial over the ring
        ex.shm_address = dest.shm_server.address
        obs_metrics.bind_pool_stats(ex.metrics, dest.shm_server.pool_stats,
                                    pool="shm-server")
        emit("shm_listening", path=dest.shm_server.address,
             ring_bytes=dest.shm_server.ring_bytes)
    mport = int(global_config().resolve("metrics_port", metrics_port))
    if mport > 0:
        dest.metrics_server = obs_metrics.MetricsServer(ex.metrics,
                                                        port=mport).start()
        emit("metrics_listening", port=dest.metrics_server.port,
             url=f"http://127.0.0.1:{dest.metrics_server.port}/metrics")
    return dest


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=list_archs())
    ap.add_argument("--role", default="local",
                    choices=["local", "destination", "host"])
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--connect", default="127.0.0.1:9000",
                    help="host role: comma-separated destination "
                         "addresses host:port[,host:port...]; a "
                         "shm:///path/doorbell.sock entry dials a "
                         "same-host shared-memory destination directly")
    ap.add_argument("--codec", default="raw",
                    help="host role: requested wire codec (downgraded to "
                         "what the peer advertises)")
    ap.add_argument("--transport", default="tcp",
                    choices=["tcp", "shm", "both"],
                    help="destination role: listeners to stand up.  'shm' "
                         "serves same-host clients over a shared-memory "
                         "ring (mmap zero-copy); 'both' adds the SHM "
                         "doorbell beside TCP and advertises it in the "
                         "handshake so same-host clients auto-upgrade")
    ap.add_argument("--shm-path", default=None,
                    help="destination role: AF_UNIX doorbell path for the "
                         "SHM listener (default: a fresh temp dir)")
    ap.add_argument("--coalesce", action="store_true",
                    help="destination role: micro-batch concurrent "
                         "batchable run ops into stacked dispatches")
    ap.add_argument("--tenant-weights", default="",
                    help="destination role: pin per-tenant fair-drain "
                         "weights, e.g. acme:3,beta:1 (overrides "
                         "frame-declared qos)")
    ap.add_argument("--tenant-max-inflight", type=int, default=0,
                    help="destination role: per-tenant admission cap on "
                         "concurrent run requests (0 = unlimited; beyond "
                         "it the tenant gets TenantThrottled)")
    ap.add_argument("--tenant-max-bytes", type=float, default=0.0,
                    help="destination role: per-tenant admission cap on "
                         "in-flight payload bytes (0 = unlimited)")
    ap.add_argument("--tenant", default=None,
                    help="host role: tenant identity for the session "
                         "(isolated destination caches + fair-share drain)")
    ap.add_argument("--qos-weight", type=float, default=1.0,
                    help="host role: declared fair-share weight")
    ap.add_argument("--qos-priority", type=int, default=0,
                    help="host role: declared priority class (higher "
                         "drains first)")
    ap.add_argument("--drain", action="store_true",
                    help="destination role: exit via zero-downtime drain — "
                         "on ctrl-c stop admitting (DestinationDraining "
                         "bounces tell clients to re-home to their warm "
                         "standbys), bleed the QoS queues, then stop")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="destination role: max seconds to wait for "
                         "in-flight work to bleed during --drain")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-in-flight", type=int, default=8,
                    help="host role: in-flight window cap (adaptive below)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="destination role: serve Prometheus text on "
                         "http://127.0.0.1:PORT/metrics (default: the "
                         "metrics_port knob / AVEC_METRICS_PORT; 0 = off)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the family-preserving miniature of --arch "
                         "instead of its published widths (CPU tests)")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    if args.role == "destination":
        enable_compile_cache()
        lib = make_model_library(cfg, max_cache_len=args.max_len)
        weights = {}
        for part in args.tenant_weights.split(","):
            if part.strip():
                tname, _, w = part.partition(":")
                weights[tname.strip()] = float(w or 1.0)
        dest = start_destination(
            {"lm": lib}, name=f"{args.arch}-dest", port=args.port,
            transport=args.transport, shm_path=args.shm_path,
            coalesce=args.coalesce, tenant_weights=weights,
            tenant_max_inflight=args.tenant_max_inflight,
            tenant_max_bytes=args.tenant_max_bytes,
            metrics_port=args.metrics_port)
        ex = dest.executor
        emit("destination_listening", arch=args.arch,
             port=dest.server.port if dest.server is not None else None,
             transport=args.transport, device=ex.device_info(),
             coalesce=args.coalesce, tenant_weights=weights,
             tenant_max_inflight=args.tenant_max_inflight,
             tenant_max_bytes=args.tenant_max_bytes)
        try:
            while True:
                time.sleep(1)
        except KeyboardInterrupt:
            if args.drain:
                # zero-downtime exit: stop admitting (clients re-home on the
                # DestinationDraining bounce; ping keeps advertising
                # "draining" so schedulers stop routing here), bleed every
                # QoS queue, THEN tear the server down — in-flight requests
                # finish and their responses go out before the socket dies
                emit("drain_begin", name=ex.name, pending=ex.pending_work())
                res = ex.drain(timeout_s=args.drain_timeout)
                emit("drain_end", name=ex.name, drained=res["drained"],
                     pending=res["pending"], replay_hits=ex.replay_hits)
            dest.stop()
        return

    if args.role == "host":
        pin_host_cpu()
        params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
        targets = [addr.strip() if addr.strip().startswith(("tcp://",
                                                            "shm://"))
                   else f"tcp://{addr.strip()}"
                   for addr in args.connect.split(",") if addr.strip()]
        with avec.connect(targets, codec=args.codec, shadow_every=0,
                          max_in_flight=args.max_in_flight) as client:
            for name in client.destinations:
                caps = client.capabilities(name)
                emit("handshake", destination=name,
                     protocol_version=caps.protocol_version,
                     runtime=type(client.runtime(name)).__name__,
                     codec=client.codec_for(name), coalesce=caps.coalesce,
                     config=caps.config)
            sess = client.session(
                cfg, params, "lm", tenant=args.tenant,
                qos=avec.QoS(weight=args.qos_weight,
                             priority=args.qos_priority))
            rng = np.random.default_rng(args.seed)
            prompts = {f"r{i}": {"tokens": rng.integers(
                0, cfg.vocab_size, (1, 16)).astype(np.int32),
                "targets": rng.integers(0, cfg.vocab_size, (1, 16))
                .astype(np.int32)} for i in range(args.requests)}
            t0 = time.perf_counter()
            sess.map("score", prompts)
            dt = time.perf_counter() - t0
            emit("offload_complete", requests=args.requests, seconds=dt,
                 req_per_s=args.requests / dt,
                 assigned=sess.last_map_stats["assigned"])
            for name, s in client.stats().items():
                if "window" not in s:
                    continue
                emit("runtime_stats", destination=name, window=s["window"],
                     max_in_flight=s["max_in_flight"],
                     wire_ema_ms=s["wire_ema_s"] * 1e3,
                     compute_ema_ms=s["compute_ema_s"] * 1e3,
                     send_stalls=s["send_stalls"],
                     sends_resumed=s["sends_resumed"],
                     recv_retries=s["recv_retries"],
                     bytes_sent=s["bytes_sent"],
                     bytes_received=s["bytes_received"])
            for name in client.destinations:
                ts = client.refresh_capabilities(name).tenant_stats
                for tenant, row in sorted(ts.items()):
                    emit("tenant_stats", destination=name, tenant=tenant,
                         drain_share=row.get("drain_share", 0.0),
                         served=row.get("served", 0),
                         throttled=row.get("throttled", 0),
                         queue_depth=row.get("queue_depth", 0))
        return

    enable_compile_cache()
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_len=args.max_len)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        eng.submit(Request(f"r{i}",
                           rng.integers(0, cfg.vocab_size,
                                        rng.integers(4, 16)).tolist(),
                           max_new_tokens=16))
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    toks = sum(len(v) for v in out.values())
    emit("engine_complete", requests=args.requests, tokens=toks, seconds=dt,
         tok_per_s=toks / dt, engine_ticks=eng.steps)


if __name__ == "__main__":
    main()
