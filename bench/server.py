"""The destination process of one benchmark run: the only process that
touches the chip.

It stands the destination up with the program's own
``repro.launch.serve.start_destination`` over a ``make_model_library``
library, makes the weights on the chip from the seed (one jitted call) and
installs them in the executor's model cache under the fingerprint the
session will present, so ``ensure_model`` hits and no weights cross the
wire.  It then answers JSON-line commands from the run on stdin:

  window_start   start the profiler (traced runs); mark the compile count
                 and the destination's counters
  window_end     stop the profiler and reduce its trace; report compiles in
                 the window, the counters' growth in it and the chip's peak
                 memory
  reference      stop serving, free the program's state, regenerate the
                 weights from the seed and run the plain reference (and,
                 when asked, the int8 control) over the sampled requests
  reseed         replace the weights (calibration runs)
  exit

Run by ``bench/run.py``; not meant to be started by hand.
"""
from __future__ import annotations

import argparse
import gc
import os
import shutil
import sys
import time

T_START = time.monotonic()

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(1, os.path.join(_ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness, trace_reduce, weights  # noqa: E402

SEQ_BUCKET = 256


class CompileWatch:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(f"[server {time.monotonic() - T_START:8.3f}s] {msg}",
          file=sys.stderr, flush=True)


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def check_layout(cfg, shapes) -> None:
    """The benchmark's weight layout must be the tree the program takes."""
    from repro.models import model as M
    want = M.abstract_params(cfg)
    got_l, got_t = jax.tree_util.tree_flatten(shapes)
    want_l, want_t = jax.tree_util.tree_flatten(want)
    if got_t != want_t:
        raise ValueError(f"weight tree differs from the program's:\n{got_t}\n"
                         f"vs\n{want_t}")
    for g, w in zip(got_l, want_l):
        if g.shape != w.shape or g.dtype != w.dtype:
            raise ValueError(f"leaf {g} differs from the program's {w}")


def rel_err(got, want, sd):
    """Per position: the root mean square of got - want over the probe ids
    and the chosen token, over the spread (standard deviation) of the
    reference's logits there."""
    return np.sqrt(np.mean((got - want) ** 2, axis=1)) / sd


class Server:
    def __init__(self, args) -> None:
        from repro.core.cache import model_fingerprint
        from repro.core.library import make_model_library
        from repro.launch.serve import start_destination

        self.args = args
        self.watch = CompileWatch()
        #: seconds from process start to the end of each set-up phase
        self.phases = {"imports": round(time.monotonic() - T_START, 3)}
        devs = jax.devices()
        self.phases["devices"] = round(time.monotonic() - T_START, 3)
        self.dev = devs[0]
        self.device = {"platform": self.dev.platform,
                       "kind": self.dev.device_kind, "count": len(devs)}
        if args.require_tpu and self.dev.platform != "tpu":
            raise SystemExit(f"no TPU: JAX's first device is "
                             f"{self.dev.platform!r}")
        if len(devs) < args.chips:
            raise SystemExit(f"the cell needs {args.chips} chips, JAX sees "
                             f"{len(devs)}")
        self.c = harness.load_config(args.config)
        self.cfg = harness.program_config(self.c)
        self.ref_mod = harness.reference_module(self.c["reference"])
        self.layout = self.ref_mod.layout(self.c)
        self.shapes = weights.shapes(self.layout)
        check_layout(self.cfg, self.shapes)
        self.fp0 = model_fingerprint(self.cfg, self.shapes)
        self.make = weights.maker(self.layout, self.dev)
        serving = self.c["serving"]
        lib = make_model_library(self.cfg,
                                 max_cache_len=serving["max_cache_len"])
        self.dest = start_destination({serving["library"]: lib},
                                      name=f"bench-{self.c['name']}",
                                      device=self.dev)
        self.phases["destination"] = round(time.monotonic() - T_START, 3)
        self.install(args.seed)
        self.phases["weights"] = round(time.monotonic() - T_START, 3)
        self.refs: dict = {}
        self.trace_dir = None
        self.t_trace0 = 0.0
        self.mark = 0
        self.counters0: dict = {}

    @property
    def fp(self) -> str:
        return f"tenant:{self.args.tenant}:{self.fp0}"

    def install(self, seed: int) -> None:
        t0 = time.monotonic()
        ex = self.dest.executor
        nbytes = weights.nbytes(self.layout)
        ex.cache.drop(self.fp)
        params = self.make(seed)
        jax.block_until_ready(params)
        ex.cache.put(self.fp, {"lib": self.c["serving"]["library"],
                               "params": params, "state": {}, "extra": {}},
                     nbytes)
        del params
        log(f"installed {nbytes} bytes of weights for seed {seed} in "
            f"{time.monotonic() - t0:.3f}s")

    def counters(self) -> dict:
        """The destination's counters, as its ``/metrics`` exposes them:
        every ``*_total`` view, keyed by name (and labels, where it has
        any)."""
        return {k: v for k, v in
                self.dest.executor.metrics.sample_values().items()
                if k.partition("{")[0].endswith("_total")}

    # -- commands -------------------------------------------------------
    def window_start(self, msg: dict) -> dict:
        self.mark = self.watch.compiles
        self.counters0 = self.counters()
        if msg.get("trace"):
            self.trace_dir = os.path.join(harness.OUT, f"trace-{os.getpid()}")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # host spans from the runtime's own annotations only: the Python
            # tracer would record every Python call of this process and slow
            # the very host path the traced run measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.t_trace0 = time.monotonic()
        return {"ok": True}

    def window_end(self, msg: dict) -> dict:
        out = {"compiles": self.watch.compiles - self.mark,
               "counters": {k: v - self.counters0.get(k, 0.0)
                            for k, v in self.counters().items()},
               "peak_bytes": peak_bytes(self.dev)}
        if self.trace_dir is not None:
            out["window_s"] = time.monotonic() - self.t_trace0
            jax.profiler.stop_trace()
            t0 = time.monotonic()
            out["trace"] = trace_reduce.reduce_trace(self.trace_dir,
                                                     self.args.chips)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.trace_dir = None
            log(f"trace reduced in {time.monotonic() - t0:.3f}s")
        return out

    def _reference(self, mode: str):
        if mode not in self.refs:
            self.refs[mode] = self.ref_mod.Reference(self.c, mode)
        return self.refs[mode]

    def reference(self, msg: dict) -> dict:
        """Widest gap, per sampled request, between the reference's best
        logit and its logit for each served token; with ``control`` also
        the gap of the token the int8 control puts first."""
        t0 = time.monotonic()
        if msg.get("free"):
            self.dest.stop()
            self.dest.executor.cache.drop(self.fp)
            self.dest = None
            gc.collect()
        ref = self._reference("f32")
        ctrl = self._reference("int8") if msg.get("control") else None
        out = []
        ids = np.asarray(msg["probe_ids"], np.int64)
        params = self.make(msg["seed"])
        for it in msg["items"]:
            toks = np.asarray(it["tokens"], np.int32)
            pad = -len(toks) % SEQ_BUCKET
            toks = np.concatenate([toks, np.zeros(pad, np.int32)])
            rows = np.asarray(it["rows"], np.int32)
            served = np.asarray(it["served"], np.int64)
            lg = np.asarray(ref.logits(params, toks, rows), np.float64)
            best = lg.max(axis=1)
            r = np.arange(len(rows))
            gap = best - lg[r, served]
            sd = lg.std(axis=1)
            got = np.asarray(it["probes"], np.float64)
            want = np.concatenate([lg[:, ids], lg[r, served][:, None]], 1)
            res = {"gap": float(gap.max()),
                   "err": float(rel_err(got, want, sd).max()),
                   "tokens": int(len(rows))}
            if ctrl is not None:
                cl = np.asarray(ctrl.logits(params, toks, rows),
                                np.float64)
                pick = cl.argmax(axis=1)
                res["ctrl_gap"] = float((best - lg[r, pick]).max())
                res["ctrl_err"] = float(rel_err(
                    np.concatenate([cl[:, ids], cl[r, pick][:, None]], 1),
                    np.concatenate([lg[:, ids], lg[r, pick][:, None]], 1),
                    sd).max())
            out.append(res)
        del params
        log(f"reference over {len(out)} requests in "
            f"{time.monotonic() - t0:.3f}s")
        return {"items": out, "ref_s": time.monotonic() - t0}

    def reseed(self, msg: dict) -> dict:
        self.install(int(msg["seed"]))
        return {"ok": True}

    def stop(self) -> None:
        if self.dest is not None:
            self.dest.stop()
            self.dest = None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True,
                    help="path of the configuration file")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--tenant", default="t0",
                    help="tenant of the session the weights are installed for")
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--allow-cpu", dest="require_tpu", action="store_false",
                    help="run on whatever JAX finds (CPU tests of the "
                         "harness only; never a measurement)")
    args = ap.parse_args(argv)
    # the command pipe gets stdout's descriptor to itself: whatever the
    # program prints goes to stderr and cannot corrupt a reply
    proto = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    jax.config.update("jax_compilation_cache_dir", harness.COMPILE_CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: evicting keeps an access-time file beside every entry,
    # and one entry written without it (by a process that ran with eviction
    # off) makes every later write to the directory fail
    jax.config.update("jax_compilation_cache_max_size", -1)
    try:
        srv = Server(args)
    except SystemExit as e:
        harness.send(proto, {"event": "error", "error": str(e)})
        return 3
    harness.send(proto, {
        "event": "ready", "port": srv.dest.server.port, "device": srv.device,
        "setup_s": time.monotonic() - T_START,
        "compiles": srv.watch.compiles, "cache_hits": srv.watch.cache_hits,
        "phases": srv.phases})
    try:
        while True:
            try:
                msg = harness.recv(sys.stdin)
            except EOFError:
                return 0
            cmd = msg["cmd"]
            if cmd == "exit":
                return 0
            harness.send(proto, getattr(srv, cmd)(msg))
    finally:
        srv.stop()


if __name__ == "__main__":
    sys.exit(main())
