"""Smoke check: AVEC's served offload path on one TPU chip.

One process owns the chip.  It stands up a destination with the same code
``python -m repro.launch.serve --role destination`` uses, and reaches it as a
host would: ``avec.connect(["tcp://127.0.0.1:<port>"])`` over a real
``TCPServer`` on loopback.  The host side (weights from ``--seed``, frames,
tokens) lives on the CPU backend; only the destination touches the chip.

Phases, in order; any failure raises and the script exits non-zero:

  device    the first JAX device must be a TPU (no CPU fallback);
  openpose  the paper's workload: OpenPose-lite at 1x368x656x3 float32,
            weights through the send-once cache, 8 ``sess.call`` and 8
            ``sess.call_async`` frames, checked bit-identical against
            ``op_forward`` on the destination's resident params on the same
            chip and within OPENPOSE_TOL of a float32 CPU reference;
  granite   granite-3-2b at published widths (5.08 GB of bf16 weights
            shipped by ``put_model``): one 1x128 prefill, 16 greedy decode
            steps and one score, all finite and bit-identical to the same
            library functions called directly on the resident params; the
            chip's peak memory must show the weights are on it.

``--four-chips`` runs only the multi-destination path: four destinations in
this process, executor i on ``jax.devices()[i]``, each behind its own
``TCPServer``; OpenPose-lite ``sess.map`` over 16 frames and one
``sess.call(..., shard=True)`` of 128 frames (at the default and at the
"highest" matmul precision), compared with one destination.

Times printed are one run, not a benchmark.  The last line of stdout is
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Run from the checkout root:  python chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import avec  # noqa: E402
from repro.configs import get_arch  # noqa: E402
from repro.configs.avec_openpose import WORKLOAD  # noqa: E402
from repro.core.library import make_model_library, make_openpose_library  # noqa: E402
from repro.launch.serve import start_destination  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.openpose import (OpenPoseLite, op_forward,  # noqa: E402
                                   op_param_specs)
from repro.models.params import init_params  # noqa: E402
from repro.obs.config import global_config  # noqa: E402
from repro.utils import enable_compile_cache  # noqa: E402

#: served beliefs vs the float32 CPU reference (precision "highest"): the
#: chip's default f32 conv precision rounds operands to bf16, which an
#: emulation on the CPU puts at 0.5% of max|ref| for OpenPose-lite; the
#: bound leaves 4x room.  Checked as max|served - ref| <= tol * max|ref|.
OPENPOSE_TOL = 2e-2
#: sharded / mapped results vs one destination, where both compute at float32
#: precision.  At the chip's default precision a sharded call agrees only to
#: OPENPOSE_TOL: the bf16 operand rounding of its 32-row shards differs from
#: that of the 128-row call (measured 1.2e-3 of max|ref| on a v5e), so the
#: sharded call is also compared under the "highest" matmul precision.
F32_TOL = 1e-5


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def _host_np(tree):
    """Owning host copies (served results may be views over receive
    buffers)."""
    return jax.tree_util.tree_map(np.array, tree)


def _max_rel_err(a, ref) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - ref))
                 / max(float(np.max(np.abs(ref))), 1e-30))


class CompileWatch:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self) -> None:
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"compiles={self.compiles} compile_s={self.compile_s:.3f} "
                f"persistent_cache_hits={self.cache_hits}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(want: int = 1) -> jax.Device:
    devs = jax.devices()
    dev = devs[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    check(len(devs) >= want, f"need {want} chips, JAX sees {len(devs)}")
    return dev


def openpose_phase(client, dest, host, seed: int, *, frames: int = 16,
                   hw: tuple = (368, 656)) -> None:
    """Paper workload through the served path; half the frames synchronous,
    half pipelined."""
    t0 = time.perf_counter()
    net = OpenPoseLite()
    with jax.default_device(host):
        params = init_params(op_param_specs(net), jax.random.PRNGKey(seed),
                             jnp.float32)
    sess = client.session(net, params, "openpose")
    check(not sess.ensure_model(), "fresh destination already held the model")
    check(sess.ensure_model(), "send-once cache missed the second ensure")
    rng = np.random.default_rng(seed)
    stream = [rng.standard_normal((1, *hw, 3), dtype=np.float32)
              for _ in range(frames)]
    half = frames // 2
    served = [_host_np(sess.call("forward", {"frames": f}))["beliefs"]
              for f in stream[:half]]
    futs = [sess.call_async("forward", {"frames": f}) for f in stream[half:]]
    served += [_host_np(f.result())["beliefs"] for f in futs]
    wall = time.perf_counter() - t0
    want_shape = (1, hw[0] // 8, hw[1] // 8, net.n_parts + net.n_pafs)
    for b in served:
        check(b.shape == want_shape and b.dtype == np.float32,
              f"beliefs {b.shape} {b.dtype}, want {want_shape} float32")
        check(bool(np.all(np.isfinite(b))), "non-finite beliefs")

    ex = dest.executor
    resident = ex.cache.get(sess.fp)["params"]
    fwd = jax.jit(functools.partial(op_forward, net))
    direct = [np.asarray(fwd(resident, jax.device_put(f, ex.device)))
              for f in stream]
    same = [np.array_equal(s, d) for s, d in zip(served, direct)]
    log(f"[openpose] served vs direct op_forward on {ex.device.device_kind}: "
        f"{sum(same)}/{frames} frames bit-identical")
    check(all(same), "served beliefs differ from direct on-chip op_forward")

    cpu_fwd = jax.jit(functools.partial(op_forward, net))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(cpu_fwd(jax.device_put(params, host),
                                 jax.device_put(np.concatenate(stream), host)),
                         np.float64)
    err = _max_rel_err(np.concatenate(served), ref)
    log(f"[openpose] vs float32 CPU reference: max|err|/max|ref|={err:.6g} "
        f"(tolerance {OPENPOSE_TOL})")
    check(err <= OPENPOSE_TOL, "served beliefs outside the CPU tolerance")

    per = sess.profiler.per_cycle()
    log(f"[openpose] wire bytes/frame={per['bytes_per_cycle']:.0f} "
        f"(Eq. 1 at 1x3x368x656: {WORKLOAD.data_transfer_bytes():.0f}); "
        f"model transfer {sess.model_transfer_s:.3f}s; "
        f"{frames} frames in {wall:.3f}s (one run, not a benchmark)")


def granite_phase(client, dest, host, seed: int, *, cfg=None,
                  prompt: int = 128, steps: int = 16) -> None:
    """Full-width granite-3-2b: weights built on the host CPU, shipped by
    put_model, then prefill / decode / score through the session."""
    cfg = cfg or get_arch("granite-3-2b")
    t0 = time.perf_counter()
    with jax.default_device(host):
        params = M.init_params(cfg, jax.random.PRNGKey(seed))
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    t_init = time.perf_counter() - t0
    sess = client.session(cfg, params, "lm")
    t1 = time.perf_counter()
    sess.ensure_model()
    t_put = time.perf_counter() - t1
    log(f"[granite] {cfg.name}: {cfg.num_layers}L d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
        f"{cfg.param_dtype}; {nbytes} weight bytes built on the host in "
        f"{t_init:.3f}s, put_model {t_put:.3f}s")
    del params

    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (1, prompt)).astype(np.int32)
    targets = rng.integers(0, cfg.vocab_size, (1, prompt)).astype(np.int32)
    t2 = time.perf_counter()
    logits = [_host_np(sess.call("prefill", {"tokens": tokens}))["logits"]]
    fed = []
    for _ in range(steps):
        tok = np.argmax(logits[-1][:, -1], axis=-1).astype(np.int32)[:, None]
        fed.append(tok)
        logits.append(_host_np(sess.call("decode", {"tokens": tok}))["logits"])
    loss = _host_np(sess.call("score", {"tokens": tokens,
                                        "targets": targets}))["loss"]
    t_serve = time.perf_counter() - t2
    for lg in logits:
        check(bool(np.all(np.isfinite(lg))), "non-finite logits")
    check(bool(np.isfinite(loss)), "non-finite score loss")

    ex = dest.executor
    lib = ex.libraries["lm"]
    resident = ex.cache.get(sess.fp)["params"]
    state: dict = {}
    def on_chip(args):
        return jax.device_put(args, ex.device)

    direct = [np.asarray(lib["prefill"](resident, state,
                                        on_chip({"tokens": tokens}))["logits"])]
    for tok in fed:
        direct.append(np.asarray(lib["decode"](
            resident, state, on_chip({"tokens": tok}))["logits"]))
    d_loss = np.asarray(lib["score"](
        resident, {}, on_chip({"tokens": tokens, "targets": targets}))["loss"])
    same = [np.array_equal(s, d) for s, d in zip(logits, direct)]
    log(f"[granite] prefill logits {logits[0].shape} {logits[0].dtype}, "
        f"{steps} decode steps, score loss={float(loss):.6f}; served vs "
        f"direct: {sum(same)}/{len(same)} logits bit-identical, score "
        f"{'bit-identical' if np.array_equal(loss, d_loss) else 'differs'}; "
        f"served in {t_serve:.3f}s (one run, not a benchmark)")
    check(all(same), "served logits differ from direct library calls")
    check(np.array_equal(loss, d_loss), "served score differs from direct")


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def four_destination_phase(devices, host, seed: int, *, hw=(368, 656),
                           map_frames: int = 16, batch: int = 128,
                           shard_min_rows: int = 16,
                           check_peak: bool = True) -> None:
    """One executor per device behind its own TCPServer; ``sess.map`` and
    a sharded call compared with one destination."""
    net = OpenPoseLite()
    with jax.default_device(host):
        params = init_params(op_param_specs(net), jax.random.PRNGKey(seed),
                             jnp.float32)
    dests = [start_destination({"openpose": make_openpose_library(net)},
                               name=f"smoke-{i}", device=d)
             for i, d in enumerate(devices)]
    rng = np.random.default_rng(seed)
    frames = rng.standard_normal((batch, *hw, 3), dtype=np.float32)
    try:
        with avec.connect([d.address for d in dests],
                          shadow_every=0) as client, \
                avec.connect([dests[0].address], shadow_every=0) as one:
            sess = client.session(net, params, "openpose")
            ref = one.session(net, params, "openpose")
            t0 = time.perf_counter()
            mapped = sess.map("forward", {
                i: {"frames": frames[i:i + 1]} for i in range(map_frames)})
            mapped = {i: _host_np(r)["beliefs"] for i, r in mapped.items()}
            t_map = time.perf_counter() - t0
            assigned = sess.last_map_stats["assigned"]
            log(f"[four] map: {map_frames} frames over {assigned} in "
                f"{t_map:.3f}s (one run, not a benchmark)")
            check(sum(1 for n in assigned.values() if n) == len(devices),
                  f"map did not use all {len(devices)} destinations")
            one_map = [_host_np(ref.call("forward", {
                "frames": frames[i:i + 1]}))["beliefs"]
                for i in range(map_frames)]

            compared = [("map", np.concatenate(
                [mapped[i] for i in range(map_frames)]),
                np.concatenate(one_map), F32_TOL)]
            was = jax.config.jax_default_matmul_precision
            for prec, tol in ((None, OPENPOSE_TOL), ("highest", F32_TOL)):
                # global (not a context manager): the destinations trace in
                # their own server threads
                jax.config.update("jax_default_matmul_precision", prec)
                global_config().set("shard_min_rows", shard_min_rows)
                try:
                    t1 = time.perf_counter()
                    sharded = _host_np(sess.call(
                        "forward", {"frames": frames}, shard=True))["beliefs"]
                    t_shard = time.perf_counter() - t1
                    one_big = _host_np(ref.call(
                        "forward", {"frames": frames}))["beliefs"]
                finally:
                    global_config().unset("shard_min_rows")
                    jax.config.update("jax_default_matmul_precision", was)
                st = sess.last_shard_stats
                check(st is not None and len(set(st["destinations"]))
                      == len(devices),
                      f"sharded call did not split {len(devices)} ways: {st}")
                label = f"shard ({prec or 'default'} precision)"
                log(f"[four] {label}: {batch} frames as "
                    f"{[s['stop'] - s['start'] for s in st['shards']]} rows "
                    f"on {st['destinations']} in {t_shard:.3f}s (one run, "
                    f"not a benchmark)")
                compared.append((label, sharded, one_big, tol))

            for label, got, want, tol in compared:
                bit = np.array_equal(got, want)
                err = _max_rel_err(got, want.astype(np.float64))
                log(f"[four] {label} vs one destination: bit-identical={bit} "
                    f"max|err|/max|ref|={err:.3g} (tolerance {tol})")
                check(got.shape == want.shape, f"{label} shape {got.shape}")
                check(err <= tol, f"{label} outside tolerance")

            for d in dests:
                ex = d.executor
                leaves = jax.tree_util.tree_leaves(
                    ex.cache.get(sess.fp)["params"])
                homes = {dev for leaf in leaves for dev in leaf.devices()}
                log(f"[four] {ex.name}: params on {sorted(map(str, homes))}, "
                    f"executor device {ex.device}")
                check(homes == {ex.device},
                      f"{ex.name} params not on its own device")
        for dev in devices:
            peak = peak_bytes(dev)
            log(f"[four] {dev}: peak_bytes_in_use={peak}")
            check(not check_peak or peak > 0, f"{dev} shows no memory use")
    finally:
        for d in dests:
            d.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-destination path on four chips")
    args = ap.parse_args(argv)

    # the host side builds weights and references on the CPU backend, so it
    # must stay available beside an explicit platform list
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        jax.config.update("jax_platforms", f"{plats},cpu")
    log(f"[cache] compile cache: {enable_compile_cache()}")
    watch = CompileWatch()
    want = 4 if args.four_chips else 1
    dev = device_phase(want)
    host = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    if args.four_chips:
        four_destination_phase(jax.devices()[:4], host, args.seed)
    else:
        dest = start_destination(
            {"openpose": make_openpose_library(OpenPoseLite()),
             "lm": make_model_library(get_arch("granite-3-2b"),
                                      max_cache_len=2048)},
            name="chip-smoke", device=dev)
        try:
            with avec.connect([dest.address], shadow_every=0) as client:
                caps = client.capabilities(client.destinations[0])
                log(f"[handshake] {dest.address} serves "
                    f"{sorted(caps.libraries)} on {caps.raw.get('device')}")
                openpose_phase(client, dest, host, args.seed)
                granite_phase(client, dest, host, args.seed)
            peak = peak_bytes(dev)
            log(f"[memory] {dev.device_kind} peak_bytes_in_use={peak}")
            check(peak > 5e9, "peak device memory under 5 GB: the "
                              "full-width weights are not on the chip")
        finally:
            dest.stop()
    log(f"[compile] {watch.line()}; phases {time.perf_counter() - t0:.3f}s "
        f"(one run, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
