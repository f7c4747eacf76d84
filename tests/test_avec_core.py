"""AVEC core behaviour: serialization, transport, cache, interception,
executor RPC, scheduler, hedging, migration/failover, profiler accounting."""
import queue
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.configs.avec_openpose import WORKLOAD
from repro.core import (AcceleratorRegistry, AvecProfiler, AvecSession,
                        DestinationExecutor, DeviceAwareScheduler,
                        HeartbeatMonitor, HostRuntime, InterceptionLibrary,
                        MigrationManager, ModelCache, SessionShadow, Workload,
                        hedged_call, model_fingerprint)
from repro.core.costmodel import (amortized_speedup, native_cycle_time,
                                  offload_cycle_time, speedup)
from repro.core.library import make_model_library
from repro.core.memory import release_buffer
from repro.core.profiler import KEEP_CYCLES
from repro.core.serialization import (DataTransfer, eq1_bytes, pack_message,
                                      tree_wire_bytes, unpack_message)
from repro.core.transport import (Channel, LoopbackChannel, SimulatedChannel,
                                  TCPChannel, TCPServer, VirtualClock)
from repro.core.virtualization import CLOUD_RTX, JETSON_NANO, JETSON_TX2
from repro.models import model as M


from repro.core.transport import DirectChannel  # shared in-process shim


def _make_session(cfg=None, codec="raw", name="dest"):
    cfg = cfg or reduced(get_arch("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    lib = make_model_library(cfg, max_cache_len=32)
    ex = DestinationExecutor({"lm": lib}, name=name)
    rt = HostRuntime(DirectChannel(ex), codec=codec)
    return cfg, params, ex, rt, AvecSession(cfg, params, rt, "lm")


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------

def test_wire_roundtrip_nested_tree():
    tree = {"a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": [np.ones((2,), np.int32), {"c": np.zeros((1, 1), np.float64)}],
            "scalar": 7, "name": "x",
            "t": (np.full((2, 2), 3.0, np.float32),)}
    data = pack_message({"op": "test"}, tree)
    meta, out = unpack_message(data)
    assert meta["op"] == "test"
    np.testing.assert_array_equal(out["a"], tree["a"])
    np.testing.assert_array_equal(out["b"][0], tree["b"][0])
    assert isinstance(out["t"], tuple)
    assert out["scalar"] == 7 and out["name"] == "x"


@pytest.mark.parametrize("codec", ["raw", "zstd", "int8"])
def test_wire_codecs(codec):
    x = np.random.default_rng(0).standard_normal((64, 128)).astype(np.float32)
    data = pack_message({}, {"x": x}, codec=codec)
    _, out = unpack_message(data)
    if codec == "int8":
        bound = np.abs(x).max(axis=-1, keepdims=True) / 127.0
        assert np.all(np.abs(out["x"] - x) <= bound + 1e-7)
        assert len(data) < x.nbytes / 2     # actually compresses
    else:
        np.testing.assert_array_equal(out["x"], x)
    if codec == "zstd":
        assert len(data) < x.nbytes * 1.2


def test_eq1_paper_value():
    """Paper: ~3.75 MB per 1x3x368x656 frame with c=3.368421."""
    dt = eq1_bytes(WORKLOAD.dims, WORKLOAD.output_divisor)
    assert abs(dt / 1e6 - 3.75) < 0.15, dt
    assert abs(dt - WORKLOAD.data_transfer_bytes()) < 1.0


def test_bfloat16_wire_roundtrip():
    import ml_dtypes
    x = np.arange(8, dtype=np.float32).astype(ml_dtypes.bfloat16)
    _, out = unpack_message(pack_message({}, {"x": x}))
    assert out["x"].dtype == x.dtype
    np.testing.assert_array_equal(out["x"], x)


# ---------------------------------------------------------------------------
# transport
# ---------------------------------------------------------------------------

def test_loopback_and_tcp_roundtrip():
    a, b = LoopbackChannel.pair()
    a.send(b"hello")
    assert b.recv(timeout=1) == b"hello"

    server = TCPServer(lambda req: req[::-1]).start()
    ch = TCPChannel.connect("127.0.0.1", server.port)
    got = ch.request(b"abc", timeout=5)
    assert got == b"cba"
    release_buffer(got)
    ch.close()
    server.stop()


def test_simulated_channel_charges_clock():
    a, b = LoopbackChannel.pair()
    clock = VirtualClock()
    sim = SimulatedChannel(a, clock, bandwidth=1e6, latency=0.01,
                           serialize_rate=2e6, name="edge")
    payload = b"x" * 100_000
    sim.send(payload)
    t = clock.elapsed["edge.send"]
    assert abs(t - (0.01 + 0.1 + 0.05)) < 1e-9


# ---------------------------------------------------------------------------
# cache / send-once
# ---------------------------------------------------------------------------

def test_model_cache_send_once():
    cfg, params, ex, rt, sess = _make_session()
    assert sess.ensure_model() is False      # first: transferred
    assert sess.ensure_model() is True       # second: cache hit
    stats = ex.cache.stats()
    assert stats["entries"] == 1 and stats["hits"] >= 1

    # same weights, second host session -> still resident
    rt2 = HostRuntime(DirectChannel(ex))
    sess2 = AvecSession(cfg, params, rt2, "lm")
    assert sess2.ensure_model() is True


def test_fingerprint_sensitivity():
    cfg = reduced(get_arch("granite-3-2b"))
    p1 = M.init_params(cfg, jax.random.PRNGKey(0))
    fp1 = model_fingerprint(cfg, p1)
    cfg2 = reduced(get_arch("deepseek-7b"))
    p2 = M.init_params(cfg2, jax.random.PRNGKey(0))
    assert fp1 != model_fingerprint(cfg2, p2)
    assert fp1 == model_fingerprint(cfg, p1)


def test_cache_eviction_capacity():
    c = ModelCache(capacity_bytes=100)
    c.put("a", {"x": 1}, 60)
    c.put("b", {"x": 2}, 60)   # evicts a
    assert not c.has("a") and c.has("b")


# ---------------------------------------------------------------------------
# executor RPC + interception
# ---------------------------------------------------------------------------

def test_rpc_prefill_decode_matches_local():
    cfg, params, ex, rt, sess = _make_session()
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    remote = sess.call("prefill", {"tokens": np.asarray(tok)})
    local_lg, _ = M.prefill(cfg, params, {"tokens": tok}, 32)
    np.testing.assert_allclose(remote["logits"], np.asarray(local_lg), atol=1e-4)
    # stateful decode continues at the destination
    out = sess.call("decode", {"tokens": np.asarray(tok[:, :1])})
    assert out["logits"].shape == (2, 1, cfg.padded_vocab)


def test_interception_no_source_modification():
    """An application module calling openpose functions is rerouted without
    any change to its own code."""
    import repro.models.openpose as op_mod
    from repro.core.library import make_openpose_library
    from repro.models.params import init_params as ip

    net = op_mod.OpenPoseLite()
    params = ip(op_mod.op_param_specs(net), jax.random.PRNGKey(2), jnp.float32)
    ex = DestinationExecutor({"openpose": make_openpose_library(net)})
    rt = HostRuntime(DirectChannel(ex))
    sess = AvecSession(net, params, rt, "openpose")
    frames = op_mod.make_frames(1, 32, 32)

    local = op_mod.op_forward(net, params, frames)
    disp = sess.make_dispatcher({"op_forward": "forward"})
    with InterceptionLibrary(op_mod, ["op_forward"], disp):
        remote = op_mod.op_forward(net, params, {"frames": np.asarray(frames)})
    np.testing.assert_allclose(np.asarray(local), remote["beliefs"], atol=1e-5)
    # uninstalled afterwards
    local2 = op_mod.op_forward(net, params, frames)
    assert not hasattr(op_mod.op_forward, "__wrapped__")
    np.testing.assert_allclose(np.asarray(local), np.asarray(local2))
    assert len(sess.profiler.cycles) == 1


def test_remote_error_propagates():
    cfg, params, ex, rt, sess = _make_session()
    sess.ensure_model()
    ex.fail = True
    from repro.core.executor import RemoteError
    with pytest.raises(RemoteError):
        sess.call("prefill", {"tokens": np.zeros((1, 4), np.int32)})


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------

def test_profiler_accounting_sums():
    p = AvecProfiler()
    p.record_cycle(gpu_s=0.10, comm_s=0.05, bytes_sent=100, bytes_received=50)
    p.record_cycle(gpu_s=0.10, comm_s=0.05, bytes_sent=100, bytes_received=50)
    p.record_other(0.1)
    b = p.breakdown()
    assert abs(b["gpu_s"] - 0.2) < 1e-12
    assert abs(b["communication_s"] - 0.1) < 1e-12
    assert abs(b["gpu_frac"] + b["communication_frac"] + b["other_frac"] - 1.0) < 1e-9
    assert p.fps() == pytest.approx(2 / 0.4)


def test_profiler_memory_is_bounded():
    n = KEEP_CYCLES + 6
    p = AvecProfiler()
    for i in range(n):
        p.record_cycle(gpu_s=0.01, comm_s=0.02, bytes_sent=10,
                       bytes_received=5, fn=f"f{i}")
    # the totals cover every cycle; the ring keeps the most recent
    assert [c.fn for c in p.cycles] == [f"f{i}" for i in range(6, n)]
    b = p.breakdown()
    assert b["cycles"] == n and b["bytes_sent"] == 10 * n
    assert b["gpu_s"] == pytest.approx(0.01 * n)
    assert p.per_cycle()["communication_s"] == pytest.approx(0.02)
    assert p.fps() == pytest.approx(1 / 0.03)


# ---------------------------------------------------------------------------
# cost model vs paper testbed
# ---------------------------------------------------------------------------

def test_costmodel_monotone_and_paper_band():
    w = Workload("openpose", flops=WORKLOAD.forward_flops,
                 bytes_out=WORKLOAD.data_transfer_bytes() * 0.999,
                 bytes_back=WORKLOAD.data_transfer_bytes() * 0.001,
                 host_other_s=0.18,
                 model_bytes=WORKLOAD.model_weight_bytes)
    s_edge = speedup(w, JETSON_NANO, JETSON_TX2)
    s_cloud = speedup(w, JETSON_NANO, CLOUD_RTX)
    assert s_cloud > s_edge > 1.0
    # paper Table IV band (video): 1.45x edge, 7.48x cloud
    assert 1.1 < s_edge < 2.2
    assert 4.0 < s_cloud < 11.0
    # amortized speedup approaches per-cycle speedup as cycles grow
    a10 = amortized_speedup(w, JETSON_NANO, CLOUD_RTX, 10)
    a1000 = amortized_speedup(w, JETSON_NANO, CLOUD_RTX, 1000)
    assert a10 < a1000 <= s_cloud * 1.001


# ---------------------------------------------------------------------------
# scheduler + hedging
# ---------------------------------------------------------------------------

def test_scheduler_picks_best_and_respects_memory():
    reg = AcceleratorRegistry()
    reg.register(JETSON_TX2)
    reg.register(CLOUD_RTX)
    sched = DeviceAwareScheduler(reg)
    w = Workload("w", flops=160e9, bytes_out=3.7e6, bytes_back=1e6,
                 model_bytes=5.5e9)
    pick = sched.pick(w)
    assert pick.name == "cloud-rtx"
    # load shifts the decision
    reg.get("cloud-rtx").inflight = 50
    assert sched.pick(w).name == "jetson-tx2"
    # memory constraint excludes small accelerators
    w_big = Workload("big", flops=1e9, bytes_out=1e6, bytes_back=1e6,
                     model_bytes=7e9)
    reg.get("cloud-rtx").inflight = 0
    assert sched.pick(w_big).name == "jetson-tx2"  # 8GB edge fits, 6GB rtx not


def test_hedged_call_straggler():
    def slow():
        time.sleep(0.5)
        return "slow"

    def fast():
        return "fast"

    out, winner = hedged_call(slow, fast, hedge_after_s=0.05)
    assert out == "fast" and winner == "backup"
    out, winner = hedged_call(fast, slow, hedge_after_s=0.5)
    assert out == "fast" and winner == "primary"


# ---------------------------------------------------------------------------
# migration / failover
# ---------------------------------------------------------------------------

def test_failover_preserves_decode_stream():
    """Destination dies mid-stream; session fails over to a second executor
    restoring the shadowed KV state; the decoded continuation matches an
    uninterrupted local run."""
    cfg = reduced(get_arch("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    lib = make_model_library(cfg, max_cache_len=32)
    ex_a = DestinationExecutor({"lm": lib}, name="edge-a")
    ex_b = DestinationExecutor({"lm": lib}, name="edge-b")
    executors = {"edge-a": ex_a, "edge-b": ex_b}

    reg = AcceleratorRegistry()
    reg.register(JETSON_TX2._replace(name="edge-a") if hasattr(JETSON_TX2, "_replace")
                 else JETSON_TX2)
    import dataclasses as dc
    reg._pool.clear()
    reg.register(dc.replace(JETSON_TX2, name="edge-a"))
    reg.register(dc.replace(JETSON_TX2, name="edge-b"))

    def rt_factory(name):
        return HostRuntime(DirectChannel(executors[name]))

    sched = DeviceAwareScheduler(reg)
    mgr = MigrationManager(reg, sched, rt_factory)
    sess = AvecSession(cfg, params, rt_factory("edge-a"), "lm")
    shadow = SessionShadow(every_n_calls=1)

    tok = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, cfg.vocab_size)
    sess.call("prefill", {"tokens": np.asarray(tok)})
    shadow.force_snapshot(sess, step=0)

    # uninterrupted reference: greedy continuation
    from repro.serving.engine import generate_sequential
    want = generate_sequential(cfg, params, [int(t) for t in tok[0]], 5,
                               max_len=32)

    last = int(np.argmax(
        sess.call("decode", {"tokens": np.asarray([[want[0]]], np.int32)}
                  )["logits"][0, 0, :cfg.vocab_size]))
    shadow.force_snapshot(sess, step=1)
    assert last == want[1]

    # kill edge-a, failover to edge-b from the shadow
    ex_a.fail = True
    w = Workload("lm", flops=1e9, bytes_out=1e4, bytes_back=1e4, model_bytes=1e6)
    new_name = mgr.failover(sess, w, failed_name="edge-a", shadow=shadow)
    assert new_name == "edge-b"
    out = sess.call("decode", {"tokens": np.asarray([[want[1]]], np.int32)})
    got = int(np.argmax(out["logits"][0, 0, :cfg.vocab_size]))
    assert got == want[2]
    assert mgr.migrations[0]["from"] == "edge-a"


def test_heartbeat_detects_failure():
    cfg, params, ex, rt, sess = _make_session(name="hb-dest")
    reg = AcceleratorRegistry()
    import dataclasses as dc
    reg.register(dc.replace(JETSON_TX2, name="hb-dest"))
    failed = threading.Event()
    mon = HeartbeatMonitor(rt, "hb-dest", reg, interval_s=0.01, misses=2,
                           timeout_s=0.2, on_failure=lambda n: failed.set())
    mon.start()
    time.sleep(0.05)
    assert not failed.is_set()
    ex.fail = True
    assert failed.wait(timeout=2.0)
    assert not reg.get("hb-dest").healthy
    mon.stop()
