"""The repro.obs observability plane: config knob registry (env/explicit/
default precedence, handshake advertisement), metrics registry + Prometheus
exposition (+ the /metrics listener and the `metrics` control op), and the
end-to-end request trace timeline over a real offloaded call."""
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro import avec
from repro.core.executor import (DestinationExecutor, HostRuntime,
                                 PipelinedHostRuntime)
from repro.core.interception import AvecSession
from repro.core.serialization import unpack_message
from repro.core.transport import TCPChannel, TCPServer
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.config import GlobalConfig, UnknownKnobError, global_config

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")


def _tiny_library():
    def double(params, state, args):
        return {"y": np.asarray(args["x"]) * 2.0}
    return {"double": double}


# ---------------------------------------------------------------------------
# config: precedence + rejection
# ---------------------------------------------------------------------------

def test_knob_precedence_env_beats_explicit_beats_default(monkeypatch):
    cfg = global_config()
    monkeypatch.delenv("AVEC_MAX_COALESCE", raising=False)
    assert cfg.resolve("max_coalesce") == 8                 # default
    assert cfg.resolve("max_coalesce", 3) == 3              # explicit
    monkeypatch.setenv("AVEC_MAX_COALESCE", "13")
    assert cfg.resolve("max_coalesce", 3) == 13             # env wins
    assert cfg.source("max_coalesce") == "env"


def test_knob_type_parsing(monkeypatch):
    cfg = global_config()
    monkeypatch.setenv("AVEC_ADAPTIVE_WINDOW", "off")
    assert cfg.resolve("adaptive_window") is False
    monkeypatch.setenv("AVEC_ADAPTIVE_WINDOW", "true")
    assert cfg.resolve("adaptive_window") is True
    monkeypatch.setenv("AVEC_COALESCE_WINDOW_S", "0.25")
    assert cfg.resolve("coalesce_window_s") == pytest.approx(0.25)
    monkeypatch.setenv("AVEC_ADAPTIVE_WINDOW", "maybe")
    with pytest.raises(ValueError):
        cfg.resolve("adaptive_window")


def test_unknown_knob_rejected():
    cfg = global_config()
    with pytest.raises(UnknownKnobError):
        cfg.resolve("no_such_knob")
    with pytest.raises(UnknownKnobError):
        cfg.set("no_such_knob", 1)


def test_every_knob_documented_and_no_undocumented_registration():
    cfg = global_config()
    assert cfg.knobs(), "knob registry must not be empty"
    for k in cfg.knobs():
        assert k.doc.strip(), f"knob {k.name} lacks a doc string"
        assert k.env == "AVEC_" + k.name.upper()
    fresh = GlobalConfig()
    with pytest.raises(ValueError):
        fresh.register("bare", int, 0, "")


def test_env_override_reaches_executor(monkeypatch):
    monkeypatch.setenv("AVEC_MAX_COALESCE", "5")
    monkeypatch.setenv("AVEC_COALESCE_WINDOW_S", "0.007")
    ex = DestinationExecutor({"tiny": _tiny_library()}, coalesce=True,
                             max_coalesce=2)      # env beats the ctor arg
    try:
        assert ex.max_coalesce == 5
        assert ex.coalesce_window_s == pytest.approx(0.007)
        eff = ex.effective_config()
        assert eff["max_coalesce"] == 5
        assert eff["coalesce_window_s"] == pytest.approx(0.007)
    finally:
        ex.shutdown()


def test_handshake_round_trips_effective_config(monkeypatch):
    monkeypatch.setenv("AVEC_REPLAY_CACHE", "11")
    ex = DestinationExecutor({"tiny": _tiny_library()}, name="cfg-dest")
    with avec.connect([ex]) as client:
        caps = client.capabilities("cfg-dest")
        assert caps.config["replay_cache"] == 11
        assert caps.config["coalesce_window_s"] == pytest.approx(
            ex.coalesce_window_s)
        # the full registry rides along, not just the executor's own knobs
        assert "heartbeat_interval_s" in caps.config


def test_knob_cli_table():
    out = subprocess.run(
        [sys.executable, "-m", "repro.obs", "--knobs"],
        capture_output=True, text=True, check=True,
        env={**os.environ, "PYTHONPATH": SRC}).stdout
    assert "| knob |" in out and "`AVEC_MAX_COALESCE`" in out
    for k in global_config().knobs():
        assert k.name in out


# ---------------------------------------------------------------------------
# metrics: registration + exposition format
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_exposition():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("avec_test_total", "A counter.")
    c.inc(2, tenant="acme")
    g = reg.gauge("avec_test_window", "A gauge.")
    g.set(7)
    h = reg.histogram("avec_test_latency_seconds", "A histogram.",
                      buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = reg.render()
    assert "# HELP avec_test_total A counter." in text
    assert "# TYPE avec_test_total counter" in text
    assert 'avec_test_total{tenant="acme"} 2' in text
    assert "# TYPE avec_test_window gauge" in text
    assert "avec_test_window 7" in text
    assert 'avec_test_latency_seconds_bucket{le="0.1"} 1' in text
    assert 'avec_test_latency_seconds_bucket{le="1"} 2' in text
    assert 'avec_test_latency_seconds_bucket{le="+Inf"} 2' in text
    assert "avec_test_latency_seconds_count 2" in text
    assert text.endswith("\n")
    # every non-comment line is `name[{labels}] value`
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert len(line.rsplit(" ", 1)) == 2


def test_metric_kind_mismatch_and_negative_counter():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("avec_x_total", "doc")
    with pytest.raises(ValueError):
        reg.gauge("avec_x_total", "doc")
    with pytest.raises(ValueError):
        reg.counter("avec_x_total", "doc").inc(-1)


def test_bound_views_read_at_scrape_time():
    reg = obs_metrics.MetricsRegistry()
    state = {"v": 1.0}
    reg.gauge("avec_view", "doc").bind(lambda: state["v"])
    assert reg.sample_values()["avec_view"] == 1.0
    state["v"] = 4.0
    assert reg.sample_values()["avec_view"] == 4.0


def test_executor_binds_tenant_and_window_views():
    ex = DestinationExecutor({"tiny": _tiny_library()})
    try:
        names = ex.metrics.names()
        assert "avec_tenant_drain_share" in names
        assert "avec_inflight_window" in names
        text = ex.metrics.render()
        assert 'avec_inflight_window{view="destination"} 0' in text
    finally:
        ex.shutdown()


def test_metrics_http_listener():
    reg = obs_metrics.MetricsRegistry()
    reg.gauge("avec_demo_gauge", "doc").set(3)
    srv = obs_metrics.MetricsServer(reg, port=0).start()
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            body = resp.read().decode()
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
        assert "avec_demo_gauge 3" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=5)
    finally:
        srv.stop()


def test_metrics_control_op_over_wire():
    ex = DestinationExecutor({"tiny": _tiny_library()}, name="m-dest")
    server = TCPServer(ex.handle).start()
    rt = HostRuntime(TCPChannel.connect("127.0.0.1", server.port))
    try:
        rt.put_model("fp-m", "tiny", {"w": np.zeros(1, np.float32)})
        rt.run("fp-m", "double", {"x": np.ones(2, np.float32)})
        reply = rt._rpc({"op": "metrics"})[0]
        assert reply["ok"]
        assert "# TYPE avec_tenant_drain_share gauge" in reply["exposition"]
        assert isinstance(reply["samples"], dict)
        assert 'avec_inflight_window{view="destination"}' in reply["samples"]
    finally:
        rt.close()
        server.stop()
        ex.shutdown()


def test_sanitizer_gauges_exported_only_when_enabled(monkeypatch):
    monkeypatch.delenv("AVEC_SANITIZE", raising=False)
    reg = obs_metrics.MetricsRegistry()
    obs_metrics.bind_sanitizer(reg)
    assert "avec_sanitizer_live_leases" not in reg.names()
    monkeypatch.setenv("AVEC_SANITIZE", "1")
    reg2 = obs_metrics.MetricsRegistry()
    obs_metrics.bind_sanitizer(reg2)
    vals = reg2.sample_values()
    assert "avec_sanitizer_live_leases" in vals
    assert "avec_sanitizer_lock_edges" in vals
    assert vals["avec_sanitizer_lock_edges"] >= 0


def test_frontend_bind_metrics():
    from repro.serving.engine import PipelinedOffloadFrontend
    ex = DestinationExecutor({"tiny": _tiny_library()}, name="fe-dest")
    server = TCPServer(ex.handle).start()
    rt = PipelinedHostRuntime(TCPChannel.connect("127.0.0.1", server.port))
    try:
        rt.put_model("fp-fe", "tiny", {"w": np.zeros(1, np.float32)})
        fe = PipelinedOffloadFrontend(rt, "fp-fe", "double")
        reg = obs_metrics.MetricsRegistry()
        fe.bind_metrics(reg, destination="fe-dest")
        fe.map({"r0": {"x": np.ones(2, np.float32)}})
        vals = reg.sample_values()
        key = 'avec_frontend_submitted_total{destination="fe-dest",op="double"}'
        assert vals[key] == 1.0
        assert 'avec_inflight_window{destination="fe-dest"}' in vals
    finally:
        rt.close()
        server.stop()
        ex.shutdown()


# ---------------------------------------------------------------------------
# tracing: one offloaded call -> one hop-span timeline
# ---------------------------------------------------------------------------

def _traced_session(rt_cls, **ex_kw):
    ex = DestinationExecutor({"tiny": _tiny_library()}, name="tr-dest",
                             **ex_kw)
    server = TCPServer(ex.handle).start()
    rt = rt_cls(TCPChannel.connect("127.0.0.1", server.port))
    sess = AvecSession({"arch": "tiny"}, {"w": np.zeros(1, np.float32)},
                       rt, "tiny")
    return ex, server, rt, sess


def test_trace_spans_over_pipelined_tcp_offload():
    obs_trace.get_sink().clear()
    ex, server, rt, sess = _traced_session(PipelinedHostRuntime)
    try:
        x = np.random.default_rng(0).standard_normal((256, 256)) \
            .astype(np.float32)
        sess.ensure_model()
        sess.call("double", {"x": x})       # warm: model resident, jit done
        t0 = time.perf_counter()
        out = sess.call("double", {"x": x})
        wall = time.perf_counter() - t0
        np.testing.assert_array_equal(out["y"], x * 2.0)
        tr = obs_trace.get_sink().last()
        assert tr is not None and tr.wall_s is not None
        names = tr.span_names()
        # the acceptance timeline: >= 5 named hop spans on the TCP path
        for hop in ("serialize", "send", "queue", "execute", "respond"):
            assert hop in names, f"missing hop span {hop!r} in {names}"
        assert len(names) >= 5
        # spans sum to the session wall by construction (respond is the
        # remainder) and the session wall must agree with an outer stopwatch
        assert tr.total_span_s() == pytest.approx(tr.wall_s, rel=1e-6)
        assert abs(tr.total_span_s() - wall) <= 0.10 * wall
    finally:
        rt.close()
        server.stop()
        ex.shutdown()


def test_trace_spans_on_sync_runtime():
    obs_trace.get_sink().clear()
    ex, server, rt, sess = _traced_session(HostRuntime)
    try:
        sess.call("double", {"x": np.ones((8, 8), np.float32)})
        tr = obs_trace.get_sink().last()
        names = tr.span_names()
        assert "serialize" in names and "respond" in names
        assert "queue" in names and "execute" in names
    finally:
        rt.close()
        server.stop()
        ex.shutdown()


def test_trace_coalesce_span_on_batched_path():
    obs_trace.get_sink().clear()
    ex = DestinationExecutor({"tiny": _tiny_library()}, name="co-dest",
                             coalesce=True, coalesce_window_s=0.005)
    rt = HostRuntime(avec.DirectChannel(ex))
    sess = AvecSession({"arch": "tiny"}, {"w": np.zeros(1, np.float32)},
                       rt, "tiny")
    try:
        # batchable rides the meta via qos-free direct call path
        sess.ensure_model()
        trace = obs_trace.start_trace(fn="double")
        out = rt.run(sess.fp, "double", {"x": np.ones(2, np.float32)},
                     batchable=True, trace=trace)
        obs_trace.finish_trace(trace, 0.1)
        np.testing.assert_array_equal(out["y"], np.full(2, 2.0))
        names = trace.span_names()
        assert "queue" in names and "coalesce" in names
        assert "execute" in names
        # the batch's timed parts divide per call; the request's unpack and
        # admission precede its enqueue, so queue carries no child here
        assert set(trace.children("execute")) == {"execute.dispatch",
                                                  "execute.device"}
        assert set(trace.children("respond")) >= {"respond.h2d",
                                                  "respond.d2h"}
        assert trace.children("queue") == {}
    finally:
        ex.shutdown()


CHILDREN = {"queue.unpack": "queue", "queue.admit": "queue",
            "execute.dispatch": "execute", "execute.device": "execute",
            "respond.h2d": "respond", "respond.d2h": "respond",
            "respond.unpack": "respond"}
MARKS = ("client.call", "client.sent", "dest.in", "dest.out", "client.recv",
         "client.return")


@pytest.mark.parametrize("rt_cls", [PipelinedHostRuntime, HostRuntime])
def test_trace_children_and_marks_on_one_timeline(rt_cls):
    obs_trace.get_sink().clear()
    ex, server, rt, sess = _traced_session(rt_cls)
    try:
        x = np.ones((64, 64), np.float32)
        sess.call("double", {"x": x})
        sess.call("double", {"x": x})
        tr = obs_trace.get_sink().last()
        hops = {s["name"]: s["dur_s"] for s in tr.spans if "parent" not in s}
        kids = [s for s in tr.spans if "parent" in s]
        assert {s["name"]: s["parent"] for s in kids} == CHILDREN
        m = tr.marks
        # a client's send that returns after the destination took the frame
        # (here: the destination's thread holding the GIL) overlaps the
        # destination's work, and the remainder respond is short by as much
        overlap = max(m["client.sent"] - m["dest.in"], 0) * 1e-9
        for hop, slack in (("queue", 0.0), ("execute", 0.0),
                           ("respond", overlap)):
            assert sum(tr.children(hop).values()) <= \
                hops[hop] + slack + 1e-9, hop
        # execute's children are its two halves, read off the same clocks
        assert sum(tr.children("execute").values()) == pytest.approx(
            hops["execute"], rel=1e-6)
        # children never add to the wall: the hops alone sum to it
        assert tr.total_span_s() == pytest.approx(tr.wall_s, rel=1e-6)
        assert sum(hops.values()) == pytest.approx(tr.wall_s, rel=1e-6)
        assert set(m) == set(MARKS)
        # the order causality fixes (the destination may read the frame
        # before the client's write returns, so sent vs dest.in is free)
        assert m["client.call"] <= m["client.sent"] <= m["client.recv"] \
            <= m["client.return"]
        assert m["client.call"] <= m["dest.in"] <= m["dest.out"] \
            <= m["client.recv"]
        assert tr.to_dict()["marks"] == m
    finally:
        rt.close()
        server.stop()
        ex.shutdown()


def test_a_sharded_call_keeps_children_inside_their_hops():
    parent = obs_trace.TraceRecord(fn="f")
    shard = obs_trace.TraceRecord(fn="f[0:2]")
    shard.add("execute", 0.004)
    shard.add("execute.device", 0.003, parent="execute")
    shard.mark("dest.in", 5)
    shard.finish(0.010)
    obs_trace.merge_sharded(parent, [shard])
    parent.add("stitch", 0.001)
    parent.finish(0.012)
    assert parent.children("execute") == {"execute.device": 0.003}
    assert parent.total_span_s() == pytest.approx(0.012)
    assert parent.marks == {"dest.in": 5}


def test_trace_disabled_is_zero_overhead_path(monkeypatch):
    monkeypatch.setenv("AVEC_TRACE_ENABLED", "0")
    assert obs_trace.start_trace(fn="x") is None
    assert obs_trace.finish_trace(None, 1.0) is None
    import jax.profiler
    annotations = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            annotations.append(name)
            super().__init__(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    for rt_cls in (HostRuntime, PipelinedHostRuntime):
        ex = DestinationExecutor({"tiny": _tiny_library()}, name="off-dest")
        replies = []

        def handle(raw, ex=ex):
            resp = ex.handle(raw)
            replies.append(unpack_message(bytes(resp))[0])
            return resp
        server = TCPServer(handle).start()
        rt = rt_cls(TCPChannel.connect("127.0.0.1", server.port))
        sess = AvecSession({"arch": "tiny"}, {"w": np.zeros(1, np.float32)},
                           rt, "tiny")
        try:
            before = obs_trace.get_sink().completed
            sess.call("double", {"x": np.ones(2, np.float32)})
            assert obs_trace.get_sink().completed == before
            run = [r for r in replies if "compute_s" in r]
            assert len(run) == 1
            for key in ("trace", "spans", "children", "marks", "compiles"):
                assert key not in run[0], key
            assert annotations == []
        finally:
            rt.close()
            server.stop()
            ex.shutdown()
    # the same path traced does annotate: the stub above is live
    monkeypatch.setenv("AVEC_TRACE_ENABLED", "1")
    ex, server, rt, sess = _traced_session(HostRuntime)
    try:
        sess.call("double", {"x": np.ones(2, np.float32)})
        assert "avec.call:double" in annotations
        assert "avec.execute.device" in annotations
    finally:
        rt.close()
        server.stop()
        ex.shutdown()


def test_compile_counter_counts_a_fresh_jit_and_names_the_call():
    import jax
    import jax.numpy as jnp

    from repro.core.executor import COMPILES
    n0, s0 = COMPILES.count, COMPILES.seconds
    jax.jit(lambda v: v * 3.0 + 1.0)(jnp.ones(7)).block_until_ready()
    assert COMPILES.count >= n0 + 1 and COMPILES.seconds > s0

    def fresh(params, state, args):
        # a new jit on the first call only: that dispatch compiles
        if "f" not in state:
            state["f"] = jax.jit(lambda v: jnp.sin(v) * 5.0 - 2.0)
        return {"y": state["f"](args["x"])}
    ex = DestinationExecutor({"tiny": {"fresh": fresh}}, name="cc-dest")
    server = TCPServer(ex.handle).start()
    rt = HostRuntime(TCPChannel.connect("127.0.0.1", server.port))
    sess = AvecSession({"arch": "tiny"}, {"w": np.zeros(1, np.float32)},
                       rt, "tiny")
    try:
        obs_trace.get_sink().clear()
        sess.call("fresh", {"x": np.ones(5, np.float32)})
        assert obs_trace.get_sink().last().compiles >= 1
        sess.call("fresh", {"x": np.ones(5, np.float32)})
        assert obs_trace.get_sink().last().compiles == 0
        vals = ex.metrics.sample_values()
        assert vals["avec_backend_compiles_total"] == COMPILES.count
        assert vals["avec_backend_compile_seconds_total"] == pytest.approx(
            COMPILES.seconds)
    finally:
        rt.close()
        server.stop()
        ex.shutdown()


def test_emit_structured_log_line(capsys):
    obs_trace.emit("unit_event", port=9000, note="hi")
    line = capsys.readouterr().out.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["event"] == "unit_event"
    assert rec["port"] == 9000 and rec["note"] == "hi"
    assert "ts" in rec


# ---------------------------------------------------------------------------
# launch satellite: XLA_FLAGS append (not clobber)
# ---------------------------------------------------------------------------

def test_dryrun_appends_xla_flags():
    code = ("import os; import repro.launch.dryrun; "
            "print(os.environ['XLA_FLAGS'])")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True,
        env={**os.environ, "PYTHONPATH": SRC,
             "XLA_FLAGS": "--xla_dump_to=/tmp/keepme"}).stdout
    assert "--xla_dump_to=/tmp/keepme" in out
    assert "--xla_force_host_platform_device_count=512" in out
