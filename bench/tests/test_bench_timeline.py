"""The call timeline: the four readers of the program's marks and span
children, and the placing of program spans on the profiler's clock
(bench/timeline.py), on hand-built calls and planes, on calls served through
the program on the CPU, and on a trace the CPU profiler recorded."""
import time
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness
from bench import timeline as tl
from bench import trace_reduce as trc

READERS = ("dest_host_ms.decode", "flight_ms.decode", "client_ms.decode",
           "idle_in_call_pct")


def read(name, ctx):
    return harness.metric_reader(name).read(ctx)


def _marks(t0, pre, await_, post, d_in, d_out):
    """Six marks of a call starting at ``t0`` ns; dest marks are offsets
    from ``client.sent``."""
    sent = t0 + pre
    return {"client.call": t0, "client.sent": sent,
            "dest.in": sent + d_in, "dest.out": sent + d_out,
            "client.recv": sent + await_,
            "client.return": sent + await_ + post}


def _call(fn, marks, device_s, wall_s=0.0):
    return {"fn": fn, "n": 1, "wall_s": wall_s, "spans": {},
            "marks": marks, "children": {"execute.device": device_s}}


# decode calls: 0.3 ms before the send, 3 ms awaited, 0.2 ms after; the
# destination holds each from 0.4 to 2.6 ms after the send, 1.5 ms of it
# waiting on the chip
CALLS = [_call("decode", _marks(i * 4_000_000, 300_000, 3_000_000, 200_000,
                                400_000 + 1000 * i, 2_600_000), 0.0015)
         for i in range(5)] + [
    _call("prefill", _marks(30_000_000, 1_000_000, 9_000_000, 100_000,
                            500_000, 8_500_000), 0.007)]


def _ctx(calls, trace=None, window_s=0.040):
    return {"calls": calls, "window_s": window_s,
            "trace": trace or {"busy_s": 0.010, "module_s": {}}}


def test_host_flight_and_client_shares_of_a_decode_call():
    ctx = _ctx(CALLS)
    # residence 2.2 ms (the middle call) less 1.5 ms on the chip
    assert read("dest_host_ms.decode", ctx) == pytest.approx(
        2.2 - 0.002 - 1.5)
    # 3 ms awaited less the 2.2 ms residence
    assert read("flight_ms.decode", ctx) == pytest.approx(3.0 - 2.198)
    # 3.5 ms in the session's call less the 3 ms awaited
    assert read("client_ms.decode", ctx) == pytest.approx(0.5)


def test_idle_in_calls_from_the_residences():
    # five decode residences of 2.2 ms and a prefill of 8 ms, disjoint;
    # the chip busy 10 ms of them in a 40 ms window
    held = 5 * 2.2e-3 - 1e-9 * sum(1000 * i for i in range(5)) + 8e-3
    assert read("idle_in_call_pct", _ctx(CALLS)) == pytest.approx(
        100 * (held - 0.010) / 0.040)
    # a chip busier than the residences breaks the reading: nothing is read
    tr = {"busy_s": held + 1e-3, "module_s": {}}
    assert read("idle_in_call_pct", _ctx(CALLS, tr)) is None


def test_overlapping_residences_count_once():
    a = _call("decode", _marks(0, 0, 10, 0, 0, 10), 0.0)
    b = _call("decode", _marks(5, 0, 10, 0, 0, 10), 0.0)
    from bench.metrics import _timeline
    assert _timeline.held_s(_ctx([a, b])) == pytest.approx(15e-9)


@pytest.mark.parametrize("name", READERS)
def test_a_timeline_reader_with_nothing_to_read_returns_nothing(
        name, monkeypatch):
    # no calls, calls without marks (a program that stamps none), or a
    # trace in which the chip never ran: the metric is left out
    from repro.obs import trace
    monkeypatch.setattr(trace, "_SINK", trace.TraceSink())
    assert read(name, _ctx([], {"busy_s": 0.0, "module_s": {}})) is None
    bare = [{"fn": "decode", "n": 1, "wall_s": 1.0, "spans": {}}]
    assert read(name, _ctx(bare)) is None


def test_readers_find_the_program_s_records_in_the_sink(monkeypatch):
    """bench/run.py hands the readers ``{fn, n, wall_s, spans}``: the marks
    and children come from the session's own records, found by wall."""
    from repro.obs import trace
    monkeypatch.setattr(trace, "_SINK", trace.TraceSink())
    calls = []
    for i, c in enumerate(CALLS):
        rec = trace.TraceRecord(fn=c["fn"])
        rec.add("execute", 0.002)
        rec.add("execute.device", c["children"]["execute.device"],
                parent="execute")
        rec.marks.update(c["marks"])
        trace.finish_trace(rec, 0.004 + 1e-6 * i)
        calls.append({"fn": c["fn"], "n": 1, "wall_s": rec.wall_s,
                      "spans": {"execute": 0.002}})
    for name in READERS:
        assert read(name, _ctx(calls)) == pytest.approx(read(name,
                                                             _ctx(CALLS)))
    # records with no marks (a program from before them): nothing to read
    monkeypatch.setattr(trace, "_SINK", trace.TraceSink())
    for c in calls:
        rec = trace.TraceRecord(fn=c["fn"])
        rec.marks = {}
        trace.finish_trace(rec, c["wall_s"])
    for name in READERS:
        assert read(name, _ctx(calls)) is None


def test_readers_on_calls_served_through_the_program(monkeypatch):
    """A traced session over TCP on the CPU: every reader finds its
    parts."""
    from repro.core.executor import DestinationExecutor, PipelinedHostRuntime
    from repro.core.interception import AvecSession
    from repro.core.transport import TCPChannel, TCPServer
    from repro.obs import trace
    monkeypatch.setenv("AVEC_TRACE_ENABLED", "1")
    monkeypatch.setattr(trace, "_SINK", trace.TraceSink())

    def decode(params, state, args):
        return {"y": np.asarray(args["x"]) + 1.0}
    ex = DestinationExecutor({"lm": {"decode": decode}}, name="tl-dest")
    server = TCPServer(ex.handle).start()
    rt = PipelinedHostRuntime(TCPChannel.connect("127.0.0.1", server.port))
    sess = AvecSession({"arch": "tl"}, {"w": np.zeros(1, np.float32)}, rt,
                       "lm")
    try:
        for _ in range(6):
            sess.call("decode", {"x": np.ones(4, np.float32)})
            time.sleep(0.001)
    finally:
        rt.close()
        server.stop()
        ex.shutdown()
    recs = trace.get_sink().recent(100)
    calls = [{"fn": t.fn, "n": 1, "wall_s": t.wall_s, "spans": {}}
             for t in recs]
    ctx = _ctx(calls, {"busy_s": 1e-6, "module_s": {}}, window_s=1.0)
    assert len(recs) == 6
    for name in READERS:
        assert read(name, ctx) is not None, name
    # the session's call holds its wait, the residence holds the chip's
    for name in ("dest_host_ms.decode", "client_ms.decode",
                 "idle_in_call_pct"):
        assert read(name, ctx) >= 0.0, name


# ---------------------------------------------------------------------------
# bench/timeline.py: program spans on the profiler's clock
# ---------------------------------------------------------------------------

def _plane(name, lines, stats=()):
    return NS(name=name, stats=list(stats), lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs])
        for ln, evs in lines.items()])


OPS = [("fusion.1", 10, 20), ("fusion.2", 100, 10), ("fusion.3", 300, 50)]


def test_idle_in_calls_counts_overlapping_annotations_once():
    calls = [("avec.call:decode", 0, 60), ("avec.call:decode", 40, 80),
             ("avec.call:prefill", 280, 100)]
    # union [0, 120) and [280, 380): 220 ns held, 20 + 10 + 50 busy in it
    assert tl.idle_in_call_ns(OPS, calls) == 220 - 80
    assert tl.idle_in_call_ns(OPS, []) == 0
    # an operation that runs past a call's end counts only inside it
    assert tl.idle_in_call_ns([("f", 50, 100)], [("avec.call:d", 0, 100)]) \
        == 50


def test_gaps_are_named_by_a_program_span_before_a_runtime_event():
    program = [("avec.call:decode", 0, 120), ("avec.respond.d2h", 28, 75),
               ("client.app", 130, 160)]
    runtime = [("np.asarray(jax.Array)", 25, 80),
               ("PjitFunction(_decode)", 110, 200)]
    gaps = tl.name_gaps(OPS, program, runtime)
    # [110, 300): the client between calls; [30, 100): the innermost of the
    # two destination spans that cover it
    assert gaps == [["client.app", pytest.approx(190e-9)],
                    ["avec.respond.d2h", pytest.approx(70e-9)]]
    # where no program span overlaps, today's runtime event names the gap
    assert tl.name_gaps(OPS, [], runtime)[1][0] == "np.asarray(jax.Array)"


def test_every_gap_is_classed_inside_or_between_calls_and_named():
    calls = [("avec.call:decode", 0, 105), ("avec.call:decode", 95, 20),
             ("avec.call:decode", 290, 70)]
    program = calls + [("avec.execute.dispatch", 25, 80),
                       ("client.app", 120, 160)]
    out = tl.gap_summary(OPS, calls, program, [])
    # [30, 100) inside the joined calls [0, 115); [110, 300) is not
    assert out["inside"] == {"gaps": 1, "s": pytest.approx(70e-9),
                             "by_name": {"avec.execute.dispatch":
                                         [1, pytest.approx(70e-9)]}}
    assert out["between"]["gaps"] == 1
    assert list(out["between"]["by_name"]) == ["client.app"]
    # a gap that no span of either kind overlaps is still counted
    bare = tl.gap_summary(OPS, [], [], [])
    assert bare["between"]["by_name"] == {"no host event": [2, pytest.approx(
        260e-9)]}


def test_client_intervals_cut_at_the_marks():
    start = 1_000_000
    m = [_marks(start + 100, 10, 50, 5, 20, 40),
         _marks(start + 400, 10, 50, 5, 20, 40)]
    iv = tl.client_intervals(m, start + 900, start)
    assert iv[:4] == [("client.pre", 100.0, 10.0),
                      ("client.await", 110.0, 50.0),
                      ("client.post", 160.0, 5.0),
                      ("client.app", 165.0, 235.0)]
    assert iv[-1] == ("client.app", 465.0, 435.0)


def test_reduce_timeline_on_hand_built_planes():
    start = 5_000_000_000
    dev = _plane(f"{trc.DEVICE_PREFIX}0", {trc.OPS_LINE: OPS})
    host = _plane(trc.HOST_PLANE, {"python": [
        ("avec.call:decode", 0, 130), ("avec.execute.device", 5, 30),
        ("np.asarray(jax.Array)", 120, 190)]})
    env = _plane(tl.ENV_PLANE, {}, [(tl.START_STAT, start)])
    marks = [_marks(start - 10, 5, 150, 5, 10, 140)]
    out = tl.reduce_timeline([dev, host, env], marks, start + 250)
    assert out["calls"] == 1 and out["profile_start_ns"] == start
    assert out["idle_in_call_s"] == pytest.approx((130 - 30) * 1e-9)
    assert out["idle_gaps"][0][0] == "client.app"
    # [30, 100) lies inside the call, [110, 300) reaches past it
    assert out["gaps"]["inside"]["gaps"] == 1
    assert out["gaps"]["between"]["by_name"] == {
        "client.app": [1, pytest.approx(190e-9)]}
    with pytest.raises(ValueError, match="no device plane"):
        tl.reduce_timeline([host, env], marks, start + 250)


def test_a_client_mark_lands_on_its_annotation_in_a_cpu_trace(tmp_path):
    """A ``time.time_ns()`` interval, mapped through the trace's
    ``profile_start_time``, lands within 1 ms of the annotation it
    brackets."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        time.sleep(0.005)
        t0 = time.time_ns()
        with jax.profiler.TraceAnnotation("avec.call:decode"):
            time.sleep(0.003)
        t1 = time.time_ns()
    finally:
        jax.profiler.stop_trace()
    planes = list(ProfileData.from_file(
        trc.find_xplane(str(tmp_path))).planes)
    start = tl.profile_start_ns(planes)
    host = tl.host_events(p for p in planes if p.name == trc.HOST_PLANE)
    ann = [e for e in host if e[0] == "avec.call:decode"]
    assert start is not None and len(ann) == 1
    _, s, d = ann[0]
    assert abs((t0 - start) - s) < 1e6
    assert abs((t1 - start) - (s + d)) < 1e6


def test_token_gap_parts_tile_the_gap_between_returns():
    from bench import timeline_cell
    marks = [_marks(i * 4_000_000, 300_000, 3_000_000, 200_000, 400_000,
                    2_600_000) for i in range(3)]
    kids = [{"execute.device": 0.0015}] * 3
    parts = timeline_cell.token_gap_parts(marks, kids,
                                          ["prefill", "decode", "decode"])
    assert parts == pytest.approx({"app": 0.5, "client": 0.5, "flight": 0.8,
                                   "dest_host": 0.7, "device": 1.5})
    # the parts add up to client.return -> client.return
    assert sum(parts.values()) == pytest.approx(4.0)
    # no decode call after another call: nothing to cut
    assert timeline_cell.token_gap_parts(marks[:1], kids[:1],
                                         ["decode"]) == {}
