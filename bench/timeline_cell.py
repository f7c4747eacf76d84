"""One traced window of a benchmark cell on one timeline, from the edge
client's marks to the chip's operations.

    python3 bench/timeline_cell.py --workload <cell> --seed <n> [--pairs <k>]

Not a benchmark run: a tool that reads what ``bench/run.py --trace 1``
cannot yet, because its destination deletes the profiler's trace before
the client's marks reach it.  The cell is set up as ``bench/run.py`` sets
it up (the same destination, weights, client and warm-up), but the
destination runs as ``--serve`` here, where ``window_end`` takes the
client's marks and reduces the kept trace with them (``bench/timeline.py``)
before deleting it.  Windows last ``bench/run.py``'s ``TRACE_SECONDS``.
With ``--pairs k`` it first runs k pairs of windows with the program's
tracing off and on (profiler off), for what tracing costs.  Then one
window with tracing and the profiler on, and one JSON line on stdout:

  metrics      every per-layer metric of the cell, as a traced run reads it
  idle_in_call_pct_annotations
               the same share from the ``avec.call:*`` annotations
  gaps         every idle gap of the chip, inside a call or between calls,
               by the program span that names it
  token_gap_ms the mean decode token gap cut into five parts, beside the
               mean gap the client measured
  decode_spans_ms
               mean span and child times of a decode call
  module_ms_per_call
               device time of each library function's program a call
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import run  # noqa: E402  (before JAX_PLATFORMS is pinned below)

PARTS = ("app", "client", "flight", "dest_host", "device")


# ---------------------------------------------------------------------------
# the destination: bench/server.py with a window_end that keeps the trace
# until the client's marks have placed it
# ---------------------------------------------------------------------------

def serve(argv) -> int:
    import shutil

    import jax
    from jax.profiler import ProfileData

    from bench import server, timeline, trace_reduce

    class TimelineServer(server.Server):
        def window_end(self, msg: dict) -> dict:
            if self.trace_dir is None or "marks" not in msg:
                return super().window_end(msg)
            out = {"compiles": self.watch.compiles - self.mark,
                   "peak_bytes": server.peak_bytes(self.dev),
                   "window_s": time.monotonic() - self.t_trace0}
            jax.profiler.stop_trace()
            try:
                out["trace"] = trace_reduce.reduce_trace(self.trace_dir,
                                                         self.args.chips)
                planes = ProfileData.from_file(
                    trace_reduce.find_xplane(self.trace_dir)).planes
                out["timeline"] = timeline.reduce_timeline(
                    planes, msg["marks"], msg["window_end_ns"])
            finally:
                shutil.rmtree(self.trace_dir, ignore_errors=True)
                self.trace_dir = None
            return out

    server.Server = TimelineServer
    return server.main(argv)


# ---------------------------------------------------------------------------
# the edge client
# ---------------------------------------------------------------------------

def token_gap_parts(marks: list[dict], children: list[dict], fns) -> dict:
    """Mean milliseconds of the five parts of each decode call's token gap,
    ``client.return`` of the call before it to its own: the application
    between the calls, the client (its call less the wait), the flight
    (the wait less the residence), the destination's host time (the
    residence less ``execute.device``) and ``execute.device``.  ``marks``,
    ``children`` and ``fns`` are per call, in call order."""
    acc = {p: [] for p in PARTS}
    for i in range(1, len(marks)):
        m, prev, k = marks[i], marks[i - 1], children[i]
        if fns[i] != "decode" or "execute.device" not in k:
            continue
        wait = m["client.recv"] - m["client.sent"]
        held = m["dest.out"] - m["dest.in"]
        dev = k["execute.device"] * 1e9
        for p, ns in zip(PARTS, (m["client.call"] - prev["client.return"],
                                 m["client.return"] - m["client.call"] - wait,
                                 wait - held, held - dev, dev)):
            acc[p].append(ns * 1e-6)
    return {p: statistics.fmean(v) for p, v in acc.items() if v}


def _window(client, traffic, seconds: float) -> dict:
    records, t0 = run.run_window(client, traffic, seconds)
    e2e, _, _ = run.end_to_end(records, t0, seconds)
    gaps = [b - a for r in records
            for a, b in zip(r["times"], r["times"][1:])]
    e2e["tbt_mean_ms"] = statistics.fmean(gaps) * 1e3 if gaps else None
    return {"records": records, "t0": t0, "e2e": e2e}


def measure(args) -> dict:
    from bench import flops, harness
    from repro.obs import trace as tr
    from repro.obs.config import global_config

    bench, cell, c, _, traffic, srv, client = run.setup(
        args, server_cmd=[sys.executable, os.path.abspath(__file__),
                          "--serve"])
    try:
        onoff = []
        for _ in range(args.pairs):
            for traced in (False, True):
                global_config().set("trace_enabled", traced)
                w = _window(client, traffic, run.TRACE_SECONDS)
                onoff.append({"trace": traced, **w["e2e"]})
        global_config().set("trace_enabled", True)
        tr._SINK = tr.TraceSink(capacity=10 ** 7)
        client.calls, client.record = [], True
        srv.ask("window_start", trace=True)
        w = _window(client, traffic, run.TRACE_SECONDS)
        client.record = False
        end_ns = time.time_ns()
        by_id = {t.call_id: t for t in tr.get_sink().recent(10 ** 9)}
        recs = [by_id[cid] for cid, _, _ in client.calls if cid in by_id]
        end = srv.ask("window_end", marks=[t.marks for t in recs],
                      window_end_ns=end_ns)
    finally:
        client.close()
        srv.close()
    ctx = {"c": c, "calls": run.call_spans(client), "trace": end["trace"],
           "window_s": end["window_s"],
           "peak": flops.peaks(srv.ready["device"]["kind"])}
    metrics = {}
    for spec in harness.per_layer_for(bench, cell["name"]):
        metrics[spec["name"]] = harness.metric_reader(spec["name"]).read(ctx)
    spans: dict[str, list] = {}
    children = []
    for t in recs:
        kids = {}
        for s in t.spans:
            if "parent" in s:
                kids[s["name"]] = kids.get(s["name"], 0.0) + s["dur_s"]
            if t.fn == "decode":
                spans.setdefault(s["name"], []).append(s["dur_s"] * 1e3)
        children.append(kids)
    parts = token_gap_parts([t.marks for t in recs], children,
                            [t.fn for t in recs])
    tl = end["timeline"]
    return {
        "cell": cell["name"], "seed": args.seed,
        "device": srv.ready["device"], "onoff": onoff,
        "e2e_traced": w["e2e"], "metrics": metrics,
        "idle_in_call_pct_annotations":
            100.0 * tl["idle_in_call_s"] / end["window_s"],
        "device_idle_pct_window": 100.0 * (1 - end["trace"]["busy_s"]
                                           / end["window_s"]),
        "gaps": tl["gaps"], "idle_gaps": tl["idle_gaps"],
        "token_gap_ms": {**parts, "sum": sum(parts.values()),
                         "measured_mean": w["e2e"]["tbt_mean_ms"]},
        "decode_spans_ms": {k: statistics.fmean(v) for k, v in spans.items()},
        "module_ms_per_call": {
            fn: 1e3 * t / end["trace"]["module_count"][fn]
            for fn, t in end["trace"]["module_s"].items()
            if end["trace"]["module_count"].get(fn)},
        "decode_compiles": sum(t.compiles for t in recs
                               if t.fn == "decode"),
        "compiles_in_window": end["compiles"],
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--serve"]:
        return serve(argv[1:])
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=0,
                    help="untraced/traced window pairs before the profiled "
                         "window (profiler off)")
    args = ap.parse_args(argv)
    args.trace = 1
    # the edge side never takes the chip, as in bench/run.py
    os.environ["JAX_PLATFORMS"] = "cpu"
    from bench import harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.COMPILE_CACHE
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["AVEC_TRACE_ENABLED"] = "1"
    print(json.dumps(measure(args)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
