"""Arithmetic the call-timeline readers share.

A traced call of the program (``repro.obs.trace``) carries six absolute
``time.time_ns()`` marks, each pair read on one host's clock:
``client.call`` / ``client.return`` (the session's ``call``),
``client.sent`` / ``client.recv`` (request written, response in hand) and
``dest.in`` / ``dest.out`` (the destination's residence), and span
children such as ``execute.device`` (the wait for the chip).

A call in ``ctx["calls"]`` may hold them as ``marks`` ({name: ns}) and
``children`` ({name: seconds}).  Where it holds neither, as ``call_spans``
in bench/run.py gives it, they are read from the program's own record of
the call in this process's trace sink, found by function and wall.  A
program whose records carry no marks gives nothing to read.
"""
from __future__ import annotations

import numpy as np

MARKS = ("client.call", "client.sent", "dest.in", "dest.out", "client.recv",
         "client.return")


def _sink_records() -> dict:
    try:
        from repro.obs import trace
    except ImportError:
        return {}
    return {(t.fn, t.wall_s): t for t in trace.get_sink().recent(10 ** 9)}


def timeline(ctx: dict, fn: str | None = None) -> list[tuple[dict, dict]]:
    """(marks, children) of every ``fn`` call (every call when ``fn`` is
    None) that carries all six marks."""
    calls = [c for c in ctx["calls"] if fn is None or c["fn"] == fn]
    records = None
    out = []
    for c in calls:
        marks, children = c.get("marks"), c.get("children")
        if marks is None:
            if records is None:
                records = _sink_records()
            rec = records.get((c["fn"], c["wall_s"]))
            marks = getattr(rec, "marks", None)
            children = {}
            for s in getattr(rec, "spans", ()):
                if "parent" in s:
                    children[s["name"]] = (children.get(s["name"], 0.0)
                                           + s["dur_s"])
        if marks and all(k in marks for k in MARKS):
            out.append((marks, children or {}))
    return out


def _ms(ns: float) -> float:
    return ns * 1e-6


def dest_host_ms(ctx: dict, fn: str):
    """Median over ``fn`` calls of the destination's residence
    (``dest.out - dest.in``) less its wait for the chip
    (``execute.device``): unpack, admission, argument copy, the library's
    Python and dispatch, output copy, in milliseconds."""
    rest = [_ms(m["dest.out"] - m["dest.in"]) - 1e3 * k["execute.device"]
            for m, k in timeline(ctx, fn) if "execute.device" in k]
    return float(np.median(rest)) if rest else None


def flight_ms(ctx: dict, fn: str):
    """Median over ``fn`` calls of (``client.recv - client.sent``) less the
    residence (``dest.out - dest.in``): both flights, the destination's
    frame read and response pack and write.  Each difference is taken on
    one host's clock, so it holds across hosts too."""
    rest = [_ms((m["client.recv"] - m["client.sent"])
                - (m["dest.out"] - m["dest.in"]))
            for m, _ in timeline(ctx, fn)]
    return float(np.median(rest)) if rest else None


def client_ms(ctx: dict, fn: str):
    """Median over ``fn`` calls of the session's call
    (``client.return - client.call``) less the wait for the response
    (``client.recv - client.sent``): facade, session, serialize, send,
    unpack, in milliseconds."""
    rest = [_ms((m["client.return"] - m["client.call"])
                - (m["client.recv"] - m["client.sent"]))
            for m, _ in timeline(ctx, fn)]
    return float(np.median(rest)) if rest else None


def held_s(ctx: dict):
    """Seconds in which the destination held at least one call of the
    window: the union of the ``dest.in`` .. ``dest.out`` intervals."""
    from bench import trace_reduce
    calls = [("", m["dest.in"], m["dest.out"] - m["dest.in"])
             for m, _ in timeline(ctx)]
    return trace_reduce.union_ns(calls) * 1e-9 if calls else None
