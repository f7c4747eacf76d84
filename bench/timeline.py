"""Program spans on the profiler's clock: every idle stretch of the chip
placed in a layer of the program.

The destination wraps each traced call in profiler annotations
(``repro.core.executor``): ``avec.call:<fn>`` around its residence and
``avec.<child>`` around each part (``avec.execute.device``,
``avec.respond.d2h`` ...).  They are events of the trace's host plane, on
the trace's clock: nanoseconds since the profile started.  The edge client
runs in its own process, outside the trace; its marks are
``time.time_ns()`` readings, and the plane ``Task Environment`` carries
``profile_start_time`` on that clock (epoch nanoseconds), so
``mark - profile_start_time`` places a client interval on the trace's
clock, to within tens of microseconds.

Per call the client's time is cut at its marks into four intervals:
``client.pre`` (call -> sent), ``client.await`` (sent -> recv),
``client.post`` (recv -> return) and ``client.app`` (return -> the next
call, or the end of the window).

* idle in calls: the stretch of the union of the ``avec.call:*`` events in
  which no device operation ran (overlapping calls counted once);
* idle gaps: the longest gaps between busy intervals, each named by the
  program span (destination annotation or client interval) that overlaps
  it most, the shorter on a tie; only a gap that no program span overlaps
  falls back to the runtime's own host event.
"""
from __future__ import annotations

import bisect

from bench import trace_reduce as trc

PROGRAM_PREFIX = "avec."
CALL_PREFIX = "avec.call:"
ENV_PLANE = "Task Environment"
START_STAT = "profile_start_time"


def profile_start_ns(planes) -> int | None:
    """The profile's start, epoch nanoseconds (``time.time_ns()``'s clock)."""
    for plane in planes:
        if plane.name == ENV_PLANE:
            for name, value in plane.stats:
                if name == START_STAT:
                    return int(value)
    return None


def host_events(host_planes) -> list[tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for p in host_planes for line in p.lines for e in line.events]


def program_events(events) -> list[tuple[str, float, float]]:
    """The program's own annotations among the host plane's events."""
    return [e for e in events if e[0].startswith(PROGRAM_PREFIX)]


def client_intervals(marks: list[dict], window_end_ns: int,
                     start_ns: int) -> list[tuple[str, float, float]]:
    """The client's four intervals of every call (``marks``: one dict of
    ``time.time_ns()`` marks per call), on the trace's clock."""
    calls = sorted(marks, key=lambda m: m["client.call"])
    out = []
    for i, m in enumerate(calls):
        nxt = (calls[i + 1]["client.call"] if i + 1 < len(calls)
               else window_end_ns)
        for name, a, b in (("client.pre", "client.call", "client.sent"),
                           ("client.await", "client.sent", "client.recv"),
                           ("client.post", "client.recv", "client.return")):
            out.append((name, float(m[a] - start_ns), float(m[b] - m[a])))
        out.append(("client.app", float(m["client.return"] - start_ns),
                    float(max(nxt - m["client.return"], 0))))
    return out


def idle_in_call_ns(op_events, call_events) -> float:
    """Nanoseconds of the calls' union in which no operation ran."""
    calls = trc.busy_intervals(call_events)
    busy = trc.busy_intervals(op_events)
    held = sum(b - a for a, b in calls)
    inside, j = 0.0, 0
    for a, b in calls:
        while j < len(busy) and busy[j][1] <= a:
            j += 1
        k = j
        while k < len(busy) and busy[k][0] < b:
            inside += min(b, busy[k][1]) - max(a, busy[k][0])
            k += 1
    return held - inside


class _Namer:
    """Names a stretch by the event that overlaps it most, the shorter on a
    tie (events spanning half the trace or more name none)."""

    def __init__(self, events, span: float) -> None:
        evs = sorted((e for e in events if e[2] < 0.5 * span),
                     key=lambda e: e[1])
        self.events = evs
        self.starts = [e[1] for e in evs]
        self.longest = max((e[2] for e in evs), default=0.0)

    def __call__(self, g0: float, g1: float):
        best, name = (0.0, 0.0), None
        lo = bisect.bisect_left(self.starts, g0 - self.longest)
        hi = bisect.bisect_left(self.starts, g1)
        for en, es, ed in self.events[lo:hi]:
            ov = min(g1, es + ed) - max(g0, es)
            if ov > 0 and (ov, -ed) > best:
                best, name = (ov, -ed), en
        return name


def _gaps(op_events) -> tuple[list[tuple[float, float]], float]:
    """Every gap between busy intervals, and the busy span."""
    iv = trc.busy_intervals(op_events)
    gaps = [(b0[1], b1[0]) for b0, b1 in zip(iv, iv[1:]) if b1[0] > b0[1]]
    return gaps, (iv[-1][1] - iv[0][0]) if iv else 0.0


def _namer(program, host, span: float):
    by_program, by_host = _Namer(program, span), _Namer(host, span)
    return lambda g0, g1: (by_program(g0, g1) or by_host(g0, g1)
                           or "no host event")


def name_gaps(op_events, program, host, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps, each named by the program span that
    overlaps it most, else by the host event that does."""
    gaps, span = _gaps(op_events)
    name = _namer(program, host, span)
    return [[name(g0, g1), (g1 - g0) * 1e-9]
            for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:n]]


def gap_summary(op_events, call_events, program, host) -> dict:
    """Every idle gap, named as :func:`name_gaps` names it, under
    ``inside`` (wholly inside the union of the calls) or ``between``:
    per class the gaps, their seconds and, by name, [gaps, seconds]."""
    gaps, span = _gaps(op_events)
    name = _namer(program, host, span)
    calls = trc.busy_intervals(call_events)
    ends = [b for _, b in calls]
    out = {c: {"gaps": 0, "s": 0.0, "by_name": {}}
           for c in ("inside", "between")}
    for g0, g1 in gaps:
        k = bisect.bisect_right(ends, g0)
        inside = k < len(calls) and calls[k][0] <= g0 and g1 <= calls[k][1]
        cls = out["inside" if inside else "between"]
        dur = (g1 - g0) * 1e-9
        cls["gaps"] += 1
        cls["s"] += dur
        entry = cls["by_name"].setdefault(name(g0, g1), [0, 0.0])
        entry[0] += 1
        entry[1] += dur
    return out


def reduce_timeline(planes, marks: list[dict], window_end_ns: int,
                    n_gaps: int = 10) -> dict:
    """``idle_in_call_s``, the ``n_gaps`` longest ``idle_gaps`` named, every
    gap by class and name (``gaps``) and the number of ``calls`` of one
    trace (the first device plane's operations), with the client's
    ``marks`` (one dict per call) placed on its clock."""
    planes = list(planes)
    dev = sorted((p for p in planes if p.name.startswith(trc.DEVICE_PREFIX)),
                 key=lambda p: p.name)
    if not dev:
        raise ValueError("the trace holds no device plane")
    ops = (trc.line_events(dev[0], trc.OPS_LINE)
           or trc.line_events(dev[0], trc.MODULES_LINE))
    host = host_events(p for p in planes if p.name == trc.HOST_PLANE)
    dest = program_events(host)
    start = profile_start_ns(planes)
    client = ([] if start is None else
              client_intervals(marks, window_end_ns, start))
    calls = [e for e in dest if e[0].startswith(CALL_PREFIX)]
    return {
        "idle_in_call_s": idle_in_call_ns(ops, calls) * 1e-9,
        "idle_gaps": name_gaps(ops, dest + client, host, n_gaps),
        "gaps": gap_summary(ops, calls, dest + client, host),
        "calls": len(calls),
        "profile_start_ns": start,
    }
