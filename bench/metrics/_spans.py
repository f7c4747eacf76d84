"""Arithmetic the span-based readers share.  A reader gets ``ctx`` with
``calls``: one dict per call made in the traced window, ``{"fn", "n",
"wall_s", "spans": {name: seconds}}``, the spans being the program's own
(``repro.obs.trace``: serialize, send, queue, coalesce, execute, respond);
``trace``: the reduced profiler trace (``bench/trace_reduce.py``:
``busy_s``, ``module_s``, ``module_count`` and ``scope_s`` by library
function, ``device_ops``, ``idle_gaps``); ``counters``: the growth in the
window of each of the destination's counters, by its ``/metrics`` name;
``window_s``; ``peak``, the chip's row of ``bench/peaks.json``; and ``c``,
the configuration file."""
from __future__ import annotations

import numpy as np

DEST_SPANS = ("queue", "coalesce", "execute")


def of(ctx: dict, fn: str) -> list[dict]:
    return [c for c in ctx["calls"] if c["fn"] == fn]


def wire_ms(ctx: dict, fn: str):
    """Median over ``fn`` calls of wall minus the destination's queue,
    coalesce and execute spans: serialization, the wire both ways, the
    host runtime and the device-to-host copy, in milliseconds."""
    calls = of(ctx, fn)
    if not calls:
        return None
    rest = [c["wall_s"] - sum(c["spans"].get(s, 0.0) for s in DEST_SPANS)
            for c in calls]
    return float(np.median(rest)) * 1e3


def mfu_pct(ctx: dict, fn: str):
    """The operations ``fn`` calls need (bench/flops.py) over their summed
    ``execute`` spans times the chip's peak, in percent."""
    from bench import flops
    calls = of(ctx, fn)
    busy = sum(c["spans"].get("execute", 0.0) for c in calls)
    if not calls or busy <= 0:
        return None
    ops = sum(flops.cost(ctx["c"], fn, c["n"])[0] for c in calls)
    return 100.0 * ops / (busy * ctx["peak"]["bf16_flops_per_s"])


def roofline_pct(ctx: dict, fn: str):
    """Roofline time of every ``fn`` call in the traced window over the
    device time of the program ``fn`` runs (its XLA module), in percent."""
    from bench import flops
    calls = of(ctx, fn)
    dev_s = ctx["trace"]["module_s"].get(fn, 0.0)
    if not calls or dev_s <= 0:
        return None
    need = sum(flops.min_time_s(ctx["c"], fn, c["n"], ctx["peak"])
               for c in calls)
    return 100.0 * need / dev_s


def scope_roofline_pct(ctx: dict, fn: str, scope: str):
    """Least time the program scope ``scope`` of every ``fn`` call in the
    traced window needs (``parts`` of ``bench/arch/<kind>.py``) over the
    device time of the operations under that scope (the trace's
    ``scope_s``), in percent."""
    from bench import flops
    calls = of(ctx, fn)
    dev_s = ctx["trace"].get("scope_s", {}).get(fn, {}).get(scope, 0.0)
    if not calls or dev_s <= 0:
        return None
    need = 0.0
    for c in calls:
        part = flops.parts(ctx["c"], fn, c["n"]).get(scope)
        if part is None:
            return None
        need += flops.least_s(*part, ctx["peak"])
    return 100.0 * need / dev_s
