"""moe_roofline.prefill: least time the prefill calls' expert layers need
over the device time of the prefill program's operations under the scope
``avec.moe`` (router, dispatch, expert matmuls, combine, shared expert),
from the profiler trace.

Routing depends on the data, so the routed part is counted exactly from
the destination's counters of the window: each row routed to a held
expert needs ``routed(c)[0]`` operations, and each held expert hit is read
once, ``routed(c)[1]`` bytes (``bench/arch/<kind>.py``).  The fixed part
(router and shared expert) is ``parts(c, fn, n)["avec.moe"]`` per call.
The counters hold the window's totals, not each call's, so the least time
is that of the window's summed operations and bytes: never more than the
calls' own least times summed, so the share can only read low.

Prefill only: in a B=1 decode step XLA streams the next operations'
weights in by asynchronous copies that overlap other scopes, so a scope's
own operation time there can be shorter than its bytes need."""
from __future__ import annotations

ROWS = "avec_moe_routed_rows_total"
HITS = "avec_moe_experts_hit_total"


def counter(ctx: dict, name: str, fn: str):
    """The window's growth of view ``name`` for library function ``fn``,
    summed over libraries; None where the destination has no such view."""
    label = f'fn="{fn}"'
    values = [v for k, v in ctx.get("counters", {}).items()
              if k.partition("{")[0] == name
              and label in k.partition("{")[2].rstrip("}").split(",")]
    return sum(values) if values else None


def read(ctx):
    from bench import flops, harness
    c, fn = ctx["c"], "prefill"
    calls = [call for call in ctx["calls"] if call["fn"] == fn]
    dev_s = ctx["trace"].get("scope_s", {}).get(fn, {}).get("avec.moe", 0.0)
    rows, hits = counter(ctx, ROWS, fn), counter(ctx, HITS, fn)
    routed = getattr(harness.arch_module(c["reference"]), "routed", None)
    if not calls or dev_s <= 0 or rows is None or hits is None \
            or routed is None:
        return None
    ops = nbytes = 0.0
    for call in calls:
        part = flops.parts(c, fn, call["n"]).get("avec.moe")
        if part is None:
            return None
        ops, nbytes = ops + part[0], nbytes + part[1]
    row_ops, expert_bytes = routed(c)
    ops += rows * row_ops
    nbytes += hits * expert_bytes
    return 100.0 * flops.least_s(ops, nbytes, ctx["peak"]) / dev_s
