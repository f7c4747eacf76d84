"""From the profiler's trace to device numbers.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
On a TPU each chip is a plane ``/device:TPU:<i>`` whose line ``XLA Ops``
holds one event per device operation and whose line ``XLA Modules`` holds
one event per executed program, named after its jit (``jit__prefill``
for the library's ``_prefill``).  The host's threads are lines of the
plane ``/host:CPU``.

* busy: the union of the operation intervals (several operations can
  overlap on one chip; the union counts each nanosecond once);
* module time: the summed durations of one jit's program executions;
* scope time: the operations' own time in one jit's programs, by the
  innermost ``avec.<part>`` segment of each operation's framework name (the
  program's ``jax.named_scope("avec.<part>")``; ``""`` outside any);
* top operations: device time by operation and program, the time of
  operations nested in a loop's event counted once;
* idle gaps: the longest stretches between busy intervals, each named by
  the host event that overlaps it most (what the host was doing).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

#: XLA module name of each served library function: ``jax.jit`` names a
#: program ``jit_<function>``, and the library's jits are ``_prefill`` and
#: ``_decode`` (``repro.core.library.make_model_library``)
MODULES = {"prefill": "jit__prefill", "decode": "jit__decode"}

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: stat of an operation's event metadata that holds its framework name, the
#: HLO ``op_name`` (``jit(_decode)/while/body/closed_call/avec.sdpa/
#: bskgh,btkh->bkgst/dot_general:``): ``tf_op`` on a TPU v5e.  The profiler's
#: ``ProfileData`` events do not carry their metadata's stats, so
#: ``framework_names`` reads them from the xplane itself.
OP_NAME_STAT = "tf_op"
PROGRAM_ID_STAT = "program_id"
SCOPE_PREFIX = "avec."


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def line_events(plane, line_name: str) -> list[tuple[str, float, float]]:
    """(name, start_ns, duration_ns) of every event on one line."""
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
    return []


def union_ns(events) -> float:
    total, end = 0.0, float("-inf")
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def busy_intervals(events) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return [(a, b) for a, b in out]


def is_module(name: str, module: str) -> bool:
    """A program event of jit ``module``: ``jit__decode``,
    ``jit__decode(<program id>)`` or ``jit__decode.1``."""
    return name == module or name.startswith((module + "(", module + "."))


def module_ns(module_events, module: str) -> tuple[float, int]:
    """Summed duration and count of one jit's program executions."""
    total, n = 0.0, 0
    for name, _, d in module_events:
        if is_module(name, module):
            total += d
            n += 1
    return total, n


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...), kind=...`` -> ``fusion.12``;
    ``jit__decode(7290837971745813548)`` -> ``jit__decode``."""
    return name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def self_ns(events) -> list[float]:
    """Each event's duration less what the events nested in it cover: on
    the ops line a loop's event spans the operations of its body."""
    own = [d for _, _, d in events]
    stack: list[tuple[float, int]] = []
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i][1], -events[i][2])):
        _, s, d = events[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= min(d, stack[-1][0] - s)
        stack.append((s + d, i))
    return own


def programs(op_events, module_events) -> list[str]:
    """The name of the program event each operation starts in, ``"?"``
    where it starts in none."""
    mods = sorted((s, s + d, m) for m, s, d in module_events)
    starts = [m[0] for m in mods]
    out = []
    for _, s, _ in op_events:
        k = bisect.bisect_right(starts, s) - 1
        out.append(mods[k][2] if k >= 0 and s < mods[k][1] else "?")
    return out


def top_ops(op_events, module_events, n: int = 10) -> list[list]:
    """Device time by operation, each named ``<program>/<operation>`` after
    the program it ran in, nested time counted once, longest first."""
    acc: dict[str, float] = defaultdict(float)
    for (name, _, _), own, prog in zip(op_events, self_ns(op_events),
                                       programs(op_events, module_events)):
        acc[f"{short_name(prog)}/{short_name(name)}"] += own
    return [[k, v * 1e-9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def scope(op_name: str) -> str:
    """The innermost ``avec.`` segment of a framework name, ``""`` if
    none."""
    segs = [p for p in op_name.split("/") if p.startswith(SCOPE_PREFIX)]
    return segs[-1] if segs else ""


def program_id(module_event_name: str) -> str:
    """``jit__decode(7290837971745813548)`` -> ``7290837971745813548``."""
    m = re.search(r"\((\d+)\)$", module_event_name)
    return m.group(1) if m else ""


def scope_ns(op_events, module_events, names: dict) -> dict:
    """``{fn: {scope: ns}}``: the own time of each operation of a library
    function's program (nested time counted once), by the scope of its
    framework name.  ``names``: ``{(program id, operation event name):
    framework name}``; an operation without one counts under ``""``."""
    scopes = {key: scope(op) for key, op in names.items()}
    fns: dict[str, tuple] = {}
    acc: dict = defaultdict(lambda: defaultdict(float))
    for (name, _, _), own, prog in zip(op_events, self_ns(op_events),
                                       programs(op_events, module_events)):
        if prog not in fns:
            fns[prog] = (next((fn for fn, m in MODULES.items()
                               if is_module(prog, m)), None),
                         program_id(prog))
        fn, pid = fns[prog]
        if fn is not None:
            acc[fn][scopes.get((pid, name), "")] += own
    return acc


def idle_gaps(op_events, host_events, n: int = 10) -> list[list]:
    """Longest gaps between busy intervals, named by the host event that
    overlaps each most (events spanning most of the trace are ignored)."""
    iv = busy_intervals(op_events)
    gaps = sorted(((b0[1], b1[0]) for b0, b1 in zip(iv, iv[1:])
                   if b1[0] > b0[1]), key=lambda g: g[0] - g[1])[:n]
    span = (iv[-1][1] - iv[0][0]) if iv else 0.0
    host = [h for h in host_events if h[2] < 0.5 * span]
    out = []
    for g0, g1 in gaps:
        best, name = 0.0, "no host event"
        for hn, hs, hd in host:
            ov = min(g1, hs + hd) - max(g0, hs)
            if ov > best:
                best, name = ov, hn
        out.append([name, (g1 - g0) * 1e-9])
    return out


def _sum(pairs) -> dict:
    acc: dict[str, float] = defaultdict(float)
    for k, v in pairs:
        acc[k] += v
    return acc


def reduce_planes(device_planes, host_planes, names=None) -> dict:
    """Numbers averaged over the device planes given.  ``names``: per
    device plane name, the framework names ``framework_names`` reads."""
    if not device_planes:
        raise ValueError("the trace holds no device plane")
    busy, per_mod, top = 0.0, defaultdict(float), []
    counts = defaultdict(int)
    scopes: dict = defaultdict(lambda: defaultdict(float))
    for plane in device_planes:
        ops = line_events(plane, OPS_LINE) or line_events(plane, MODULES_LINE)
        mods = line_events(plane, MODULES_LINE)
        busy += union_ns(ops)
        top.extend(top_ops(ops, mods, 10 ** 9))
        for fn, mod in MODULES.items():
            t, k = module_ns(mods, mod)
            per_mod[fn] += t
            counts[fn] += k
        for fn, by in scope_ns(ops, mods,
                               (names or {}).get(plane.name, {})).items():
            for sc, t in by.items():
                scopes[fn][sc] += t
    host = [e for p in host_planes for line in p.lines
            for e in ((ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events)]
    ndev = len(device_planes)
    first = device_planes[0]
    first_ops = line_events(first, OPS_LINE) or line_events(first,
                                                            MODULES_LINE)
    return {
        "busy_s": busy / ndev * 1e-9,
        "module_s": {fn: t / ndev * 1e-9 for fn, t in per_mod.items()},
        "module_count": {fn: k // ndev for fn, k in counts.items()},
        "scope_s": {fn: {sc: t / ndev * 1e-9 for sc, t in by.items()}
                    for fn, by in scopes.items()},
        "device_ops": [[k, v / ndev] for k, v in
                       sorted(_sum(top).items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle_gaps(first_ops, host, 10),
    }


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one protobuf message: an
    int for a varint or fixed-width field, the bytes (a memoryview) of a
    length-delimited one."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            w = 8 if wire == 1 else 4
            val, i = int.from_bytes(buf[i:i + w], "little"), i + w
        else:
            raise ValueError(f"protobuf wire type {wire} in the xplane")
        yield key >> 3, val


def _map_value(entry) -> memoryview:
    """The value of one map entry (field 2; absent where it is empty)."""
    return next((v for k, v in _fields(entry) if k == 2), memoryview(b""))


def framework_names(xplane: bytes, plane_names) -> dict:
    """``{plane name: {(program id, operation event name): framework
    name}}`` for the named planes, from the event metadata of a serialized
    ``XSpace`` (``tsl/profiler/protobuf/xplane.proto``: ``XSpace.planes``
    1; ``XPlane.name`` 2, ``event_metadata`` 4, ``stat_metadata`` 5;
    ``XEventMetadata.name`` 2, ``stats`` 5; ``XStatMetadata.name`` 2;
    ``XStat.metadata_id`` 1, ``uint64_value`` 3, ``int64_value`` 4,
    ``str_value`` 5, ``ref_value`` 7)."""
    out = {}
    for num, plane in _fields(memoryview(xplane)):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for k, v in fields if k == 2), "")
        if name not in plane_names:
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                md = dict(_fields(_map_value(v)))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        table = {}
        for k, v in fields:
            if k != 4:
                continue
            ev_name, stats = "", {}
            for f, w in _fields(_map_value(v)):
                if f == 2:
                    ev_name = bytes(w).decode()
                elif f == 5:
                    st = dict(_fields(w))
                    val = (bytes(st[5]).decode() if 5 in st
                           else stat_names.get(st[7], "") if 7 in st
                           else str(st.get(3, st.get(4, ""))))
                    stats[stat_names.get(st.get(1, 0), "")] = val
            if OP_NAME_STAT in stats:
                table[(stats.get(PROGRAM_ID_STAT, ""), ev_name)] = \
                    stats[OP_NAME_STAT]
        out[name] = table
    return out


def reduce_trace(trace_dir: str, n_devices: int = 1) -> dict:
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    planes = list(pd.planes)
    dev = sorted((p for p in planes if p.name.startswith(DEVICE_PREFIX)),
                 key=lambda p: p.name)[:n_devices]
    host = [p for p in planes if p.name == HOST_PLANE]
    return reduce_planes(dev, host,
                         framework_names(raw, {p.name for p in dev}))

