"""The reduction from a profiler trace to device numbers: the arithmetic on
planes built by hand, and the reading of a trace recorded on the CPU."""
import struct
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from bench import trace_reduce as trc


def _plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=s, duration_ns=d)
                            for n, s, d in evs])
        for ln, evs in lines.items()])


def _device(i, ops, modules):
    return _plane(f"{trc.DEVICE_PREFIX}{i}", {trc.OPS_LINE: ops,
                                              trc.MODULES_LINE: modules})


OPS = [("fusion.1", 0, 10), ("fusion.2", 10, 5), ("dot.3", 30, 5),
       ("fusion.1", 100, 20)]
MODULES = [("jit__prefill(12)", 0, 15), ("jit__decode", 30, 5),
           ("jit__decode.1", 100, 20), ("jit_other", 200, 1)]
HOST = _plane(trc.HOST_PLANE, {"python": [
    ("PjitFunction(_decode)", 16, 12), ("socket recv", 36, 50),
    ("whole run", 0, 1000)]})


def test_busy_is_the_union_of_operation_intervals():
    assert trc.union_ns(OPS) == 15 + 5 + 20
    assert trc.busy_intervals(OPS) == [(0, 15), (30, 35), (100, 120)]


def test_module_time_per_library_function():
    assert trc.module_ns(MODULES, "jit__decode") == (25, 2)
    assert trc.module_ns(MODULES, "jit__prefill") == (15, 1)


def test_reduce_planes_averages_over_chips_and_names_idle_gaps():
    other = _device(1, [("fusion.9", 0, 40)], [("jit__prefill", 0, 40)])
    out = trc.reduce_planes([_device(0, OPS, MODULES), other], [HOST])
    assert out["busy_s"] == pytest.approx((40 + 40) / 2 * 1e-9)
    assert out["module_s"]["decode"] == pytest.approx(25 / 2 * 1e-9)
    assert out["module_s"]["prefill"] == pytest.approx(55 / 2 * 1e-9)
    assert out["module_count"] == {"prefill": 1, "decode": 1}
    # gaps of chip 0, longest first, each named by the host event that
    # overlaps it most; an event spanning the whole trace names none
    assert out["idle_gaps"] == [["socket recv", pytest.approx(65e-9)],
                                ["PjitFunction(_decode)",
                                 pytest.approx(15e-9)]]
    assert out["device_ops"] == [
        ["jit__prefill/fusion.9", pytest.approx(20e-9)],
        ["jit__decode.1/fusion.1", pytest.approx(10e-9)],
        ["jit__prefill/fusion.1", pytest.approx(5e-9)],
        ["jit__prefill/fusion.2", pytest.approx(2.5e-9)],
        ["jit__decode/dot.3", pytest.approx(2.5e-9)]]


def test_nested_operations_count_once():
    # a loop's event on the ops line spans the operations of its body
    ops = [("%while.4 = (s32[]) while(...)", 0, 100),
           ("%fusion.1 = f32[8] fusion(...)", 10, 10),
           ("%fusion.2 = f32[8] fusion(...)", 30, 20), ("%copy.3", 150, 5)]
    assert trc.self_ns(ops) == [70, 10, 20, 5]
    top = trc.top_ops(ops, [("jit__decode(99)", 0, 200)])
    assert top[0] == ["jit__decode/while.4", pytest.approx(70e-9)]
    assert sum(v for _, v in top) == pytest.approx(105e-9)
    assert trc.short_name("jit__decode(7290837971745813548)") == "jit__decode"


# framework names (the HLO op_name a TPU trace keeps in each operation's
# event metadata), by (program id, operation event name)
SCOPED_MODULES = [("jit__decode(7)", 0, 100), ("jit__decode(7)", 200, 100),
                  ("jit__prefill(8)", 400, 50)]
SCOPED_OPS = [("%while.1", 0, 90), ("%fusion.2", 10, 30),
              ("%fusion.3", 50, 20), ("%copy.4", 92, 8),
              ("%while.1", 200, 90), ("%fusion.2", 210, 30),
              ("%fusion.3", 250, 20), ("%copy.4", 292, 8),
              ("%fusion.2", 400, 40)]
NAMES = {
    ("7", "%while.1"): "jit(_decode)/while",
    ("7", "%fusion.2"): "jit(_decode)/while/body/avec.attn/avec.sdpa/"
                        "bskgh,btkh->bkgst/dot_general:",
    ("7", "%fusion.3"): "jit(_decode)/while/body/avec.sdpa/reduce_sum:",
    # the same operation name in another program: not the decode's
    ("8", "%fusion.2"): "jit(_prefill)/avec.other/add:",
}


def test_scope_time_by_the_innermost_avec_segment():
    out = trc.scope_ns(SCOPED_OPS, SCOPED_MODULES, NAMES)
    # the loop's own time (90 less its body's 50) counts once, under its
    # own name; the copy has no framework name: both unscoped
    assert dict(out["decode"]) == {"avec.sdpa": 2 * (30 + 20),
                                   "": 2 * (40 + 8)}
    assert dict(out["prefill"]) == {"avec.other": 40}
    assert trc.scope("jit(f)/avec.a/x/avec.b/dot_general:") == "avec.b"
    assert trc.scope("jit(f)/while/body/dot_general:") == ""
    assert trc.program_id("jit__decode(7290837971745813548)") == \
        "7290837971745813548"


def test_scope_time_sums_to_the_module_s_own_operation_time():
    out = trc.scope_ns(SCOPED_OPS, SCOPED_MODULES, NAMES)
    own = trc.self_ns(SCOPED_OPS)
    for fn in ("decode", "prefill"):
        want = sum(own[i] for i in range(len(SCOPED_OPS))
                   if (i < 8) == (fn == "decode"))
        assert sum(out[fn].values()) == pytest.approx(want)
    # without framework names every operation is unscoped
    assert dict(trc.scope_ns(SCOPED_OPS, SCOPED_MODULES, {})["decode"]) == {
        "": sum(own[:8])}


def test_scope_time_is_averaged_over_chips():
    other = _device(1, [("%fusion.2", 0, 60)], [("jit__decode(9)", 0, 60)])
    names = {f"{trc.DEVICE_PREFIX}0": NAMES,
             f"{trc.DEVICE_PREFIX}1": {("9", "%fusion.2"): "a/avec.sdpa/b:"}}
    out = trc.reduce_planes([_device(0, SCOPED_OPS, SCOPED_MODULES), other],
                            [HOST], names)
    assert out["scope_s"]["decode"] == {
        "avec.sdpa": pytest.approx((100 + 60) / 2 * 1e-9),
        "": pytest.approx(96 / 2 * 1e-9)}
    assert out["scope_s"]["prefill"] == {"avec.other": pytest.approx(20e-9)}
    # no names given: all of a function's own time is unscoped
    out = trc.reduce_planes([_device(0, SCOPED_OPS, SCOPED_MODULES)], [HOST])
    assert out["scope_s"]["decode"] == {"": pytest.approx(196e-9)}


def _pb(fields) -> bytes:
    """A protobuf message from (field number, value) pairs: an int is a
    varint, bytes or str a length-delimited field, a float a double."""
    def varint(v):
        out = b""
        while True:
            out += bytes([v & 0x7F | (0x80 if v > 0x7F else 0)])
            v >>= 7
            if not v:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, float):
            out += varint(num << 3 | 1) + struct.pack("<d", v)
        elif isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_framework_names_from_a_serialized_xspace():
    stat_md = [(5, _pb([(1, k), (2, _pb([(1, k), (2, name)]))]))
               for k, name in ((1, "tf_op"), (2, "program_id"),
                               (3, "flops"), (4, "hlo_category"))]

    def event_md(k, name, *stats):
        return (4, _pb([(1, k), (2, _pb([(1, k), (2, name),
                                         *((5, _pb(st)) for st in stats)]))]))

    dev = _pb([(1, 3), (2, "/device:TPU:0"), (3, _pb([(2, "XLA Ops")])),
               *stat_md,
               event_md(1, "%fusion.2 = bf16[8] fusion(...)",
                        [(1, 1), (5, "jit(_decode)/avec.sdpa/dot_general:")],
                        [(1, 2), (3, 2 ** 63 + 5)], [(1, 3), (2, 1.5)]),
               # a name given by reference to a stat's metadata
               event_md(2, "%copy.4", [(1, 2), (3, 7)], [(1, 1), (7, 4)]),
               # no framework name: left out
               event_md(3, "%while.1", [(1, 2), (3, 7)])])
    host = _pb([(1, 4), (2, "/host:CPU"), *stat_md,
                event_md(1, "%x", [(1, 1), (5, "host op")])])
    raw = _pb([(1, host), (1, dev), (2, "an error")])
    assert trc.framework_names(raw, {"/device:TPU:0"}) == {"/device:TPU:0": {
        (str(2 ** 63 + 5), "%fusion.2 = bf16[8] fusion(...)"):
            "jit(_decode)/avec.sdpa/dot_general:",
        ("7", "%copy.4"): "hlo_category"}}


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        trc.reduce_planes([], [HOST])


def test_a_trace_recorded_on_the_cpu(tmp_path):
    @jax.jit
    def _prefill(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((128, 128))
    _prefill(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            _prefill(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    planes = {p.name: p for p in ProfileData.from_file(
        trc.find_xplane(str(tmp_path))).planes}
    host = [e.name for line in planes[trc.HOST_PLANE].lines
            for e in line.events]
    assert any("_prefill" in name for name in host)
    # the CPU's operations carry no framework name in their metadata
    with open(trc.find_xplane(str(tmp_path)), "rb") as f:
        assert trc.framework_names(f.read(), set(planes)) == {
            name: {} for name in planes}
    # the CPU has no device plane: the reduction refuses the trace rather
    # than report a device number from it
    with pytest.raises(ValueError, match="no device plane"):
        trc.reduce_trace(str(tmp_path))
