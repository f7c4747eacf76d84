"""The per-layer metric readers, from span records and a reduced trace, and
the way BENCHMARK.json assigns metrics to cells."""
import os

import numpy as np
import pytest

from bench import flops, harness

GRANITE = harness.load_config(harness.config_path("granite-3-2b"))
PEAK = flops.peaks("TPU v5 lite")


def _call(fn, n, wall, queue, execute, coalesce=0.0):
    return {"fn": fn, "n": n, "wall_s": wall,
            "spans": {"serialize": 1e-4, "send": 2e-4, "queue": queue,
                      "coalesce": coalesce, "execute": execute,
                      "respond": wall - queue - execute - coalesce - 3e-4}}


def _ctx(calls, trace=None, window_s=10.0):
    return {"c": GRANITE, "calls": calls, "window_s": window_s, "peak": PEAK,
            "trace": trace or {"busy_s": 6.0, "module_s": {}}}


CALLS = [_call("prefill", 512, 0.060, 0.001, 0.050),
         _call("prefill", 1024, 0.110, 0.002, 0.100, coalesce=0.001),
         _call("decode", 512, 0.012, 0.0005, 0.010),
         _call("decode", 513, 0.013, 0.0010, 0.010),
         _call("decode", 514, 0.020, 0.0100, 0.0085)]


def read(name, ctx):
    return harness.metric_reader(name).read(ctx)


def test_wire_ms_is_the_median_of_what_the_destination_did_not_take():
    assert read("wire_ms.prefill", _ctx(CALLS)) == pytest.approx(
        1e3 * np.median([0.060 - 0.051, 0.110 - 0.103]))
    assert read("wire_ms.decode", _ctx(CALLS)) == pytest.approx(
        1e3 * np.median([0.0015, 0.002, 0.0015]))


def test_mfu_is_needed_operations_over_execute_time():
    ops = sum(flops.cost(GRANITE, "decode", n)[0] for n in (512, 513, 514))
    want = 100 * ops / (0.0285 * 197e12)
    assert read("mfu_pct.decode", _ctx(CALLS)) == pytest.approx(want)
    ops = sum(flops.cost(GRANITE, "prefill", n)[0] for n in (512, 1024))
    assert read("mfu_pct.prefill", _ctx(CALLS)) == pytest.approx(
        100 * ops / (0.150 * 197e12))


def test_roofline_is_least_time_over_the_program_s_device_time():
    tr = {"busy_s": 1.0, "module_s": {"decode": 0.025, "prefill": 0.12}}
    need = sum(flops.min_time_s(GRANITE, "decode", n, PEAK)
               for n in (512, 513, 514))
    assert read("decode_roofline", _ctx(CALLS, tr)) == pytest.approx(
        100 * need / 0.025)
    need = sum(flops.min_time_s(GRANITE, "prefill", n, PEAK)
               for n in (512, 1024))
    assert read("prefill_roofline", _ctx(CALLS, tr)) == pytest.approx(
        100 * need / 0.12)


def test_sdpa_roofline_is_the_scope_s_least_time_over_its_device_time():
    tr = {"busy_s": 1.0, "module_s": {"decode": 0.025},
          "scope_s": {"decode": {"avec.sdpa": 0.002, "": 0.023}}}
    need = sum(flops.least_s(*flops.parts(GRANITE, "decode", n)["avec.sdpa"],
                             PEAK) for n in (512, 513, 514))
    # bound by the K/V bytes: 513..515 positions of 81,920 bytes
    assert need == pytest.approx(81920 * (513 + 514 + 515) / 819e9)
    assert read("sdpa_roofline.decode", _ctx(CALLS, tr)) == pytest.approx(
        100 * need / 0.002)
    # a program without the scope, and a kind that counts none
    tr["scope_s"]["decode"] = {"": 0.025}
    assert read("sdpa_roofline.decode", _ctx(CALLS, tr)) is None
    ctx = _ctx(CALLS, {"busy_s": 1.0, "module_s": {"decode": 0.025},
                       "scope_s": {"decode": {"avec.sdpa": 0.002}}})
    ctx["c"] = harness.load_config(harness.config_path("mamba2-130m"))
    assert read("sdpa_roofline.decode", ctx) is None


def test_idle_share():
    assert read("device_idle_pct", _ctx(CALLS, {"busy_s": 2.5,
                                                "module_s": {}},
                                        window_s=10.0)) == pytest.approx(75.0)


@pytest.mark.parametrize("name", ["wire_ms.prefill", "wire_ms.decode",
                                  "mfu_pct.prefill", "mfu_pct.decode",
                                  "prefill_roofline", "decode_roofline",
                                  "device_idle_pct", "sdpa_roofline.decode"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    # no calls of the kind, and a trace in which the program never ran: the
    # metric is left out of the line, never reported as 0
    ctx = _ctx([], {"busy_s": 0.0, "module_s": {}})
    assert read(name, ctx) is None


def test_every_metric_has_a_reader_and_every_cell_its_metrics():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           f"{m['name']}.py"))
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        mine = {m["name"] for m in harness.end_to_end_for(bench,
                                                          cell["name"])}
        assert "setup_s" in mine and len(mine) >= 2 and mine <= e2e
        layer = harness.per_layer_for(bench, cell["name"])
        assert layer and all(m["moves"] in mine for m in layer)


def test_a_metric_listed_for_some_cells_only():
    bench = {"end_to_end": [{"name": "setup_s"},
                            {"name": "ttft_p95_ms", "workloads": ["b"]}],
             "per_layer": [{"name": "queue", "moves": "ttft_p95_ms"},
                           {"name": "idle", "moves": "setup_s",
                            "workloads": ["a"]}]}
    assert [m["name"] for m in harness.end_to_end_for(bench, "a")] == [
        "setup_s"]
    assert [m["name"] for m in harness.per_layer_for(bench, "a")] == ["idle"]
    assert [m["name"] for m in harness.per_layer_for(bench, "b")] == [
        "queue"]
