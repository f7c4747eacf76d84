"""bench/run.py end to end on the CPU at a tiny size: it refuses to measure
without a TPU, a sound run comes out correct, and a run whose timed path is
broken underneath, or served by the int8 control, comes out not correct."""
import json
import os
import subprocess
import sys
import types

import pytest

from bench import harness

DATA = os.path.join(os.path.dirname(__file__), "data")
SERVER = os.path.join(harness.BENCH, "server.py")

# The destination process with one fault planted in the served library
# before it starts; argv after the code is the server's own.
FAULTY_SERVER = """
import runpy, sys
sys.path[:0] = [{root!r}, {src!r}]
import numpy as np
import repro.core.library as L
make = L.make_model_library
def faulty(cfg, max_cache_len=256):
    lib = make(cfg, max_cache_len)
    prefill, decode = lib["prefill"], lib["decode"]
{body}
    lib["prefill"], lib["decode"] = prefill, decode
    return lib
L.make_model_library = faulty
sys.argv = [{server!r}] + sys.argv[1:]
runpy.run_path({server!r}, run_name="__main__")
"""

FAULTS = {
    # a decode step that hands back its state unchanged: the cache and the
    # position never advance past the prompt
    "state_unchanged": """
    step = decode
    def decode(params, state, args):
        keep = dict(state)
        out = step(params, state, args)
        state.clear(); state.update(keep)
        return out
""",
    # a token altered where it is produced: every fifth decode step's
    # logits favour a token the model did not choose
    "token_altered": """
    step, n = decode, [0]
    def decode(params, state, args):
        out = step(params, state, args)
        n[0] += 1
        if n[0] % 5 == 0:
            lg = np.array(out["logits"])
            lg[0, -1, (int(lg[0, -1].argmax()) + 7) % cfg.vocab_size] = 1e3
            out = {"logits": lg}
        return out
""",
}


# The destination with the comparison's control in the program's place: the
# int8 reference serves every call, over the whole token history, and its
# logits go back over the wire as the program's would.
CONTROL_SERVER = """
import runpy, sys
sys.path[:0] = [{root!r}, {src!r}]
import numpy as np
from bench import harness
import repro.core.library as L
c = harness.load_config(sys.argv[sys.argv.index("--config") + 1])
ctrl = harness.reference_module(c["reference"]).Reference(c, "int8")
def control(cfg, max_cache_len=256):
    def last(params, toks):
        padded = np.concatenate([toks, np.zeros(-len(toks) % 64, np.int32)])
        rows = np.array([len(toks) - 1], np.int32)
        return {{"logits": np.asarray(ctrl.logits(params, padded, rows))[None]}}
    def prefill(params, state, args):
        state["toks"] = np.asarray(args["tokens"][0], np.int32)
        return last(params, state["toks"])
    def decode(params, state, args):
        state["toks"] = np.concatenate(
            [state["toks"], np.asarray(args["tokens"][0], np.int32)])
        return last(params, state["toks"])
    return {{"prefill": prefill, "decode": decode}}
L.make_model_library = control
sys.argv = [{server!r}] + sys.argv[1:]
runpy.run_path({server!r}, run_name="__main__")
"""


def _args(seed, workload="granite-chat"):
    return types.SimpleNamespace(workload=workload, seed=seed, seconds=2.0,
                                 trace=0)


def _spec(config, mix, workload="granite-chat"):
    bench = harness.load_benchmark()
    cell = dict(harness.find(bench["workloads"], workload, "workload"))
    with open(os.path.join(DATA, f"{mix}.json")) as f:
        traffic = json.load(f)
    return bench, cell, os.path.join(DATA, f"{config}.json"), traffic


def _run(config, mix, seed, server_cmd=None):
    from bench import run
    return run.run(_args(seed), allow_cpu=True, server_cmd=server_cmd,
                   spec=_spec(config, mix))


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(harness.BENCH, "run.py"),
                        "--workload", "granite-chat", "--seed", "3",
                        "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"metrics"' not in p.stdout


def test_sound_run_is_correct(tiny_config):
    out = _run(tiny_config, "tiny-chat", 2 ** 31 + 99)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"setup_s", "tokens_per_s", "ttft_p50_ms",
                                   "tbt_p95_ms"}
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, tiny_config):
    code = FAULTY_SERVER.format(root=harness.ROOT,
                                src=os.path.join(harness.ROOT, "src"),
                                server=SERVER, body=FAULTS[fault])
    out = _run(tiny_config, "tiny-chat", 2 ** 31 + 7,
               server_cmd=[sys.executable, "-c", code])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["correct"], out["checks"]


def test_int8_control_served_is_not_correct(tiny_config):
    """The control judged by the code that judges the program: its logits
    cross the wire as received logits and fail the comparison."""
    code = CONTROL_SERVER.format(root=harness.ROOT,
                                 src=os.path.join(harness.ROOT, "src"),
                                 server=SERVER)
    out = _run(tiny_config, "tiny-chat", 2 ** 31 + 5,
               server_cmd=[sys.executable, "-c", code])
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["correct"], out["checks"]
    ck = out["checks"]["logit_err"]
    assert ck["value"] > ck["limit"], out["checks"]


def test_the_window_reports_the_destination_s_counters():
    """Each tiny-dense decode call in the window donates its whole cache,
    and the window's counters grow by that."""
    from bench import run
    args = _args(2 ** 31 + 3)
    _, _, c, _, traffic, server, client = run.setup(
        args, allow_cpu=True, spec=_spec("tiny-dense", "tiny-chat"))
    try:
        end = run.measure(args, traffic, server, client)["end"]
    finally:
        client.close()
        server.close()
    decodes = sum(1 for _, fn, _ in client.calls if fn == "decode")
    # K and V of every layer and slot, bfloat16
    hd = c["hidden_size"] // c["num_attention_heads"]
    cache = (2 * c["num_hidden_layers"] * c["serving"]["max_cache_len"]
             * c["num_key_value_heads"] * hd * 2)
    assert decodes > 0
    assert end["counters"]["avec_state_donated_bytes_total"] == \
        decodes * cache
    assert end["counters"]["avec_backend_compiles_total"] == 0


def test_traced_readers_get_the_window_s_counters(monkeypatch):
    from bench import run
    seen = []
    monkeypatch.setattr(harness, "metric_reader", lambda name: types.
                        SimpleNamespace(read=lambda ctx: seen.append(ctx)))
    bench, cell, c_path, _ = run.load_cell("granite-chat")
    trace = {"busy_s": 1.0, "module_s": {}, "module_count": {},
             "scope_s": {}, "device_ops": [], "idle_gaps": []}
    m = {"records": [], "t0": 0.0, "seconds": 10.0, "window_s": 10.0,
         "sample_requests": 0, "sample_tokens": 0,
         "end": {"peak_bytes": 1, "compiles": 0, "trace": trace,
                 "counters": {"avec_state_donated_bytes_total": 3.0}}}
    server = types.SimpleNamespace(ready={"device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}})
    run.result_line(types.SimpleNamespace(trace=1), bench, cell,
                    harness.load_config(c_path), server,
                    types.SimpleNamespace(calls=[]), m, None)
    assert len(seen) == len(harness.per_layer_for(bench, "granite-chat"))
    assert all(ctx["counters"] == {"avec_state_donated_bytes_total": 3.0}
               for ctx in seen)
