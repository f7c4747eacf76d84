"""Each plain reference in bench/reference against the served program at a
small size on the CPU: prefill, then greedy decode through the cache, and
the comparison's int8 control."""
import copy
import os

import numpy as np

from bench import harness, weights

DATA = os.path.join(os.path.dirname(__file__), "data")


def _config(name, dtype=None):
    c = harness.load_config(os.path.join(DATA, f"{name}.json"))
    if dtype is not None:
        c = copy.deepcopy(c)
        c["torch_dtype"] = dtype
    return c


def _serve(c, params, prompt, n_out):
    """The program's library, called as the destination calls it: logits
    of the prefill and of each greedy decode step, and the tokens served."""
    from repro.core.library import make_model_library
    lib = make_model_library(harness.program_config(c),
                             max_cache_len=c["serving"]["max_cache_len"])
    state, logits, served = {}, [], []
    out = lib["prefill"](params, state, {"tokens": prompt[None, :]})
    for _ in range(n_out):
        lg = np.asarray(out["logits"][0, -1], np.float64)
        logits.append(lg[:c["vocab_size"]])
        served.append(int(np.argmax(lg)))
        out = lib["decode"](params, state,
                            {"tokens": np.array([[served[-1]]], np.int32)})
    return np.stack(logits), np.array(served)


def _inputs(c, seed, P=37, n_out=9):
    params = weights.maker(harness.reference_module(
        c["reference"]).layout(c))(seed)
    prompt = np.random.default_rng(seed).integers(
        0, c["vocab_size"], P).astype(np.int32)
    return params, prompt, n_out


def test_reference_matches_the_served_program(tiny_config):
    c = _config(tiny_config, "float32")    # float32 weights and compute
    params, prompt, n_out = _inputs(c, 2 ** 31 + 11)
    logits, served = _serve(c, params, prompt, n_out)
    ref = harness.reference_module(c["reference"]).Reference(c)
    toks = np.concatenate([prompt, served[:-1]])
    rows = np.arange(len(prompt) - 1, len(toks))
    want = np.asarray(ref.logits(params, toks, rows), np.float64)
    scale = np.abs(want).max()
    np.testing.assert_allclose(logits, want, rtol=0, atol=2e-4 * scale)


def test_reference_is_causal_and_ignores_padding(tiny_config):
    c = _config(tiny_config)
    params, prompt, _ = _inputs(c, 5)
    ref = harness.reference_module(c["reference"]).Reference(c)
    rows = np.arange(len(prompt))
    a = np.asarray(ref.logits(params, prompt, rows))
    padded = np.concatenate([prompt, np.zeros(27, np.int32)])
    b = np.asarray(ref.logits(params, padded, rows))
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * np.abs(a).max())


def _numbers(c, seed):
    """The comparison's numbers for the served program and for the int8
    control, on one seeded prompt and its greedy continuation."""
    from bench.server import rel_err
    mod = harness.reference_module(c["reference"])
    params, prompt, n_out = _inputs(c, seed, P=64, n_out=24)
    got, served = _serve(c, params, prompt, n_out)
    toks = np.concatenate([prompt, served[:-1]])
    rows = np.arange(len(prompt) - 1, len(toks))
    want = np.asarray(mod.Reference(c).logits(params, toks, rows), np.float64)
    cl = np.asarray(mod.Reference(c, "int8").logits(params, toks, rows),
                    np.float64)
    best, sd, r = want.max(axis=1), want.std(axis=1), np.arange(len(rows))
    pick = cl.argmax(axis=1)
    return {"err": float(rel_err(got, want, sd).max()),
            "gap": float((best - want[r, served]).max()),
            "ctrl_err": float(rel_err(cl, want, sd).max()),
            "ctrl_gap": float((best - want[r, pick]).max())}


def test_int8_control_fails_where_bf16_serving_passes(tiny_config):
    """The comparison at the configuration's own precision (bfloat16) and
    its control one step below (int8 weights and activations).  The tiny
    files' limits lie between the two readings."""
    c = _config(tiny_config)
    lim = c["limits"]
    for seed in (1, 2, 3):
        n = _numbers(c, seed)
        assert n["err"] <= lim["logit_err"] < n["ctrl_err"], n
        assert n["gap"] <= lim["max_logit_gap"], n
