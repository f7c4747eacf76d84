"""The expert layer that holds some of the router's experts, against the
plain reference at a small size on the CPU: the shares of every rank add
up to the uncut layer, no token is dropped however the router leans, and
the destination's expert counters are the routing counted by hand.  Also:
the configurations that hold no experts serve what they served before the
expert layer and the Granite scalars came in, bit for bit."""
import copy
import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights

DATA = os.path.join(os.path.dirname(__file__), "data")
TINY = "tiny-granitemoehybrid"


def _config(name=TINY, **kw):
    c = copy.deepcopy(harness.load_config(os.path.join(DATA, f"{name}.json")))
    c["torch_dtype"] = "float32"
    for k, v in kw.items():
        if k in ("first_held", "router_experts"):
            c["expert_parallel"][k] = v
        else:
            c[k] = v
    return c


def _ref():
    return harness.reference_module("granitemoehybrid")


def _moe_params(c, seed):
    """The expert sublayer's parameters of one MoE layer."""
    lo = _ref().layout(c)["blocks"]["layers"][0]["moe"]
    return jax.tree_util.tree_map(lambda a: a[0], weights.maker(lo)(seed))


def _ref_moe(c, p, x):
    return jax.jit(lambda p, x: _ref().moe(c, "f32", p, x))(p, x)


def _serve(cfg, p, x):
    from repro.models.moe import moe_serve
    return jax.jit(lambda p, x: moe_serve(cfg, p, x))(p, x)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """Eight chips of 9 experts each: their outputs summed, with the shared
    expert (which every chip computes) counted once, are the whole
    layer's output by the uncut reference."""
    from repro.models.mlp import apply_mlp
    whole = _config(num_local_experts=72, first_held=0)
    p = _moe_params(whole, 11)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, whole["hidden_size"]))
    want, _ = _ref_moe(whole, p, x.reshape(16, -1))
    got = jnp.zeros_like(x)
    for r in range(8):
        c = _config(num_local_experts=9, first_held=9 * r)
        cfg = harness.program_config(c)
        share = dict(p, **{w: p[w][9 * r:9 * r + 9]
                           for w in ("w_gate", "w_up", "w_down")})
        y, _ = _serve(cfg, share, x)
        got = got + y
    shared = apply_mlp(harness.program_config(whole), p["dense"], x)
    got = got - 7 * shared
    np.testing.assert_allclose(np.asarray(got).reshape(16, -1),
                               np.asarray(want), rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_no_token_is_dropped_when_every_token_picks_one_held_expert():
    from repro.models.moe import apply_moe
    c = _config()
    cfg = harness.program_config(c)
    first, d = c["expert_parallel"]["first_held"], c["hidden_size"]
    p = _moe_params(c, 5)
    e0 = first + 2
    p["router"] = p["router"].at[:, e0].set(1.0)
    T = 64
    x = 3.0 + jax.random.normal(jax.random.PRNGKey(1), (1, T, d))
    want, top_e = _ref_moe(c, p, x[0])
    assert bool(jnp.all(top_e[:, 0] == e0))        # all T rows on e0
    got, counts = _serve(cfg, p, x)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=0, atol=2e-5 * scale)
    held = (top_e >= first) & (top_e < first + c["num_local_experts"])
    assert int(counts[0]) == int(held.sum()) >= T
    # the training path's capacity would drop most of e0's rows here
    whole = _config(num_local_experts=72, first_held=0)
    pw = _moe_params(whole, 5)
    pw["router"] = pw["router"].at[:, e0].set(1.0)
    dropped, _ = jax.jit(lambda p, x: apply_moe(
        harness.program_config(whole), p, x))(pw, x)
    undropped, _ = _ref_moe(whole, pw, x[0])
    assert float(jnp.abs(dropped[0] - undropped).max()) > 0.1 * float(
        jnp.abs(undropped).max())


def _hand_count(c, routes, lo, hi):
    """(rows routed to held experts, held experts hit) of positions
    [lo, hi) as one call, summed over layers."""
    first, held = c["expert_parallel"]["first_held"], c["num_local_experts"]
    rows = hits = 0
    for top_e in routes:
        e = top_e[lo:hi]
        mine = e[(e >= first) & (e < first + held)]
        rows += mine.size
        hits += np.unique(mine).size
    return rows, hits


def test_the_expert_counters_are_the_routing_counted_by_hand(monkeypatch):
    from repro.core.executor import DestinationExecutor
    from repro.core.library import ExpertCounts, make_model_library
    monkeypatch.setattr(ExpertCounts, "FOLD", 3)   # fold on the way too
    c = _config()
    params = weights.maker(_ref().layout(c))(2 ** 31 + 17)
    lib = make_model_library(harness.program_config(c),
                             max_cache_len=c["serving"]["max_cache_len"])
    ex = DestinationExecutor({"lm": lib}, name="moe-counts")
    P, n = 29, 8
    prompt = np.random.default_rng(4).integers(
        0, c["vocab_size"], P).astype(np.int32)
    state, toks = {}, list(prompt)
    out = lib["prefill"](params, state, {"tokens": prompt[None]})
    for _ in range(n):
        toks.append(int(np.argmax(np.asarray(out["logits"])[0, -1])))
        out = lib["decode"](params, state,
                            {"tokens": np.array([[toks[-1]]], np.int32)})
    routes = _ref().Reference(c).routes(params, np.asarray(toks, np.int32))
    want_prefill = _hand_count(c, routes, 0, P)
    want_decode = np.sum([_hand_count(c, routes, t, t + 1)
                          for t in range(P, P + n)], axis=0)
    assert want_decode[0] > 0 and want_prefill[1] > 0
    seen = ex.metrics.sample_values()
    for i, name in enumerate(("avec_moe_routed_rows_total",
                              "avec_moe_experts_hit_total")):
        assert seen[f'{name}{{fn="prefill",lib="lm"}}'] == want_prefill[i]
        assert seen[f'{name}{{fn="decode",lib="lm"}}'] == want_decode[i]


def test_a_library_without_experts_keeps_no_counters():
    from repro.core.library import make_model_library
    c = harness.load_config(os.path.join(DATA, "tiny-dense.json"))
    assert make_model_library(harness.program_config(c)).counters == {}


# sha256 of the float32 logits of one prefill (21 tokens) and six greedy
# decode steps, weights from seed 7, as served before the expert layer and
# the Granite scalars were added to the program
SERVED_BEFORE = {
    "tiny-dense":
        "490916d0235e65c108a1c3c3ea8b7fbe21f5509491d2177cb2b2516822cb30c8",
    "tiny-mamba2":
        "7b5022bcabd6e017433fc0a5affce5ad021b24f1f462682b9d0c3ab4f3e3d2d2",
}


def test_configurations_without_the_new_scalars_serve_as_before():
    from repro.core.library import make_model_library
    for name, digest in SERVED_BEFORE.items():
        c = harness.load_config(os.path.join(DATA, f"{name}.json"))
        params = weights.maker(
            harness.reference_module(c["reference"]).layout(c))(7)
        cfg = harness.program_config(c)
        assert cfg.norm_eps == 1e-6 and cfg.rope
        lib = make_model_library(cfg,
                                 max_cache_len=c["serving"]["max_cache_len"])
        state = {}
        prompt = np.random.default_rng(7).integers(
            0, c["vocab_size"], 21).astype(np.int32)
        out = lib["prefill"](params, state, {"tokens": prompt[None]})
        h = hashlib.sha256()
        for _ in range(6):
            lg = np.asarray(out["logits"], np.float32)
            h.update(lg.tobytes())
            out = lib["decode"](params, state, {"tokens": np.array(
                [[int(lg[0, -1].argmax())]], np.int32)})
        h.update(np.asarray(out["logits"], np.float32).tobytes())
        assert h.hexdigest() == digest, name


def test_the_tpu_grouped_matmul_serves_what_ragged_dot_serves(monkeypatch):
    """The TPU branch of the expert matmuls (the Pallas grouped matmul, in
    interpret mode here) against the other platforms' ``ragged_dot``:
    prefill-sized and decode-sized inputs, rows past the groups masked."""
    import functools
    from jax.experimental.pallas.ops.tpu import megablox
    c = _config()
    cfg = harness.program_config(c)
    p = _moe_params(c, 9)
    for T in (1, 40):
        x = jax.random.normal(jax.random.PRNGKey(T), (1, T, c["hidden_size"]))
        want = _serve(cfg, p, x)
        with monkeypatch.context() as m:
            m.setattr(jax, "default_backend", lambda: "tpu")
            m.setattr(megablox, "gmm",
                      functools.partial(megablox.gmm, interpret=True))
            got = _serve(cfg, p, x)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=0, atol=1e-5)


def test_moe_roofline_reads_the_window_s_counters_over_the_scope_s_time():
    from bench import flops
    c = harness.load_config(harness.config_path("granite-4.0-h-small"))
    peak = flops.peaks("TPU v5 lite")
    calls = [{"fn": "prefill", "n": 1280, "wall_s": 0.1, "spans": {}},
             {"fn": "prefill", "n": 640, "wall_s": 0.1, "spans": {}},
             {"fn": "decode", "n": 1300, "wall_s": 0.01, "spans": {}}]
    counters = {'avec_moe_routed_rows_total{fn="prefill",lib="lm"}': 24000.0,
                'avec_moe_experts_hit_total{fn="prefill",lib="lm"}': 180.0,
                'avec_moe_routed_rows_total{fn="decode",lib="lm"}': 13.0,
                'avec_moe_experts_hit_total{fn="decode",lib="lm"}': 13.0}
    ctx = {"c": c, "calls": calls, "counters": counters, "peak": peak,
           "trace": {"scope_s": {"prefill": {"avec.moe": 0.05}}}}
    d, f, E, fs, L = 4096, 768, 72, 1536, 10
    ops = (1920 * L * (2 * d * E + 6 * d * fs)       # router, shared expert
           + 24000 * 6 * d * f)                        # the routed rows
    nbytes = 2 * L * (4 * d * E + 2 * 3 * d * fs) + 180 * 3 * d * f * 2
    want = 100 * max(ops / peak["bf16_flops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"]) / 0.05
    reader = harness.metric_reader("moe_roofline.prefill")
    assert reader.read(ctx) == pytest.approx(want, rel=1e-12)
    # a destination without the counters (the parent) leaves it out
    assert reader.read(dict(ctx, counters={})) is None
