"""The decode step updates the session's KV cache in place: the stacked
cache rides in the layer scan's carry, the library donates it to the step,
and the destination serializes a session's calls with its snapshot and
restore and counts the state bytes donated."""
import functools
import re
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced, with_overrides
from repro.core import AvecSession, DestinationExecutor, HostRuntime
from repro.core.executor import STATE_DONATED
from repro.core.library import decode_jit, make_model_library
from repro.core.transport import DirectChannel
from repro.models import model as M

PROMPT, STEPS, MAX_LEN = 6, 5, 32


def _tiny(arch):
    cfg = reduced(get_arch(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tok = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                        (1, PROMPT + STEPS), 0, cfg.vocab_size))
    return cfg, params, tok


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-130m"])
def test_library_decode_is_bit_identical_to_the_undonated_step(arch):
    cfg, params, tok = _tiny(arch)
    lib = make_model_library(cfg, max_cache_len=MAX_LEN)
    _, cache = jax.jit(lambda p, b: M.prefill(cfg, p, b, MAX_LEN))(
        params, {"tokens": tok[:, :PROMPT]})
    step = jax.jit(functools.partial(M.decode_step, cfg))
    state: dict = {}
    lib["prefill"](params, state, {"tokens": tok[:, :PROMPT]})
    for t in range(PROMPT, PROMPT + STEPS):
        given = jax.tree_util.tree_leaves(state["cache"])
        got = lib["decode"](params, state, {"tokens": tok[:, t:t + 1]})
        want, cache = step(params, cache, {"tokens": tok[:, t:t + 1],
                                           "pos": jnp.asarray(t, jnp.int32)})
        assert all(x.is_deleted() for x in given), "cache was not donated"
        np.testing.assert_array_equal(np.asarray(got["logits"]),
                                      np.asarray(want))
    for a, b in zip(jax.tree_util.tree_leaves(state["cache"]),
                    jax.tree_util.tree_leaves(cache)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-130m",
                                  "granite-4.0-h-small"])
def test_served_cache_is_in_the_compute_dtype(arch):
    """The library's prefill serves K/V and conv state in the compute dtype
    (bf16 here, where ``reduced`` alone computes in float32) and the SSM
    state in float32; a decode gives back every leaf in its own dtype."""
    cfg = with_overrides(reduced(get_arch(arch)), param_dtype="bfloat16",
                         compute_dtype="bfloat16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tok = np.asarray(jax.random.randint(jax.random.PRNGKey(1),
                                        (1, PROMPT + 1), 0, cfg.vocab_size))
    lib = make_model_library(cfg, max_cache_len=MAX_LEN)
    state: dict = {}
    lib["prefill"](params, state, {"tokens": tok[:, :PROMPT]})
    want = {"k": jnp.bfloat16, "v": jnp.bfloat16, "conv": jnp.bfloat16,
            "ssm": jnp.float32}
    leaves = jax.tree_util.tree_flatten_with_path(state["cache"])[0]
    assert {path[-1].key for path, _ in leaves} >= (
        {"k", "v"} if arch == "granite-3-2b" else {"conv", "ssm"})
    for path, leaf in leaves:
        assert leaf.dtype == want[path[-1].key], (path, leaf.dtype)
    given = [leaf.dtype for leaf in jax.tree_util.tree_leaves(state["cache"])]
    lib["decode"](params, state, {"tokens": tok[:, PROMPT:]})
    assert [leaf.dtype for leaf in
            jax.tree_util.tree_leaves(state["cache"])] == given


def test_a_copy_of_the_state_keeps_its_cache():
    """A caller that keeps a copy of the state keeps a live cache: the step
    donates only a cache the state alone holds."""
    cfg, params, tok = _tiny("granite-3-2b")
    lib = make_model_library(cfg, max_cache_len=MAX_LEN)
    state: dict = {}
    lib["prefill"](params, state, {"tokens": tok[:, :PROMPT]})
    kept = dict(state)
    nxt = {"tokens": tok[:, PROMPT:PROMPT + 1]}
    first = np.asarray(lib["decode"](params, state, nxt)["logits"])
    assert not any(x.is_deleted()
                   for x in jax.tree_util.tree_leaves(kept["cache"]))
    again = np.asarray(lib["decode"](params, kept, nxt)["logits"])
    np.testing.assert_array_equal(first, again)


@pytest.mark.parametrize("donate", [True, False])
def test_decode_step_at_granite_widths_aliases_the_whole_cache(donate):
    """Compiled on abstract shapes at granite-3-2b's widths with a 4096-slot
    float32 cache (``init_cache``'s; the CPU backend widens a bf16 cache's
    updates, so the served bf16 cache is compiled for the v5e in
    ``test_tpu_compile.py``): donated, the step aliases every cache byte
    and copies none; undonated, it writes a new cache through a whole
    copy."""
    cfg = with_overrides(get_arch("granite-3-2b"), param_dtype="bfloat16",
                         compute_dtype="bfloat16")
    cache = M.abstract_cache(cfg, 1, 4096, jnp.float32)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    step = (decode_jit(cfg) if donate
            else jax.jit(functools.partial(M.decode_step, cfg)))
    compiled = step.lower(M.abstract_params(cfg), cache, batch).compile()
    aliased = compiled.memory_analysis().alias_size_in_bytes
    copies = re.findall(r"= f32\[40,1,4096,8,64\]\S* copy\(", compiled.as_text())
    if donate:
        assert aliased >= _nbytes(cache) == 671_088_640
        assert copies == []
        # the benchmark's trace reduction finds the step by its module name
        assert compiled.as_text().startswith("HloModule jit__decode,")
    else:
        assert aliased == 0 and copies


def _served(arch="granite-3-2b"):
    cfg, params, tok = _tiny(arch)
    ex = DestinationExecutor(
        {"lm": make_model_library(cfg, max_cache_len=MAX_LEN)}, name="inplace")
    rt = HostRuntime(DirectChannel(ex))
    sess = AvecSession(cfg, params, rt, "lm")
    sess.call("prefill", {"tokens": tok[:, :PROMPT]})
    return ex, rt, sess, tok


def test_snapshot_between_decodes_and_the_donated_bytes_counter():
    ex, rt, sess, tok = _served()
    state = ex.cache.get(sess.fp)["state"]
    cache_bytes = _nbytes(state["cache"])
    for t in range(PROMPT, PROMPT + 2):
        n0 = STATE_DONATED.total
        sess.call("decode", {"tokens": tok[:, t:t + 1]})
        assert STATE_DONATED.total - n0 == cache_bytes
        snap = rt.snapshot(sess.fp)
        assert snap["pos"] == t + 1
        for a, b in zip(jax.tree_util.tree_leaves(snap["cache"]),
                        jax.tree_util.tree_leaves(state["cache"])):
            np.testing.assert_array_equal(a, np.asarray(b))
        # on the CPU a snapshot's arrays may view the state's buffers, and
        # a buffer something views is copied, not donated
        del snap, a
    vals = ex.metrics.sample_values()
    assert vals["avec_state_donated_bytes_total"] == STATE_DONATED.total
    assert "avec_state_donated_bytes_total" in ex.metrics.render()


def test_snapshots_racing_decodes_see_live_state():
    """A session's snapshots wait for its library calls, so none reads a
    buffer a decode has donated."""
    ex, rt, sess, tok = _served()
    errors: list = []

    def snapshots():
        other = HostRuntime(DirectChannel(ex))
        try:
            for _ in range(8):
                other.snapshot(sess.fp)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=snapshots) for _ in range(4)]
        for th in threads:
            th.start()
        for _ in range(12):
            sess.call("decode", {"tokens": tok[:, PROMPT:PROMPT + 1]})
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(th.is_alive() for th in threads)
    assert errors == []
    assert rt.snapshot(sess.fp)["pos"] == PROMPT + 12


def test_snapshot_waits_for_the_sessions_library_call():
    entered, release = threading.Event(), threading.Event()

    def step(params, state, args):
        entered.set()
        release.wait(10)
        state["steps"] = state.get("steps", 0) + 1
        return {"y": np.zeros(1, np.float32)}

    ex = DestinationExecutor({"lib": {"step": step}}, name="serial")
    sess = AvecSession({"arch": "stub"}, {"w": np.zeros(1, np.float32)},
                       HostRuntime(DirectChannel(ex)), "lib")
    call = threading.Thread(
        target=sess.call, args=("step", {"x": np.zeros(1, np.float32)}))
    call.start()
    assert entered.wait(10)
    snaps: list = []
    snap = threading.Thread(target=lambda: snaps.append(
        HostRuntime(DirectChannel(ex)).snapshot(sess.fp)))
    snap.start()
    snap.join(timeout=0.3)
    assert snap.is_alive(), "the snapshot did not wait for the call"
    release.set()
    call.join(timeout=10)
    snap.join(timeout=10)
    assert not call.is_alive() and not snap.is_alive()
    assert int(snaps[0]["steps"]) == 1
