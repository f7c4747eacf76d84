"""Compile the main path's kernels, the served decode step and the
OpenPose-lite forward for a described TPU v5e chip, at real widths, without a chip attached.

What interpret mode cannot show — block shapes the Mosaic compiler refuses,
fast-memory overuse, programs that do not fit — fails here.  Nothing runs,
so these tests say nothing about results or times.

The topology is described inside a module fixture (never at import): only
one process may load the TPU compiler library, so only the worker that is
given this file loads it."""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch, with_overrides
from repro.core.library import decode_jit
from repro.kernels import ops
from repro.models import model as M
from repro.models.openpose import OpenPoseLite, op_forward, op_param_specs
from repro.models.params import abstract_params


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_in(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_granite_widths(one_chip):
    cfg = get_arch("granite-3-2b")
    D = cfg.d_model // cfg.num_heads
    q = jax.ShapeDtypeStruct((1, 2048, cfg.num_heads, D), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, cfg.num_kv_heads, D), jnp.bfloat16,
                              sharding=one_chip)
    c = _compile(functools.partial(ops.flash_attention, impl="pallas"),
                 q, kv, kv)
    assert _kernel_in(c)


def test_decode_attention_compiles_granite_widths(one_chip):
    cfg = get_arch("granite-3-2b")
    D = cfg.d_model // cfg.num_heads
    q = jax.ShapeDtypeStruct((8, 1, cfg.num_heads, D), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 2048, cfg.num_kv_heads, D), jnp.bfloat16,
                              sharding=one_chip)
    lens = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    c = _compile(functools.partial(ops.decode_attention, impl="pallas"),
                 q, kv, kv, lens)
    assert _kernel_in(c)


def test_rmsnorm_compiles(one_chip):
    x = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16, sharding=one_chip)
    s = jax.ShapeDtypeStruct((2048,), jnp.float32, sharding=one_chip)
    c = _compile(functools.partial(ops.rmsnorm, impl="pallas"), x, s)
    assert _kernel_in(c)


def test_ssd_scan_compiles_mamba2_widths(one_chip):
    cfg = get_arch("mamba2-130m")
    ssm = cfg.ssm
    H, P, N, G = (ssm.n_heads(cfg.d_model), ssm.head_dim, ssm.d_state,
                  ssm.n_groups)
    B, S = 1, 2048

    def sd(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    c = _compile(functools.partial(ops.ssd_scan, chunk=ssm.chunk,
                                   impl="pallas"),
                 sd((B, S, H, P)), sd((B, S, H)), sd((H,)),
                 sd((B, S, G, N)), sd((B, S, G, N)))
    assert _kernel_in(c)


def test_comm_quant_compiles(one_chip):
    x = jax.ShapeDtypeStruct((4096, 2048), jnp.float32, sharding=one_chip)
    c = _compile(functools.partial(ops.quantize_int8, impl="pallas"), x)
    assert _kernel_in(c)
    q = jax.ShapeDtypeStruct((4096, 2048), jnp.int8, sharding=one_chip)
    s = jax.ShapeDtypeStruct((4096, 1), jnp.float32, sharding=one_chip)
    c = _compile(functools.partial(ops.dequantize_int8, impl="pallas"), q, s)
    assert _kernel_in(c)


def test_openpose_forward_compiles_paper_geometry(one_chip):
    net = OpenPoseLite()
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract_params(op_param_specs(net), jnp.float32))
    frames = jax.ShapeDtypeStruct((1, 368, 656, 3), jnp.float32,
                                  sharding=one_chip)
    c = _compile(functools.partial(op_forward, net), params, frames)
    m = c.memory_analysis()
    assert m.output_size_in_bytes >= 46 * 82 * 57 * 4


def test_decode_step_updates_the_granite_cache_in_place(one_chip):
    """The served decode step at granite-3-2b's widths, on the 4096-slot
    cache its prefill hands over: every cache byte aliased, and no layer's
    K or V slice copied out of or back into the stacked cache."""
    cfg = with_overrides(get_arch("granite-3-2b"), param_dtype="bfloat16",
                         compute_dtype="bfloat16")

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = M.abstract_params(cfg)
    _, cache = jax.eval_shape(
        lambda p, t: M.prefill(cfg, p, {"tokens": t}, 4096),
        params, jax.ShapeDtypeStruct((1, 8), jnp.int32))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1), jnp.int32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    c = decode_jit(cfg).lower(on_chip(params), on_chip(cache),
                              on_chip(batch)).compile()
    nbytes = sum(a.size * a.dtype.itemsize
                 for a in jax.tree_util.tree_leaves(cache))
    assert c.memory_analysis().alias_size_in_bytes >= nbytes
    assert not re.search(r"= \w+\[(1,)?1,4096,8,64\]\S* "
                         r"(fusion|copy|dynamic-update-slice)\(", c.as_text())
