"""Fused RMSNorm as a Pallas TPU kernel (row-blocked, fp32 reduction)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                   # (br, D)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) *
                  s_ref[...].astype(jnp.float32)[None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "eps", "interpret"))
def rmsnorm(x, scale, *, br: int = 256, eps: float = 1e-6,
            interpret: bool = False):
    """x: (..., D); scale: (D,).  Row-blocked fused norm."""
    orig_shape = x.shape
    D = x.shape[-1]
    xf = x.reshape(-1, D)
    n = xf.shape[0]
    br = min(br, n)
    pad = (-n) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    nb = xf.shape[0] // br

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((br, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xf, scale)
    return out[:n].reshape(orig_shape)
