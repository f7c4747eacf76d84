"""Per-row symmetric int8 quantization: Pallas TPU kernels plus the
canonical leaf helpers every consumer shares.

This is the communication-overhead reducer of the framework — the AVEC wire
codec (``core.serialization``, codec ``int8``) and the compressed cross-pod
gradient all-reduce (``optim.compression``) both quantize through THIS
module, so the math exists exactly once: ``scale = max(absmax_row, 1e-12)
/ 127``, ``q = clip(round(x / scale), -127, 127)``.

**Error bound.**  Per element, ``|x - q*scale| <= scale/2 =
max(absmax_row, 1e-12)/254`` (round-to-nearest never clips: ``x/scale``
peaks at exactly 127 for the row max), i.e. a per-row max abs error of
``absmax_row/254`` plus float32 arithmetic eps.  Tests and the
``comm_quant_narrow_link`` bench gate on this bound.

Leaf layout: a leaf of any rank is quantized over :func:`leaf_rows` — rank
>= 2 collapses leading axes onto rows of the final axis, rank 0/1 becomes
a single row — so per-row scales track the final-axis distribution and the
(rows,) scale vector stays small on the wire.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Canonical leaf helpers (one implementation for wire codec + optimizer)
# ---------------------------------------------------------------------------

def leaf_rows(x):
    """Canonical 2-D per-row view of a leaf for row-scaled quantization
    (works for numpy and jax arrays; rank 0/1 becomes one row)."""
    return x.reshape(-1, x.shape[-1]) if x.ndim >= 2 else x.reshape(1, -1)


def quantize_int8_np(x) -> tuple[np.ndarray, np.ndarray]:
    """NumPy mirror of the kernel math for the wire hot path (no jit
    dispatch per frame).  ``x`` (any rank, any layout — non-contiguous
    views are fine) -> ``(q int8 (rows, cols), scale f32 (rows, 1))``."""
    flat = np.ascontiguousarray(leaf_rows(np.asarray(x)), dtype=np.float32)
    absmax = np.max(np.abs(flat), axis=1, keepdims=True) if flat.size \
        else np.zeros((flat.shape[0], 1), np.float32)
    scale = np.maximum(absmax, 1e-12) / 127.0
    q = np.clip(np.rint(flat / scale), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32)


def dequantize_int8_np(q, scale, dtype=np.float32) -> np.ndarray:
    """Inverse of :func:`quantize_int8_np` (still (rows, cols); reshape is
    the caller's because only it knows the original leaf shape)."""
    return (np.asarray(q).astype(np.float32) * np.asarray(scale)).astype(dtype)


def quantize_leaf(x, *, impl: str = "ref"):
    """jax-path leaf quantization over :func:`leaf_rows` (shared by
    ``optim.compression``); dispatches pallas/ref via ``kernels.ops``."""
    from repro.kernels import ops
    return ops.quantize_int8(leaf_rows(x).astype(jnp.float32), impl=impl)


def dequantize_leaf(q, s, shape, dtype, *, impl: str = "ref"):
    from repro.kernels import ops
    out = ops.dequantize_int8(q, s, jnp.float32, impl=impl)
    return out.reshape(shape).astype(dtype)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                   # (br, D)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def quantize_int8(x, *, br: int = 256, interpret: bool = False):
    """x: (N, D) -> (q int8 (N, D), scale f32 (N, 1))."""
    n, D = x.shape
    br = min(br, n)
    pad = (-n) % br
    xf = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    nb = xf.shape[0] // br
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, D), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct(xf.shape, jnp.int8),
                   jax.ShapeDtypeStruct((xf.shape[0], 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xf)
    return q[:n], s[:n]


@functools.partial(jax.jit, static_argnames=("dtype", "br", "interpret"))
def dequantize_int8(q, scale, dtype=jnp.float32, *, br: int = 256,
                    interpret: bool = False):
    n, D = q.shape
    br = min(br, n)
    pad = (-n) % br
    qf = jnp.pad(q, ((0, pad), (0, 0))) if pad else q
    sf = jnp.pad(scale, ((0, pad), (0, 0))) if pad else scale
    nb = qf.shape[0] // br
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(qf.shape, dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(qf, sf)
    return out[:n]
