"""jit'd wrappers around the Pallas kernels.

Each op accepts model-native layouts, rearranges to the kernel layout, and
dispatches to the Pallas kernel (``impl="pallas"``) or the pure-jnp oracle
(``impl="ref"``).  ``impl`` defaults to the kernels on a TPU backend and to
the oracle elsewhere (Mosaic does not lower to the CPU backend).  A Pallas
kernel runs in interpret mode only when the caller passes
``interpret=True``: off the TPU, ``impl="pallas"`` without it fails to
lower instead of silently running the interpreter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.comm_quant import dequantize_int8 as _deq_k
from repro.kernels.comm_quant import quantize_int8 as _q_k
from repro.kernels.decode_attention import decode_attention as _dec_k
from repro.kernels.flash_attention import flash_attention as _fa_k
from repro.kernels.rmsnorm import rmsnorm as _rms_k
from repro.kernels.ssd_scan import ssd_scan_kernel as _ssd_k


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_impl() -> str:
    return "pallas" if on_tpu() else "ref"


# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, impl: str | None = None,
                    interpret: bool = False):
    """Model layout q: (B,S,H,D), k/v: (B,T,K,D) -> (B,S,H,D)."""
    impl = impl or default_impl()
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if impl == "pallas":
        o = _fa_k(qt, kt, vt, causal=causal, interpret=interpret)
    else:
        o = _ref.flash_attention(qt, kt, vt, causal=causal)
    return o.transpose(0, 2, 1, 3)


def decode_attention(q, k, v, kv_len, *, impl: str | None = None,
                     interpret: bool = False):
    """Model layout q: (B,1,H,D), k/v: (B,S,K,D), kv_len (B,) -> (B,1,H,D)."""
    impl = impl or default_impl()
    B, _, H, D = q.shape
    K = k.shape[2]
    G = H // K
    qt = q.reshape(B, H, D).reshape(B, K, G, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if impl == "pallas":
        o = _dec_k(qt, kt, vt, kv_len, interpret=interpret)
    else:
        o = _ref.decode_attention(qt, kt, vt, kv_len)
    return o.reshape(B, H, D)[:, None]


def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 256, impl: str | None = None,
             interpret: bool = False):
    """Model layout x: (B,S,H,P), dt: (B,S,H), A: (H,), Bm/Cm: (B,S,G,N).
    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    impl = impl or default_impl()
    if impl == "ref":
        return _ref.ssd_scan(x, dt, A, Bm, Cm)
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    L = min(chunk, S) if S % min(chunk, S) == 0 else chunk
    pad = (-S) % L
    xf = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    dtf = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    Bf = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Cf = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nc = Sp // L
    xk = xf.reshape(B, nc, L, H, P).transpose(0, 3, 1, 2, 4)       # (B,H,nc,L,P)
    dtk = dtf.reshape(B, nc, L, H).transpose(0, 3, 1, 2)[:, :, :, None]  # (B,H,nc,1,L)
    dak = dtk * A[None, :, None, None, None].astype(dtk.dtype)
    Bk = Bf.reshape(B, nc, L, G, N).transpose(0, 3, 1, 2, 4)        # (B,G,nc,L,N)
    Ck = Cf.reshape(B, nc, L, G, N).transpose(0, 3, 1, 2, 4)
    y, st = _ssd_k(xk, dtk, dak, Bk, Ck, chunk=L, interpret=interpret)
    y = y.transpose(0, 2, 3, 1, 4).reshape(B, Sp, H, P)[:, :S]
    return y, st


def rmsnorm(x, scale, *, eps: float = 1e-6, impl: str | None = None,
            interpret: bool = False):
    impl = impl or default_impl()
    if impl == "pallas":
        return _rms_k(x, scale, eps=eps, interpret=interpret)
    return _ref.rmsnorm(x, scale, eps=eps)


def quantize_int8(x, *, impl: str | None = None, interpret: bool = False):
    impl = impl or default_impl()
    if impl == "pallas":
        return _q_k(x, interpret=interpret)
    return _ref.quantize_int8(x)


def dequantize_int8(q, scale, dtype=jnp.float32, *, impl: str | None = None,
                    interpret: bool = False):
    impl = impl or default_impl()
    if impl == "pallas":
        return _deq_k(q, scale, dtype, interpret=interpret)
    return _ref.dequantize_int8(q, scale, dtype)
