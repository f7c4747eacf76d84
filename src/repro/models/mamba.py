"""Mamba2 block: gated SSD mixer with causal depthwise conv.

Layout follows the Mamba2 reference: separate z/x/B/C/dt projections (split
here so x-path channels shard over the model axis while B/C stay replicated),
causal depthwise conv over (x, B, C), softplus-discretized dt, SSD scan,
D skip, gated RMSNorm, output projection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import ParamSpec
from repro.models.ssd import ssd_chunked, ssd_step


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def mamba_specs(cfg) -> dict:
    d, ssm = cfg.d_model, cfg.ssm
    di = ssm.d_inner(d)
    nh = ssm.n_heads(d)
    gn = ssm.n_groups * ssm.d_state
    ck = ssm.conv_kernel
    return {
        "wz": ParamSpec((d, di), ("embed", "mlp"), "normal", d ** -0.5),
        "wx": ParamSpec((d, di), ("embed", "mlp"), "normal", d ** -0.5),
        "wB": ParamSpec((d, gn), ("embed", None), "normal", d ** -0.5),
        "wC": ParamSpec((d, gn), ("embed", None), "normal", d ** -0.5),
        "wdt": ParamSpec((d, nh), ("embed", "ssm_heads"), "normal", d ** -0.5),
        "dt_bias": ParamSpec((nh,), ("ssm_heads",), "mamba_dt_bias", dtype=jnp.float32),
        "A_log": ParamSpec((nh,), ("ssm_heads",), "mamba_a_log", dtype=jnp.float32),
        "D": ParamSpec((nh,), ("ssm_heads",), "ones", dtype=jnp.float32),
        "conv_x": ParamSpec((ck, di), (None, "mlp"), "normal", ck ** -0.5),
        "conv_B": ParamSpec((ck, gn), (None, None), "normal", ck ** -0.5),
        "conv_C": ParamSpec((ck, gn), (None, None), "normal", ck ** -0.5),
        "conv_bx": ParamSpec((di,), ("mlp",), "zeros"),
        "conv_bB": ParamSpec((gn,), (None,), "zeros"),
        "conv_bC": ParamSpec((gn,), (None,), "zeros"),
        "norm_scale": ParamSpec((di,), ("mlp",), "ones", dtype=jnp.float32),
        "wo": ParamSpec((di, d), ("mlp", "embed"), "normal", di ** -0.5),
    }


# ---------------------------------------------------------------------------
# Causal depthwise conv (sequence path)
# ---------------------------------------------------------------------------

def _causal_conv(x, w, b):
    """x: (B,S,C); w: (ck,C) depthwise; left-padded causal conv + silu."""
    ck = w.shape[0]
    C = x.shape[-1]
    xp = jnp.pad(x, ((0, 0), (ck - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp, w[:, None, :].astype(x.dtype),       # (ck, 1, C) WIO depthwise
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=C)
    return jax.nn.silu(out + b.astype(x.dtype))


def _conv_step(window, w, b):
    """window: (B,ck,C) last ck inputs (current included); returns (B,C)."""
    out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w.astype(jnp.float32))
    return jax.nn.silu(out + b.astype(jnp.float32)).astype(window.dtype)


def _gated_norm(y, z, scale, eps):
    """Mamba2 gated RMSNorm: rmsnorm(y * silu(z)) * scale."""
    yf = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale).astype(y.dtype)


def _project(cfg, p, x):
    dt_ = x.dtype
    z = x @ p["wz"].astype(dt_)
    xr = x @ p["wx"].astype(dt_)
    Br = x @ p["wB"].astype(dt_)
    Cr = x @ p["wC"].astype(dt_)
    dt = jax.nn.softplus(
        (x.astype(jnp.float32) @ p["wdt"].astype(jnp.float32)) + p["dt_bias"])
    return z, xr, Br, Cr, dt


# ---------------------------------------------------------------------------
# Sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def mamba_forward(cfg, p, x, *, return_cache: bool = False):
    """x: (B,S,d) -> (out, cache|None).  Cache: {"conv": (B,ck-1,conv_dim),
    "ssm": (B,H,P,N)}."""
    ssm = cfg.ssm
    B_, S, d = x.shape
    di = ssm.d_inner(d)
    nh = ssm.n_heads(d)
    hd = ssm.head_dim
    g, n = ssm.n_groups, ssm.d_state

    z, xr, Br, Cr, dt = _project(cfg, p, x)
    xr_pre, Br_pre, Cr_pre = xr, Br, Cr                 # pre-conv (for cache)
    xr = _causal_conv(xr, p["conv_x"], p["conv_bx"])
    Br = _causal_conv(Br, p["conv_B"], p["conv_bB"])
    Cr = _causal_conv(Cr, p["conv_C"], p["conv_bC"])

    A = -jnp.exp(p["A_log"])
    xh = xr.reshape(B_, S, nh, hd)
    Bh = Br.reshape(B_, S, g, n)
    Ch = Cr.reshape(B_, S, g, n)
    y, final_state = ssd_chunked(xh, dt, A, Bh, Ch, ssm.chunk)
    y = y + (p["D"][None, None, :, None] * xh.astype(jnp.float32)).astype(y.dtype)
    y = y.reshape(B_, S, di)
    y = _gated_norm(y, z, p["norm_scale"], cfg.norm_eps)
    out = y @ p["wo"].astype(y.dtype)

    if not return_cache:
        return out, None
    ck = ssm.conv_kernel
    pre = jnp.concatenate([xr_pre, Br_pre, Cr_pre], axis=-1)  # (B,S,conv_dim)
    pad = max(ck - 1 - S, 0)
    window = jnp.pad(pre, ((0, 0), (pad, 0), (0, 0)))[:, -(ck - 1):, :]
    cache = {"conv": window, "ssm": final_state.astype(jnp.float32)}
    return out, cache


# ---------------------------------------------------------------------------
# Single-token decode
# ---------------------------------------------------------------------------

def mamba_decode(cfg, p, x, cache):
    """x: (B,1,d); cache {"conv": (B,ck-1,conv_dim), "ssm": (B,H,P,N)}."""
    ssm = cfg.ssm
    B_, _, d = x.shape
    di = ssm.d_inner(d)
    nh = ssm.n_heads(d)
    hd = ssm.head_dim
    g, n = ssm.n_groups, ssm.d_state
    gn = g * n

    z, xr, Br, Cr, dt = _project(cfg, p, x)
    pre = jnp.concatenate([xr, Br, Cr], axis=-1)        # (B,1,conv_dim)
    window = jnp.concatenate([cache["conv"].astype(pre.dtype), pre], axis=1)
    new_conv = window[:, 1:, :]

    xr_t = _conv_step(window[:, :, :di], p["conv_x"], p["conv_bx"])
    Br_t = _conv_step(window[:, :, di:di + gn], p["conv_B"], p["conv_bB"])
    Cr_t = _conv_step(window[:, :, di + gn:], p["conv_C"], p["conv_bC"])

    A = -jnp.exp(p["A_log"])
    y_t, new_state = ssd_step(
        cache["ssm"], xr_t.reshape(B_, nh, hd), dt[:, 0],
        A, Br_t.reshape(B_, g, n), Cr_t.reshape(B_, g, n))
    y_t = y_t + (p["D"][None, :, None] * xr_t.reshape(B_, nh, hd).astype(jnp.float32)
                 ).astype(y_t.dtype)
    y = y_t.reshape(B_, 1, di)
    y = _gated_norm(y, z, p["norm_scale"], cfg.norm_eps)
    out = y @ p["wo"].astype(y.dtype)
    return out, {"conv": new_conv, "ssm": new_state}


def init_mamba_cache(cfg, batch: int, dtype=jnp.bfloat16) -> dict:
    ssm = cfg.ssm
    d = cfg.d_model
    di = ssm.d_inner(d)
    conv_dim = di + 2 * ssm.n_groups * ssm.d_state
    return {
        "conv": jnp.zeros((batch, ssm.conv_kernel - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, ssm.n_heads(d), ssm.head_dim, ssm.d_state),
                         jnp.float32),
    }
