"""Shared primitive layers: norms, embeddings, rotary embeddings, linear."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.params import ParamSpec


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

def norm_specs(cfg) -> dict:
    if cfg.norm == "rmsnorm":
        return {"scale": ParamSpec((cfg.d_model,), ("embed",), "ones", dtype=jnp.float32)}
    return {"scale": ParamSpec((cfg.d_model,), ("embed",), "ones", dtype=jnp.float32),
            "bias": ParamSpec((cfg.d_model,), ("embed",), "zeros", dtype=jnp.float32)}


def apply_norm(cfg, p, x):
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_specs(cfg) -> dict:
    specs = {"tok": ParamSpec((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"),
                              "normal", 0.02)}
    if not cfg.tie_embeddings:
        specs["head"] = ParamSpec((cfg.d_model, cfg.padded_vocab), ("embed", "vocab"),
                                  "normal", cfg.d_model ** -0.5)
    return specs


def embed_tokens(cfg, p, tokens):
    h = p["tok"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    return h


def unembed(cfg, p, h):
    """Project to padded-vocab logits (divided by ``logits_scaling``); pad
    region masked to -inf so softmax / sampling are exact over the real
    vocab."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, p["tok"].astype(h.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, p["head"].astype(h.dtype))
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
    return jnp.where(pad_mask[None, None, :], -1e30, logits)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                          # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[..., None, :]                   # (B, S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_at(positions, d: int):
    """Whisper-style sinusoidal embeddings evaluated at ``positions``
    (any int array); returns positions.shape + (d,)."""
    dim = jnp.arange(d // 2, dtype=jnp.float32)
    inv = jnp.exp(-dim * (jnp.log(10000.0) / max(d // 2 - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def sinusoidal_positions(n: int, d: int):
    return sinusoidal_at(jnp.arange(n), d)


# ---------------------------------------------------------------------------
# Linear helpers
# ---------------------------------------------------------------------------

def linear_specs(d_in: int, d_out: int, axes, *, bias: bool, scale=None) -> dict:
    specs = {"w": ParamSpec((d_in, d_out), axes, "normal",
                            scale if scale is not None else d_in ** -0.5)}
    if bias:
        specs["b"] = ParamSpec((d_out,), (axes[1],), "zeros")
    return specs


def apply_linear(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y
