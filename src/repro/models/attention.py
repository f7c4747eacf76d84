"""GQA attention: train / prefill / decode paths over one core, ``sdpa``,
which upcasts Q/K/V to float32 and materializes the whole score tensor.

KV caches are plain pytrees: {"k": (B, S_max, K, hd), "v": (B, S_max, K, hd)};
decode reads and writes them stacked over layers, (L, B, S_max, K, hd).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope
from repro.models.params import ParamSpec

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def attn_specs(cfg) -> dict:
    d, H, K, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    specs = {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", "head_dim"), "normal", d ** -0.5),
        "wk": ParamSpec((d, K, hd), ("embed", "kv_heads", "head_dim"), "normal", d ** -0.5),
        "wv": ParamSpec((d, K, hd), ("embed", "kv_heads", "head_dim"), "normal", d ** -0.5),
        "wo": ParamSpec((H, hd, d), ("heads", "head_dim", "embed"), "normal",
                        (H * hd) ** -0.5),
    }
    if cfg.use_bias:
        specs["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), "zeros")
        specs["bk"] = ParamSpec((K, hd), ("kv_heads", "head_dim"), "zeros")
        specs["bv"] = ParamSpec((K, hd), ("kv_heads", "head_dim"), "zeros")
        specs["bo"] = ParamSpec((d,), ("embed",), "zeros")
    return specs


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------

def _project_q(cfg, p, x, positions, rope: bool):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
    return q


def _project_kv(cfg, p, x, positions, rope: bool):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if rope:
        k = apply_rope(k, positions, cfg.rope_theta)
    return k, v


def _out_proj(p, o):
    B, S = o.shape[:2]
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(o.dtype))
    if "bo" in p:
        y = y + p["bo"].astype(o.dtype)
    return y


# ---------------------------------------------------------------------------
# Core scaled-dot-product attention (GQA), mask by positions
# ---------------------------------------------------------------------------

def sdpa(q, k, v, q_pos, kv_pos, causal: bool, scale: float = 0.0):
    """q: (B,S,H,hd); k/v: (B,T,K,hd); q_pos: (B,S) | None; kv_pos: (B,T) | None.
    Scores and the weighted sum over values are float32; scores are scaled
    by ``scale`` (0: hd ** -0.5).  Returns (B,S,H,hd) in q's dtype."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale or hd ** -0.5
    qr = q.reshape(B, S, K, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bskgh,btkh->bkgst", qr,
                        k.astype(jnp.float32)) * scale       # (B,K,G,S,T)
    if causal:
        mask = kv_pos[:, None, :] <= q_pos[:, :, None]        # (B,S,T)
        scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", probs, v.astype(jnp.float32))
    return o.reshape(B, S, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Self-attention entry points
# ---------------------------------------------------------------------------

def self_attention(cfg, p, x, positions, *, rope: bool = True, causal: bool = True):
    """Full self-attention (train path; bidirectional for encoders).  x: (B,S,d)."""
    q = _project_q(cfg, p, x, positions, rope)
    k, v = _project_kv(cfg, p, x, positions, rope)
    o = sdpa(q, k, v, positions, positions, causal, cfg.attn_scale)
    return _out_proj(p, o)


def self_attention_prefill(cfg, p, x, positions, cache_len: int, *, rope: bool = True):
    """Causal self-attention that also builds the KV cache (padded to
    cache_len).  Returns (out, cache).  The attention core and the cache's
    padding run under the scope ``avec.sdpa``, the projections outside."""
    B, S, _ = x.shape
    q = _project_q(cfg, p, x, positions, rope)
    k, v = _project_kv(cfg, p, x, positions, rope)
    with jax.named_scope("avec.sdpa"):
        o = sdpa(q, k, v, positions, positions, True, cfg.attn_scale)
        pad = cache_len - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return _out_proj(p, o), {"k": kc, "v": vc}


def self_attention_decode(cfg, p, x, cache, layer, pos, *, rope: bool = True):
    """One-token decode against a cache stacked over layers.  x: (B,1,d);
    cache k/v: (L,B,S_max,K,hd); layer: this layer's index into them; pos:
    () int32 shared write index, or (B,) per-row indices (continuous
    batching).  Returns (out, the stacked k/v with this token written at
    (layer, :, pos)): the write is one token, so a cache carried through
    the layer scan and donated is updated in place, never copied whole.
    The one-token K/V write, the K/V read and the attention core run under
    the scope ``avec.sdpa``, the projections outside."""
    B = x.shape[0]
    S_max = cache["k"].shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((B, 1), pos, jnp.int32)
    q = _project_q(cfg, p, x, positions, rope)
    k_new, v_new = _project_kv(cfg, p, x, positions, rope)
    with jax.named_scope("avec.sdpa"):
        if per_row:
            rows = jnp.arange(B)
            kc = cache["k"].at[layer, rows, pos].set(
                k_new[:, 0].astype(cache["k"].dtype))
            vc = cache["v"].at[layer, rows, pos].set(
                v_new[:, 0].astype(cache["v"].dtype))
        else:
            at = (layer, 0, pos, 0, 0)
            kc = jax.lax.dynamic_update_slice(
                cache["k"], k_new[None].astype(cache["k"].dtype), at)
            vc = jax.lax.dynamic_update_slice(
                cache["v"], v_new[None].astype(cache["v"].dtype), at)
        k = jax.lax.dynamic_index_in_dim(kc, layer, 0, keepdims=False)
        v = jax.lax.dynamic_index_in_dim(vc, layer, 0, keepdims=False)
        kv_pos = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32)[None],
                                  (B, S_max))
        o = sdpa(q, k, v, positions, kv_pos, True, cfg.attn_scale)
    return _out_proj(p, o), {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder, llama-vision image layers)
# ---------------------------------------------------------------------------

def cross_attention(cfg, p, x, context):
    """Bidirectional cross-attention; context: (B, Tc, d)."""
    B, S, _ = x.shape
    zeros_q = jnp.zeros((B, S), jnp.int32)
    zeros_k = jnp.zeros((B, context.shape[1]), jnp.int32)
    q = _project_q(cfg, p, x, zeros_q, rope=False)
    k, v = _project_kv(cfg, p, context, zeros_k, rope=False)
    o = sdpa(q, k, v, None, None, False, cfg.attn_scale)
    return _out_proj(p, o)


def cross_attention_cached(cfg, p, x, cache, layer):
    """Decode-time cross-attention against precomputed context KV, stacked
    over layers: cache cross_k/cross_v (L,B,Tc,K,hd); layer: this layer's
    index into them."""
    B, S, _ = x.shape
    zeros_q = jnp.zeros((B, S), jnp.int32)
    q = _project_q(cfg, p, x, zeros_q, rope=False)
    k, v = (jax.lax.dynamic_index_in_dim(cache[n], layer, 0, keepdims=False)
            for n in ("cross_k", "cross_v"))
    o = sdpa(q, k, v, None, None, False, cfg.attn_scale)
    return _out_proj(p, o)


def cross_kv(cfg, p, context):
    zeros_k = jnp.zeros((context.shape[0], context.shape[1]), jnp.int32)
    k, v = _project_kv(cfg, p, context, zeros_k, rope=False)
    return {"cross_k": k, "cross_v": v}


def init_attn_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    K, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, max_len, K, hd), dtype),
            "v": jnp.zeros((batch, max_len, K, hd), dtype)}
