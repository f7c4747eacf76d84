"""Whisper-style encoder-decoder backbone.

The conv/audio frontend is a stub: the encoder consumes precomputed frame
embeddings (B, F, d_model).  Positions are fixed sinusoidal (Whisper);
attention is bidirectional in the encoder, causal + cross in the decoder.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.layers import (apply_norm, embed_specs, embed_tokens,
                                 norm_specs, sinusoidal_at, sinusoidal_positions)
from repro.models.mlp import mlp_specs, apply_mlp
from repro.models.params import stack_specs


def _enc_layer_specs(cfg) -> dict:
    return {"mixer_norm": norm_specs(cfg), "attn": attn.attn_specs(cfg),
            "ffn_norm": norm_specs(cfg), "mlp": mlp_specs(cfg)}


def _dec_layer_specs(cfg) -> dict:
    return {"mixer_norm": norm_specs(cfg), "attn": attn.attn_specs(cfg),
            "cross_norm": norm_specs(cfg), "cross": attn.attn_specs(cfg),
            "ffn_norm": norm_specs(cfg), "mlp": mlp_specs(cfg)}


def encdec_specs(cfg) -> dict:
    return {
        "embed": embed_specs(cfg),
        "enc_blocks": stack_specs(_enc_layer_specs(cfg), cfg.enc_layers, "layers"),
        "enc_norm": norm_specs(cfg),
        "dec_blocks": stack_specs(_dec_layer_specs(cfg), cfg.num_layers, "layers"),
        "final_norm": norm_specs(cfg),
    }


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

def encode(cfg, params, frames):
    """frames: (B, F, d) stubbed frame embeddings -> encoder output (B, F, d)."""
    B, F, d = frames.shape
    pos = sinusoidal_positions(F, d).astype(frames.dtype)
    h = frames + pos[None]
    zeros = jnp.zeros((B, F), jnp.int32)

    def body(hh, p):
        n = apply_norm(cfg, p["mixer_norm"], hh)
        hh = hh + attn.self_attention(cfg, p["attn"], n, zeros, rope=False,
                                      causal=False)
        n = apply_norm(cfg, p["ffn_norm"], hh)
        hh = hh + apply_mlp(cfg, p["mlp"], n)
        return hh, None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["enc_blocks"],
                        unroll=True if cfg.unroll_blocks else 1)
    return apply_norm(cfg, params["enc_norm"], h)


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------

def _embed_dec(cfg, params, tokens, positions):
    h = embed_tokens(cfg, params["embed"], tokens)
    return h + sinusoidal_at(positions, cfg.d_model).astype(h.dtype)


def dec_hidden(cfg, params, tokens, enc_out):
    """Train path: (B,S) tokens + (B,F,d) encoder output -> (B,S,d)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    h = _embed_dec(cfg, params, tokens, positions)

    def body(hh, p):
        n = apply_norm(cfg, p["mixer_norm"], hh)
        hh = hh + attn.self_attention(cfg, p["attn"], n, positions, rope=False)
        n = apply_norm(cfg, p["cross_norm"], hh)
        hh = hh + attn.cross_attention(cfg, p["cross"], n, enc_out)
        n = apply_norm(cfg, p["ffn_norm"], hh)
        hh = hh + apply_mlp(cfg, p["mlp"], n)
        return hh, None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["dec_blocks"],
                        unroll=True if cfg.unroll_blocks else 1)
    return apply_norm(cfg, params["final_norm"], h)


def dec_prefill(cfg, params, tokens, enc_out, cache_len: int):
    """Returns (h, cache).  Cache per layer: self {"k","v"} + {"cross_k","cross_v"}."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    h = _embed_dec(cfg, params, tokens, positions)

    def body(hh, p):
        n = apply_norm(cfg, p["mixer_norm"], hh)
        mix, kv = attn.self_attention_prefill(cfg, p["attn"], n, positions,
                                              cache_len, rope=False)
        hh = hh + mix
        n = apply_norm(cfg, p["cross_norm"], hh)
        hh = hh + attn.cross_attention(cfg, p["cross"], n, enc_out)
        ckv = attn.cross_kv(cfg, p["cross"], enc_out)
        n = apply_norm(cfg, p["ffn_norm"], hh)
        hh = hh + apply_mlp(cfg, p["mlp"], n)
        return hh, {**kv, **ckv}

    h, cache = jax.lax.scan(body, h, params["dec_blocks"],
                            unroll=True if cfg.unroll_blocks else 1)
    return apply_norm(cfg, params["final_norm"], h), cache


def dec_step(cfg, params, cache, tokens, pos):
    """One-token decode.  tokens: (B,1); pos: () shared or (B,) per-row."""
    B = tokens.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((B, 1), pos, jnp.int32)
    h = _embed_dec(cfg, params, tokens, positions)

    def body(carry, xs):
        hh, cc = carry
        i, p = xs
        n = apply_norm(cfg, p["mixer_norm"], hh)
        mix, kv = attn.self_attention_decode(cfg, p["attn"], n, cc, i, pos,
                                             rope=False)
        hh = hh + mix
        n = apply_norm(cfg, p["cross_norm"], hh)
        hh = hh + attn.cross_attention_cached(cfg, p["cross"], n, cc, i)
        n = apply_norm(cfg, p["ffn_norm"], hh)
        hh = hh + apply_mlp(cfg, p["mlp"], n)
        return (hh, {**cc, **kv}), None

    (h, cache), _ = jax.lax.scan(
        body, (h, cache), (jnp.arange(cfg.num_layers), params["dec_blocks"]),
        unroll=True if cfg.unroll_blocks else 1)
    return apply_norm(cfg, params["final_norm"], h), cache


def encdec_init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    K, hd = cfg.num_kv_heads, cfg.head_dim
    L, F = cfg.num_layers, cfg.num_audio_frames
    return {
        "k": jnp.zeros((L, batch, max_len, K, hd), dtype),
        "v": jnp.zeros((L, batch, max_len, K, hd), dtype),
        "cross_k": jnp.zeros((L, batch, F, K, hd), dtype),
        "cross_v": jnp.zeros((L, batch, F, K, hd), dtype),
    }
