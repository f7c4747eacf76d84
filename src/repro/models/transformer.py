"""Decoder-only stack: scan-over-blocks with optional remat.

Used by families dense / moe / ssm / hybrid / vlm.  Returns hidden states;
unembedding and losses live in ``repro.models.model``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.blocks import apply_block, block_specs, num_blocks, stacked_cache
from repro.models.layers import embed_specs, embed_tokens, norm_specs, apply_norm
from repro.models.params import stack_specs


def lm_specs(cfg) -> dict:
    return {
        "embed": embed_specs(cfg),
        "blocks": stack_specs(block_specs(cfg), num_blocks(cfg), "layers"),
        "final_norm": norm_specs(cfg),
    }


def _positions(tokens):
    B, S = tokens.shape
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def lm_hidden(cfg, params, tokens, *, context=None):
    """Train-path forward to final hidden states (B, S, d)."""
    positions = _positions(tokens)
    h = embed_tokens(cfg, params["embed"], tokens)

    def body(carry, bp):
        hh = carry
        hh, _, aux = apply_block(cfg, bp, hh, positions=positions, mode="train",
                                 cache=None, context=context)
        return hh, aux

    if cfg.remat:
        body = jax.checkpoint(body)
    h, auxs = jax.lax.scan(body, h, params["blocks"],
                           unroll=True if cfg.unroll_blocks else 1)
    h = apply_norm(cfg, params["final_norm"], h)
    return h, jnp.sum(auxs)


def lm_prefill(cfg, params, tokens, cache_len: int, *, context=None):
    """Prefill: returns (h (B,S,d), stacked cache, the MoE layers' counts
    summed over layers: int32 (rows routed to held experts, held experts
    hit))."""
    positions = _positions(tokens)
    h = embed_tokens(cfg, params["embed"], tokens)

    def body(hh, bp):
        hh, nc, counts = apply_block(cfg, bp, hh, positions=positions,
                                     mode="prefill", cache=None,
                                     cache_len=cache_len, context=context)
        return hh, (nc, counts)

    h, (cache, counts) = jax.lax.scan(body, h, params["blocks"],
                                      unroll=True if cfg.unroll_blocks else 1)
    h = apply_norm(cfg, params["final_norm"], h)
    return h, cache, jnp.sum(counts, axis=0)


def lm_decode_step(cfg, params, cache, tokens, pos, *, context=None):
    """One-token decode.  tokens: (B,1); pos: () shared or (B,) per-row int32.
    Returns (h, cache, MoE counts as ``lm_prefill``).  The stacked cache
    rides in the scan's carry and each block writes only what it changes
    into it, so with the cache donated the step updates it in place."""
    B = tokens.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None] if pos.ndim == 1 else jnp.full((B, 1), pos, jnp.int32)
    h = embed_tokens(cfg, params["embed"], tokens)

    def body(carry, xs):
        hh, cc = carry
        i, bp = xs
        hh, cc, counts = apply_block(cfg, bp, hh, positions=positions,
                                     mode="decode", cache=cc, pos=pos, layer=i,
                                     context=context)
        return (hh, cc), counts

    (h, cache), counts = jax.lax.scan(
        body, (h, cache), (jnp.arange(num_blocks(cfg)), params["blocks"]),
        unroll=True if cfg.unroll_blocks else 1)
    h = apply_norm(cfg, params["final_norm"], h)
    return h, cache, jnp.sum(counts, axis=0)


def lm_init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    return stacked_cache(cfg, batch, max_len, dtype)
