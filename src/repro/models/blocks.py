"""Layer-block assembly shared by all decoder families.

A *block* is the smallest repeating unit of the stack (1 layer for dense/moe,
``attn_every`` layers for jamba, ``cross_attn_every`` layers for the VLM).
All blocks of a model share one pytree structure, so block parameters are
stacked with a leading dimension and the stack is applied with
``jax.lax.scan`` — keeping HLO size O(block) instead of O(num_layers) for the
100-layer archs.

Per-layer cache entries (decode):
  attn layer  -> {"k", "v"}
  mamba layer -> {"conv", "ssm"}
  cross layer -> additionally {"cross_k", "cross_v"}
Prefill builds them per block and the scan stacks them.  Decode carries the
stacked cache through the scan instead: each layer reads its slice at the
block index and writes back only what it changes (one token of K/V; the
whole SSM state), so a donated cache is updated in place.

Each layer also returns ``aux``: in training the MoE load-balancing loss;
in prefill and decode the MoE layer's counts (``moe.moe_serve``), int32
(rows routed to held experts, held experts hit), zero without an MoE.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import mamba as mb
from repro.models.layers import norm_specs, apply_norm
from repro.models.mlp import mlp_specs, apply_mlp
from repro.models.moe import moe_specs, apply_moe, moe_serve
from repro.models.params import ParamSpec


def block_size(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.attn_every
    if cfg.family == "vlm" and cfg.cross_attn_every > 0:
        return cfg.cross_attn_every
    return 1


def num_blocks(cfg) -> int:
    bs = block_size(cfg)
    assert cfg.num_layers % bs == 0, (cfg.name, cfg.num_layers, bs)
    return cfg.num_layers // bs


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def _layer_specs(cfg, i: int) -> dict:
    """Specs for global layer index i (only i % block_size matters)."""
    kind = cfg.layer_kind(i)
    specs: dict = {"mixer_norm": norm_specs(cfg)}
    if kind == "attn":
        specs["attn"] = attn.attn_specs(cfg)
    else:
        specs["mamba"] = mb.mamba_specs(cfg)
    if cfg.layer_has_cross_attn(i):
        specs["cross_norm"] = norm_specs(cfg)
        specs["cross"] = attn.attn_specs(cfg)
        specs["cross_gate"] = ParamSpec((1,), (None,), "zeros", dtype=jnp.float32)
    if kind == "attn" or cfg.family != "ssm":
        # every non-pure-SSM layer has an FFN sublayer
        specs["ffn_norm"] = norm_specs(cfg)
        if cfg.layer_has_moe(i):
            specs["moe"] = moe_specs(cfg)
        else:
            specs["mlp"] = mlp_specs(cfg)
    return specs


def block_specs(cfg) -> dict:
    bs = block_size(cfg)
    return {"layers": [_layer_specs(cfg, j) for j in range(bs)]}


# ---------------------------------------------------------------------------
# Cache
# ---------------------------------------------------------------------------

def _layer_cache(cfg, i: int, batch: int, max_len: int, dtype) -> dict:
    kind = cfg.layer_kind(i)
    cache: dict = {}
    if kind == "attn":
        cache.update(attn.init_attn_cache(cfg, batch, max_len, dtype))
    else:
        cache.update(mb.init_mamba_cache(cfg, batch, dtype))
    if cfg.layer_has_cross_attn(i):
        K, hd = cfg.num_kv_heads, cfg.head_dim
        cache["cross_k"] = jnp.zeros((batch, cfg.num_vision_tokens, K, hd), dtype)
        cache["cross_v"] = jnp.zeros((batch, cfg.num_vision_tokens, K, hd), dtype)
    return cache


def block_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    bs = block_size(cfg)
    return {"layers": [_layer_cache(cfg, j, batch, max_len, dtype) for j in range(bs)]}


def stacked_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
    """Cache stacked over blocks (leading dim = num_blocks) for the scan."""
    nb = num_blocks(cfg)
    one = block_cache(cfg, batch, max_len, dtype)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (nb,) + x.shape).copy(), one)


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------

def _no_aux(mode: str):
    """A layer's ``aux`` when it adds none (see the module docstring)."""
    if mode == "train":
        return jnp.zeros((), jnp.float32)
    return jnp.zeros((2,), jnp.int32)


def _residual(cfg, h, y):
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return h + y


def _apply_layer(cfg, j: int, p: dict, h, *, positions, mode: str,
                 cache: dict | None, cache_len, pos, layer, context):
    """One layer.  Returns (h, new_cache, aux).  In prefill the new cache
    is this layer's, its K/V padded to ``cache_len``.  In decode, ``cache``
    holds this layer's leaves stacked over blocks and ``layer`` is the
    block index; the new cache is the stacked leaves, updated."""
    aux = _no_aux(mode)
    new_cache: dict = {}
    kind = "attn" if "attn" in p else "mamba"

    # ---- token mixer -------------------------------------------------------
    normed = apply_norm(cfg, p["mixer_norm"], h)
    rope = cfg.rope and cfg.family != "encdec"
    if kind == "attn":
        if mode == "train":
            mix = attn.self_attention(cfg, p["attn"], normed, positions, rope=rope)
        elif mode == "prefill":
            mix, kv = attn.self_attention_prefill(
                cfg, p["attn"], normed, positions, cache_len, rope=rope)
            new_cache.update(kv)
        else:  # decode
            mix, kv = attn.self_attention_decode(cfg, p["attn"], normed, cache,
                                                 layer, pos, rope=rope)
            new_cache.update(kv)
    else:
        if mode == "train":
            mix, _ = mb.mamba_forward(cfg, p["mamba"], normed, return_cache=False)
        elif mode == "prefill":
            mix, mc = mb.mamba_forward(cfg, p["mamba"], normed, return_cache=True)
            new_cache.update(mc)
        else:
            mix, mc = mb.mamba_decode(cfg, p["mamba"], normed, {
                n: jax.lax.dynamic_index_in_dim(cache[n], layer, 0, keepdims=False)
                for n in ("conv", "ssm")})
            new_cache.update({n: jax.lax.dynamic_update_index_in_dim(
                cache[n], mc[n].astype(cache[n].dtype), layer, 0) for n in mc})

    if cfg.parallel_block and "mlp" in p:
        # command-r style: shared-norm parallel attn + ffn residual
        y = apply_mlp(cfg, p["mlp"], normed)
        h = h + mix + y
        # cross/moe never combined with parallel_block in assigned archs
        return h, new_cache, aux

    h = _residual(cfg, h, mix)

    # ---- gated cross-attention (VLM) ---------------------------------------
    if "cross" in p:
        cn = apply_norm(cfg, p["cross_norm"], h)
        if mode == "decode":
            ca = attn.cross_attention_cached(cfg, p["cross"], cn, cache, layer)
            new_cache["cross_k"] = cache["cross_k"]
            new_cache["cross_v"] = cache["cross_v"]
        else:
            ca = attn.cross_attention(cfg, p["cross"], cn, context)
            if mode == "prefill":
                new_cache.update(attn.cross_kv(cfg, p["cross"], context))
        gate = jnp.tanh(p["cross_gate"]).astype(h.dtype)
        h = h + gate * ca

    # ---- FFN ----------------------------------------------------------------
    if "moe" in p:
        fn = apply_norm(cfg, p["ffn_norm"], h)
        with jax.named_scope("avec.moe"):
            y, aux = (apply_moe if mode == "train" else moe_serve)(
                cfg, p["moe"], fn)
        h = _residual(cfg, h, y)
    elif "mlp" in p:
        fn = apply_norm(cfg, p["ffn_norm"], h)
        h = _residual(cfg, h, apply_mlp(cfg, p["mlp"], fn))

    return h, new_cache, aux


def apply_block(cfg, p: dict, h, *, positions, mode: str, cache: dict | None,
                cache_len=None, pos=None, layer=None, context=None):
    """Apply one block (list of layers).  Returns (h, new_cache, aux), aux
    summed over the layers.  In prefill ``cache_len`` is the length the K/V
    cache is padded to; in decode ``cache`` is the whole stacked cache and
    ``layer`` the block's index into it."""
    aux = _no_aux(mode)
    new_layers = []
    for j, lp in enumerate(p["layers"]):
        lcache = cache["layers"][j] if cache is not None else None
        h, nc, a = _apply_layer(cfg, j, lp, h, positions=positions, mode=mode,
                                cache=lcache, cache_len=cache_len, pos=pos,
                                layer=layer, context=context)
        new_layers.append(nc)
        aux = aux + a
    return h, {"layers": new_layers}, aux
