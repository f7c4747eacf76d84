"""Unified model API — family dispatch.

Functions (all pure; cfg is static):
  param_specs(cfg)                       -> ParamSpec tree
  init_params(cfg, rng)                  -> concrete params
  abstract_params(cfg)                   -> ShapeDtypeStruct params (dry-run)
  forward_hidden(cfg, params, batch)     -> (h, aux)
  logits(cfg, params, h)                 -> (B,S,V) fp32
  loss_fn(cfg, params, batch)            -> (loss, metrics)
  prefill(cfg, params, batch, cache_len) -> (last_logits, cache)
  decode_step(cfg, params, cache, batch) -> (logits, cache)
    (either with ``expert_counts=True`` adds the MoE layers' counts: int32
    (rows routed to held experts, held experts hit), summed over layers)
  init_cache(cfg, batch, max_len)        -> cache pytree
  input_specs(cfg, shape)                -> ShapeDtypeStruct batch (dry-run)

Batch dicts: {"tokens": (B,S) int32, "targets": (B,S) int32} plus family
extras — vlm: "vision" (B,Tv,d); encdec: "frames" (B,F,d); decode batches:
{"tokens": (B,1), "pos": () int32} (+ frozen "vision" context for vlm).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import encdec as ed
from repro.models import params as pm
from repro.models import transformer as tf
from repro.models.layers import unembed

IGNORE = -1  # target id excluded from the loss


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def param_specs(cfg):
    if cfg.family == "encdec":
        return ed.encdec_specs(cfg)
    return tf.lm_specs(cfg)


def init_params(cfg, rng):
    return pm.init_params(param_specs(cfg), rng, jnp.dtype(cfg.param_dtype))


def abstract_params(cfg):
    return pm.abstract_params(param_specs(cfg), jnp.dtype(cfg.param_dtype))


# ---------------------------------------------------------------------------
# Forward / losses
# ---------------------------------------------------------------------------

def _context(cfg, batch):
    if cfg.family == "vlm":
        return batch["vision"]
    return None


def forward_hidden(cfg, params, batch):
    if cfg.family == "encdec":
        enc = ed.encode(cfg, params, batch["frames"])
        return ed.dec_hidden(cfg, params, batch["tokens"], enc), jnp.zeros((), jnp.float32)
    h, aux = tf.lm_hidden(cfg, params, batch["tokens"], context=_context(cfg, batch))
    return h, aux


def logits_from_hidden(cfg, params, h):
    return unembed(cfg, params["embed"], h)


def _xent_full(cfg, params, h, targets):
    lg = logits_from_hidden(cfg, params, h)              # (B,S,Vp) fp32
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.clip(targets, 0, cfg.padded_vocab - 1)
    gold = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = (targets != IGNORE).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_fn(cfg, params, batch):
    h, aux = forward_hidden(cfg, params, batch)
    xent = _xent_full(cfg, params, h, batch["targets"])
    loss = xent + aux
    return loss, {"loss": loss, "xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# Serving paths
# ---------------------------------------------------------------------------

def prefill(cfg, params, batch, cache_len: int, *,
            expert_counts: bool = False):
    """Returns (last-token logits (B,1,V), cache[, MoE counts])."""
    counts = jnp.zeros((2,), jnp.int32)
    if cfg.family == "encdec":
        enc = ed.encode(cfg, params, batch["frames"])
        h, cache = ed.dec_prefill(cfg, params, batch["tokens"], enc, cache_len)
    else:
        h, cache, counts = tf.lm_prefill(cfg, params, batch["tokens"],
                                         cache_len,
                                         context=_context(cfg, batch))
    lg = logits_from_hidden(cfg, params, h[:, -1:])
    return (lg, cache, counts) if expert_counts else (lg, cache)


def decode_step(cfg, params, cache, batch, *, expert_counts: bool = False):
    """batch: {"tokens": (B,1), "pos": ()} (+ "vision" context for vlm).
    Returns (logits (B,1,V), new cache[, MoE counts])."""
    counts = jnp.zeros((2,), jnp.int32)
    if cfg.family == "encdec":
        h, cache = ed.dec_step(cfg, params, cache, batch["tokens"], batch["pos"])
    else:
        h, cache, counts = tf.lm_decode_step(cfg, params, cache,
                                             batch["tokens"], batch["pos"],
                                             context=_context(cfg, batch))
    lg = logits_from_hidden(cfg, params, h)
    return (lg, cache, counts) if expert_counts else (lg, cache)


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    if cfg.family == "encdec":
        return ed.encdec_init_cache(cfg, batch, max_len, dtype)
    return tf.lm_init_cache(cfg, batch, max_len, dtype)


# ---------------------------------------------------------------------------
# Dry-run input specs (no allocation)
# ---------------------------------------------------------------------------

def input_specs(cfg, shape) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
    B, S = shape.global_batch, shape.seq_len
    i32, cdt = jnp.int32, jnp.dtype(cfg.compute_dtype)
    if shape.kind == "train":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                 "targets": jax.ShapeDtypeStruct((B, S), i32)}
    elif shape.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    else:  # decode: one new token against a seq_len-deep cache
        batch = {"tokens": jax.ShapeDtypeStruct((B, 1), i32),
                 "pos": jax.ShapeDtypeStruct((), i32)}
    if cfg.family == "vlm":
        batch["vision"] = jax.ShapeDtypeStruct((B, cfg.num_vision_tokens,
                                                cfg.d_model), cdt)
    if cfg.family == "encdec" and shape.kind != "decode":
        batch["frames"] = jax.ShapeDtypeStruct((B, cfg.num_audio_frames,
                                                cfg.d_model), cdt)
    return batch


def abstract_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype))
