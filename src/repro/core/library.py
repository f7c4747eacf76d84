"""Executor library adapters: expose repro model zoo + OpenPose-lite as
destination-executable libraries (the "Caffe" of this reproduction).

Library functions have signature ``fn(params, state, args) -> outputs`` where
``state`` is the mutable per-session dict (serving caches live there, which
is what migration snapshots)."""
from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M


def decode_jit(cfg):
    """The decode step, jitted with its cache argument donated: the step
    writes the new token into the cache's buffers in place
    (``models.transformer.lm_decode_step``), and the call deletes the
    arrays it was given."""
    def _decode(params, cache, batch):
        return M.decode_step(cfg, params, cache, batch)

    return jax.jit(_decode, donate_argnums=1)


def make_model_library(cfg, max_cache_len: int = 256) -> dict:
    """Serving library for one ModelConfig: score / prefill / decode."""

    @jax.jit
    def _loss(params, batch):
        return M.loss_fn(cfg, params, batch)[0]

    @functools.partial(jax.jit, static_argnames=())
    def _prefill(params, batch):
        return M.prefill(cfg, params, batch, max_cache_len,
                         cache_dtype=jnp.float32)

    _decode = decode_jit(cfg)

    def score(params, state, args):
        return {"loss": _loss(params, args)}

    def prefill(params, state, args):
        logits, cache = _prefill(params, args)
        state["cache"] = cache
        state["pos"] = int(args["tokens"].shape[1])
        return {"logits": logits}

    def decode(params, state, args):
        batch = dict(args)
        batch["pos"] = jnp.asarray(state["pos"], jnp.int32)
        cache = state["cache"]
        # the step deletes the cache it is given; where something besides
        # this state holds it (a caller's copy of the state), the holder
        # keeps it, and the step gets a copy.  Held here: the state,
        # ``cache`` and getrefcount's argument.
        if sys.getrefcount(cache) > 3:
            cache = jax.tree_util.tree_map(jnp.copy, cache)
        logits, state["cache"] = _decode(params, cache, batch)
        state["pos"] = int(state["pos"]) + 1
        return {"logits": logits}

    def hidden(params, state, args):
        h, _ = M.forward_hidden(cfg, params, args)
        return {"hidden": h}

    return {"score": score, "prefill": prefill, "decode": decode,
            "hidden": hidden}


def make_openpose_library(net) -> dict:
    """The paper's workload: the Caffe backbone as a destination library."""
    from repro.models.openpose import op_forward

    fwd = jax.jit(lambda params, frames: op_forward(net, params, frames))

    def forward(params, state, args):
        return {"beliefs": fwd(params, args["frames"])}

    return {"forward": forward}
