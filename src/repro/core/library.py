"""Executor library adapters: expose repro model zoo + OpenPose-lite as
destination-executable libraries (the "Caffe" of this reproduction).

Library functions have signature ``fn(params, state, args) -> outputs`` where
``state`` is the mutable per-session dict (serving caches live there, which
is what migration snapshots).  A library may keep counters of its own
(``Library.counters``), which the executor exposes beside its own views."""
from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitize as _sanitize
from repro.models import model as M


class Library(dict):
    """A destination library: its functions by name, and ``counters``, the
    ``*_total`` views it keeps: ``{name: (doc, samples)}`` with
    ``samples() -> [(labels, value)]``, read when the views are."""

    def __init__(self, fns: dict, counters: dict | None = None) -> None:
        super().__init__(fns)
        self.counters = counters or {}


class ExpertCounts:
    """The MoE layers' counts, per library function: rows routed to the
    held experts and held experts hit, summed over layers and calls.

    A call leaves its counts on the device and adds nothing to the host's
    work but a list append.  Reading the totals, or every ``FOLD`` calls,
    copies the pending counts to the host in one transfer (their calls
    have finished) and adds them up there: nothing is compiled."""

    FOLD = 1024
    FNS = ("prefill", "decode")

    def __init__(self) -> None:
        self._lock = _sanitize.make_lock("ExpertCounts._lock")
        self._pending = {fn: [] for fn in self.FNS}  # guarded-by: _lock
        self._totals = {fn: np.zeros(2, np.int64)    # guarded-by: _lock
                        for fn in self.FNS}

    def add(self, fn: str, counts) -> None:
        with self._lock:
            pending = self._pending[fn]
            pending.append(counts)
            if len(pending) < self.FOLD:
                return
            self._pending[fn] = []
        self._fold(fn, pending)

    def _fold(self, fn: str, arrays: list) -> None:
        add = np.sum(np.asarray(jax.device_get(arrays), np.int64)
                     .reshape(-1, 2), axis=0)
        with self._lock:
            self._totals[fn] += add

    def totals(self) -> dict:
        """``{fn: (rows, experts hit)}`` of every call so far."""
        with self._lock:
            taken = self._pending
            self._pending = {fn: [] for fn in self.FNS}
        for fn, arrays in taken.items():
            self._fold(fn, arrays)
        with self._lock:
            return {fn: tuple(int(v) for v in t)
                    for fn, t in self._totals.items()}

    def views(self) -> dict:
        """The two ``*_total`` views, one sample per library function."""
        def column(i):
            return lambda: [({"fn": fn}, t[i])
                            for fn, t in self.totals().items()]
        return {
            "avec_moe_routed_rows_total": (
                "Rows (token, expert) that MoE layers routed to the "
                "experts they hold, summed over layers and calls.",
                column(0)),
            "avec_moe_experts_hit_total": (
                "Held experts that received at least one row, summed over "
                "MoE layers and calls.", column(1)),
        }


def decode_jit(cfg, expert_counts: bool = False):
    """The decode step, jitted with its cache argument donated: the step
    writes the new token into the cache's buffers in place
    (``models.transformer.lm_decode_step``), and the call deletes the
    arrays it was given."""
    def _decode(params, cache, batch):
        return M.decode_step(cfg, params, cache, batch,
                             expert_counts=expert_counts)

    return jax.jit(_decode, donate_argnums=1)


def make_model_library(cfg, max_cache_len: int = 256) -> Library:
    """Serving library for one ModelConfig: score / prefill / decode; with
    an MoE, the counters of ``ExpertCounts``."""
    counts = ExpertCounts() if cfg.moe is not None else None

    @jax.jit
    def _loss(params, batch):
        return M.loss_fn(cfg, params, batch)[0]

    @functools.partial(jax.jit, static_argnames=())
    def _prefill(params, batch):
        return M.prefill(cfg, params, batch, max_cache_len,
                         expert_counts=counts is not None)

    _decode = decode_jit(cfg, expert_counts=counts is not None)

    def score(params, state, args):
        return {"loss": _loss(params, args)}

    def prefill(params, state, args):
        logits, cache, *n = _prefill(params, args)
        if counts is not None:
            counts.add("prefill", n[0])
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
        logits, state["cache"], *n = _decode(params, cache, batch)
        if counts is not None:
            counts.add("decode", n[0])
        state["pos"] = int(state["pos"]) + 1
        return {"logits": logits}

    def hidden(params, state, args):
        h, _ = M.forward_hidden(cfg, params, args)
        return {"hidden": h}

    return Library({"score": score, "prefill": prefill, "decode": decode,
                    "hidden": hidden},
                   counters=counts.views() if counts is not None else None)


def make_openpose_library(net) -> dict:
    """The paper's workload: the Caffe backbone as a destination library."""
    from repro.models.openpose import op_forward

    fwd = jax.jit(lambda params, frames: op_forward(net, params, frames))

    def forward(params, state, args):
        return {"beliefs": fwd(params, args["frames"])}

    return {"forward": forward}
