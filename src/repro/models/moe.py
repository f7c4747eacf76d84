"""Mixture-of-Experts layer: top-k routing with capacity-bounded sort dispatch.

Dispatch strategy (TPU-native adaptation of GShard/Switch): tokens are routed
top-k, assignments are stably sorted by expert id, each assignment gets a
position-in-expert via a cumulative-count subtraction, assignments beyond
per-expert ``capacity`` are dropped, and rows are scattered into an
(..., E, C, d) buffer that feeds batched per-expert GEMMs — no (T, E, C)
one-hot dispatch tensor is ever materialized.

All B*S tokens form one dispatch group.  Under GSPMD with tokens sharded
over `data` and experts over `model`, the scatter into that global buffer
resolves to an all-reduce of the whole (E, C, d) buffer across `data`
(15 TB/device measured in the moonshot x train_4k dry-run).

Expert weights carry the "experts" logical axis and shard over the model mesh
axis (expert parallelism).

A layer may hold only some of the router's experts (``MoEConfig.held``,
``first_held``): as one chip of an expert-parallel deployment it routes over
all of them and computes its own experts' part of the result, and what the
other experts would add is left out.

Serving (prefill and decode) uses ``moe_serve``: no capacity and no dropped
token, the work bounded by the rows routed to the held experts (a grouped
matmul over rows sorted by expert).  ``apply_moe`` is the training path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.mlp import mlp_specs, apply_mlp
from repro.models.params import ParamSpec


def moe_specs(cfg) -> dict:
    m = cfg.moe
    d, E, H, f = cfg.d_model, m.num_experts, m.n_held, m.d_ff
    specs = {
        "router": ParamSpec((d, E), ("embed", None), "normal", d ** -0.5,
                            dtype=jnp.float32),
        "w_gate": ParamSpec((H, d, f), ("experts", "embed", "expert_mlp"),
                            "normal", d ** -0.5),
        "w_up": ParamSpec((H, d, f), ("experts", "embed", "expert_mlp"),
                          "normal", d ** -0.5),
        "w_down": ParamSpec((H, f, d), ("experts", "expert_mlp", "embed"),
                            "normal", f ** -0.5),
    }
    if m.dense_residual:
        specs["dense"] = mlp_specs(cfg)
    return specs


def _capacity(cfg, n_tokens: int) -> int:
    m = cfg.moe
    c = math.ceil(n_tokens * m.top_k / m.num_experts * m.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor 8


def apply_moe(cfg, p, x):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).  Training path
    over a layer that holds every expert: per-expert capacity, assignments
    past it dropped."""
    m = cfg.moe
    B, S, d = x.shape
    E, k = m.num_experts, m.top_k
    T = B * S
    xt = x.reshape(T, d)

    # --- routing (fp32) ----------------------------------------------------
    logits = xt.astype(jnp.float32) @ p["router"]              # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                     # (T,k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch), computed over ALL tokens
    me = jnp.mean(probs, axis=0)                               # (E,)
    assign = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    fe = assign / (T * k)
    aux = m.router_aux_weight * E * jnp.sum(fe * me)

    # --- capacity-bounded sort dispatch ------------------------------------
    C = _capacity(cfg, T)
    flat_e = top_e.reshape(T * k)
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])        # (E,)
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < C
    dest = sorted_e * C + jnp.where(keep, pos_in_e, 0)         # (TK,)
    src_tok = sort_idx // k

    # dropped entries are zeroed and .add'ed at slot 0 of their expert, so
    # they cannot clobber a kept row (a .set with colliding indices would).
    # Constraining rows/buf to a sharding before the scatter was tried under
    # GSPMD and moved 3.4x more collective traffic (moonshot dry-run).
    rows = xt[src_tok] * keep[:, None].astype(xt.dtype)        # (TK,d)
    buf = jnp.zeros((E * C, d), xt.dtype).at[dest].add(rows)
    buf = buf.reshape(E, C, d)

    # --- per-expert SwiGLU (batched GEMMs over experts) --------------------
    dt = buf.dtype
    g_ = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(dt))
    u_ = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    h = jax.nn.silu(g_) * u_
    out_flat = jnp.einsum("ecf,efd->ecd", h,
                          p["w_down"].astype(dt)).reshape(E * C, d)

    # --- combine -------------------------------------------------------------
    w = (top_p.reshape(T * k)[sort_idx] * keep).astype(xt.dtype)
    y = jnp.zeros((T, d), xt.dtype).at[src_tok].add(out_flat[dest] * w[:, None])

    if m.dense_residual:
        y = y + apply_mlp(cfg, p["dense"], xt)
    return y.reshape(B, S, d), aux


def _grouped_matmul(x, w, sizes, out_dtype):
    """x (R, k) rows sorted by group, w (G, k, n), sizes (G,) -> (R, n):
    row r times the matrix of its group.  Rows past ``sizes.sum()`` are
    left undefined.  On a TPU the Pallas grouped matmul (megablox), which
    skips empty groups and keeps the enclosing scope's name on its
    operation (XLA's expansion of ``ragged_dot`` there drops it, so no
    scope could time the expert matmuls); elsewhere ``ragged_dot``."""
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(x, w, sizes, preferred_element_type=out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    R, k = x.shape
    tiling = (min(R, _GMM_TILE_M), _tile(k), _tile(w.shape[2]))
    return gmm(x, w, sizes, preferred_element_type=out_dtype, tiling=tiling)


_GMM_TILE_M = 512


def _tile(n: int) -> int:
    """The grouped matmul's tile along a weight dimension of size n."""
    return n if n <= 768 else 512


def _rows(n: int) -> int:
    """Row count of the grouped matmuls for n assignments: a multiple of
    the grouped matmul's row tile (16 rows below one tile)."""
    if n <= _GMM_TILE_M:
        return -(-n // 16) * 16
    return -(-n // _GMM_TILE_M) * _GMM_TILE_M


def moe_serve(cfg, p, x):
    """Serving path.  x: (B, S, d) -> (out (B, S, d), counts int32 (2,):
    the rows routed to held experts and the held experts hit).

    Routing in float32: the top-k router logits, gated by their softmax.
    Every assignment to a held expert is computed (no capacity, no dropped
    token): assignments sorted by local expert id, the held ones first, feed
    one grouped matmul (``_grouped_matmul``) per projection over at most
    T * min(k, held) rows (rounded up to the matmul's row tile); each output
    row is scaled by its gate and added back to its token in float32."""
    m = cfg.moe
    B, S, d = x.shape
    k, H = m.top_k, m.n_held
    T = B * S
    xt = x.reshape(T, d)

    logits = xt.astype(jnp.float32) @ p["router"]              # (T,E)
    top_l, top_e = jax.lax.top_k(logits, k)                    # (T,k)
    gates = jax.nn.softmax(top_l, axis=-1).reshape(T * k)

    local = (top_e - m.first_held).reshape(T * k)
    group = jnp.where((local >= 0) & (local < H), local, H)    # H: not held
    R = _rows(T * min(k, H))   # the most assignments held experts can get
    group = jnp.pad(group, (0, max(R - T * k, 0)), constant_values=H)
    order = jnp.argsort(group, stable=True)[:R]
    sizes = jnp.zeros((H,), jnp.int32).at[group].add(1, mode="drop")
    valid = group[order] < H
    tok = jnp.minimum(order // k, T - 1)
    rows = xt[tok]                                             # (R,d)

    dt = x.dtype
    g_ = _grouped_matmul(rows, p["w_gate"].astype(dt), sizes, dt)
    u_ = _grouped_matmul(rows, p["w_up"].astype(dt), sizes, dt)
    h = jax.nn.silu(g_) * u_
    out = _grouped_matmul(h, p["w_down"].astype(dt), sizes, jnp.float32)
    w = jnp.where(valid, gates[jnp.minimum(order, T * k - 1)], 0.0)
    contrib = jnp.where(valid[:, None], out, 0.0) * w[:, None]
    y = jnp.zeros((T, d), jnp.float32).at[tok].add(contrib)
    y = y.astype(dt)

    if m.dense_residual:
        y = y + apply_mlp(cfg, p["dense"], xt)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0, dtype=jnp.int32)])
    return y.reshape(B, S, d), counts
