"""Logical-axis sharding rules -> NamedShardings.

Every parameter carries logical axis names (ParamSpec.axes); these rules map
them onto the production mesh.  AVEC's link-hierarchy rule (DESIGN.md §2)
decides the mapping: tensor-parallel axes ("model") stay on ICI inside a pod,
batch crosses ("pod","data"), and nothing chatty maps onto DCN.

Profiles:
  dp_tp   — baseline: weights sharded over "model" only (replicated over
            data); batch over ("pod","data").
  fsdp_tp — beyond-paper: the d_model ("embed") weight axis additionally
            shards over "data" (ZeRO-3 style), collapsing per-chip param +
            optimizer memory by the data-axis size.

Divisibility policy: a dimension shards over an axis group only when the
group size divides it exactly (jit in_shardings reject uneven shards) —
minicpm's 36 heads, arctic's 56 heads and mamba2's 24 SSD heads therefore
replicate over "model" in the baseline.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.params import ParamSpec, is_spec

# logical axis -> mesh axis group, per profile
_RULES_DP_TP: dict = {
    "vocab": ("model",), "heads": ("model",), "kv_heads": ("model",),
    "mlp": ("model",), "experts": ("model",), "conv_in": ("model",),
    "ssm_heads": ("model",), "expert_mlp": None, "embed": None,
    "head_dim": None, "layers": None, None: None,
}
_RULES_FSDP_TP = dict(_RULES_DP_TP, embed=("data",))
# "_hd" variants additionally shard head_dim over "model" — effective only
# when the head axis itself could not shard (uneven heads / few KV heads):
# the seen-axis filter in spec_to_pspec keeps one "model" use per tensor.
_RULES_DP_TP_HD = dict(_RULES_DP_TP, head_dim=("model",))
_RULES_FSDP_TP_HD = dict(_RULES_FSDP_TP, head_dim=("model",))

PROFILES = {"dp_tp": _RULES_DP_TP, "fsdp_tp": _RULES_FSDP_TP,
            "dp_tp_hd": _RULES_DP_TP_HD, "fsdp_tp_hd": _RULES_FSDP_TP_HD}


def data_axes(mesh: Mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _axis_size(mesh: Mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _map_dim(mesh: Mesh, dim: int, logical, rules) -> Optional[object]:
    axes = rules.get(logical, None)
    if not axes:
        return None
    # jit in_shardings require exact divisibility (GSPMD pads only
    # intermediates) — replicate otherwise (e.g. minicpm 36H, arctic 56H,
    # mamba2 24 SSD heads over model=16).
    if dim % _axis_size(mesh, axes) != 0:
        return None
    return axes if len(axes) > 1 else axes[0]


def spec_to_pspec(mesh: Mesh, spec: ParamSpec, profile: str) -> P:
    rules = PROFILES[profile]
    entries = [_map_dim(mesh, d, a, rules) for d, a in zip(spec.shape, spec.axes)]
    # a mesh axis may appear at most once per pspec: keep first occurrence
    seen: set = set()
    clean = []
    for e in entries:
        names = (e if isinstance(e, tuple) else (e,)) if e else ()
        if any(n in seen for n in names):
            clean.append(None)
            continue
        seen.update(names)
        clean.append(e)
    return P(*clean)


def specs_to_shardings(mesh: Mesh, spec_tree, profile: str = "dp_tp"):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, spec_to_pspec(mesh, s, profile)),
        spec_tree, is_leaf=is_spec)


# ---------------------------------------------------------------------------
# Activations / inputs / caches
# ---------------------------------------------------------------------------

def batch_pspec(mesh: Mesh, batch_size: int, rank: int,
                seq_axis: Optional[int] = None, seq_len: int = 0) -> P:
    """Batch-leading activation sharding: batch over ("pod","data") when it
    divides; for batch=1 long-context cells, optionally shard the sequence
    dim over "data" instead."""
    da = data_axes(mesh)
    total = _axis_size(mesh, da)
    entries: list = [None] * rank
    if batch_size >= total and batch_size % total == 0:
        entries[0] = da if len(da) > 1 else da[0]
    elif seq_axis is not None and seq_len >= total and seq_len % total == 0:
        entries[seq_axis] = da if len(da) > 1 else da[0]
    return P(*entries)


def input_shardings(mesh: Mesh, cfg, abstract_batch: dict) -> dict:
    out = {}
    for key, leaf in abstract_batch.items():
        if leaf.ndim == 0:
            out[key] = NamedSharding(mesh, P())
        else:
            out[key] = NamedSharding(
                mesh, batch_pspec(mesh, leaf.shape[0], leaf.ndim))
    return out


def cache_shardings(mesh: Mesh, cfg, abstract_cache, batch_size: int,
                    profile: str = "dp_tp"):
    """Decode-cache shardings by leaf name.  Leaf layouts (lm stack):
      k/v/cross_k/cross_v: (nb, B, S, K, hd)     [encdec: (L, B, S, K, hd)]
      conv:                (nb, B, ck-1, D)
      ssm:                 (nb, B, H, P, N)
    Batch shards over ("pod","data") when divisible; for batch=1 (long_500k)
    the KV sequence dim shards over "data" instead (sequence parallelism).
    Head-like dims shard over "model" when they fit."""
    da = data_axes(mesh)
    d_total = _axis_size(mesh, da)
    m_total = mesh.shape["model"]
    da_entry = da if len(da) > 1 else da[0]
    batch_ok = batch_size >= d_total and batch_size % d_total == 0

    def leaf_sharding(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        rank = leaf.ndim
        entries: list = [None] * rank
        if batch_ok:
            entries[1] = da_entry
        if name in ("k", "v", "cross_k", "cross_v"):
            if not batch_ok and leaf.shape[2] % d_total == 0:
                entries[2] = da_entry            # sequence-sharded KV
            if leaf.shape[3] % m_total == 0:
                entries[3] = "model"
            elif profile.endswith("_hd") and leaf.shape[4] % m_total == 0:
                entries[4] = "model"             # KV head_dim sharding
        elif name == "conv":
            if leaf.shape[3] % m_total == 0:
                entries[3] = "model"
        elif name == "ssm":
            if leaf.shape[2] % m_total == 0:
                entries[2] = "model"
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(leaf_sharding, abstract_cache)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())
