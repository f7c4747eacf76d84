"""Mamba2/SSD (``bench/reference/mamba2.py``) as the program runs it: the
``ModelConfig`` a configuration file maps onto, and the operations and bytes
a served call needs (see ``bench/flops.py``)."""
from __future__ import annotations

from bench.flops import itemsize


def program_config(c: dict):
    from repro.configs import get_arch, with_overrides
    from repro.configs.base import SSMConfig
    dtype = c["torch_dtype"]
    heads = c["expand"] * c["d_model"] // c["headdim"]
    kw = dict(d_model=c["d_model"], num_layers=c["n_layer"],
              num_heads=heads, num_kv_heads=heads,
              head_dim=c["d_model"] // heads,
              vocab_size=c["vocab_size"],
              tie_embeddings=c["tie_embeddings"],
              ssm=SSMConfig(d_state=c["d_state"], expand=c["expand"],
                            head_dim=c["headdim"],
                            conv_kernel=c["d_conv"],
                            chunk=c["chunk_size"],
                            n_groups=c["ngroups"]))
    return with_overrides(get_arch(c["arch"]), param_dtype=dtype,
                          compute_dtype=dtype, **kw)


def cost(c: dict, fn: str, n: int) -> tuple[float, float]:
    d, L, V = c["d_model"], c["n_layer"], c["vocab_size"]
    di = c["expand"] * d
    H, P, N = di // c["headdim"], c["headdim"], c["d_state"]
    gn, ck = c["ngroups"] * N, c["d_conv"]
    conv_dim = di + 2 * gn
    b = itemsize(c)
    per_layer = d * (2 * di + 2 * gn + H) + di * d
    weights = (b * (L * (per_layer + ck * conv_dim + conv_dim) + V * d)
               + 4 * (L * (3 * H + di + d) + d))
    token = 2 * per_layer + 2 * ck * conv_dim + 5 * H * P * N
    state = L * (H * P * N + (ck - 1) * conv_dim) * b
    head = 2 * d * V
    if fn == "prefill":
        return (float(n * L * token + head),
                float(weights + state + 4 * n + 4 * V))
    if fn == "decode":
        return float(L * token + head), float(weights + 2 * state + 4 + 4 * V)
    raise ValueError(fn)


def parts(c: dict, fn: str, n: int) -> dict:
    """No program scope of this architecture is counted yet."""
    return {}
