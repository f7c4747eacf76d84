"""The dense GQA decoder (``bench/reference/dense_gqa.py``) as the program
runs it: the ``ModelConfig`` a configuration file maps onto, and the
operations and bytes a served call needs (see ``bench/flops.py``)."""
from __future__ import annotations

from bench.flops import itemsize


def program_config(c: dict):
    from repro.configs import get_arch, with_overrides
    dtype = c["torch_dtype"]
    kw = dict(d_model=c["hidden_size"], num_layers=c["num_hidden_layers"],
              num_heads=c["num_attention_heads"],
              num_kv_heads=c["num_key_value_heads"],
              head_dim=c["hidden_size"] // c["num_attention_heads"],
              d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
              rope_theta=c["rope_theta"],
              tie_embeddings=c["tie_word_embeddings"])
    return with_overrides(get_arch(c["arch"]), param_dtype=dtype,
                          compute_dtype=dtype, **kw)


def cost(c: dict, fn: str, n: int) -> tuple[float, float]:
    d, L = c["hidden_size"], c["num_hidden_layers"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd, f, V = d // H, c["intermediate_size"], c["vocab_size"]
    b = itemsize(c)
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f
    weights = b * (L * per_layer + V * d) + 4 * (2 * L + 1) * d
    kv_token = L * 2 * K * hd * b
    head = 2 * d * V
    if fn == "prefill":
        attn = L * 4 * H * hd * n * (n + 1) / 2
        flops = 2 * n * L * per_layer + attn + head
        nbytes = weights + n * kv_token + 4 * n + 4 * V
    elif fn == "decode":
        attn = L * 4 * H * hd * (n + 1)
        flops = 2 * L * per_layer + attn + head
        nbytes = weights + (n + 1) * kv_token + 4 + 4 * V
    else:
        raise ValueError(fn)
    return float(flops), float(nbytes)


def parts(c: dict, fn: str, n: int) -> dict:
    """``avec.sdpa``, the attention core of every layer (the cache's K/V
    write and read and the score and value products, not the projections):
    4·H·hd operations per (query, key at or before it) pair; prefill writes
    the prompt's K/V, decode reads K/V up to and including position n."""
    L = c["num_hidden_layers"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c["hidden_size"] // H
    kv_token = L * 2 * K * hd * itemsize(c)
    if fn == "prefill":
        return {"avec.sdpa": (float(L * 4 * H * hd * n * (n + 1) / 2),
                              float(n * kv_token))}
    if fn == "decode":
        return {"avec.sdpa": (float(L * 4 * H * hd * (n + 1)),
                              float((n + 1) * kv_token))}
    raise ValueError(fn)
