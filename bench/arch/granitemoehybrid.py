"""Granite 4.0 hybrid (``bench/reference/granitemoehybrid.py``) as the
program runs it: the ``ModelConfig`` a configuration file maps onto, and
the operations and bytes a served call needs (see ``bench/flops.py``).

Routing depends on the data, so a whole call's cost counts the routed
experts at their expectation (a token picks ``k * held / E`` held experts;
n tokens hit ``held * (1 - (1 - k/E)^n)`` of them a layer).  The program
scope ``avec.moe`` is counted exactly instead: ``parts`` gives its fixed
part (router and shared expert) and ``routed`` the cost of each row routed
and each expert hit, which the destination counts
(``avec_moe_routed_rows_total``, ``avec_moe_experts_hit_total``)."""
from __future__ import annotations

from bench.flops import itemsize


def _period(c: dict) -> tuple[int, int]:
    from bench import harness
    return harness.reference_module(c["reference"]).period(c)


def program_config(c: dict):
    from repro.configs import get_arch, with_overrides
    from repro.configs.base import MoEConfig, SSMConfig
    dtype = c["torch_dtype"]
    d = c["hidden_size"]
    every, at = _period(c)
    ep = c["expert_parallel"]
    if c["mamba_n_heads"] * c["mamba_d_head"] != c["mamba_expand"] * d:
        raise ValueError("mamba_n_heads * mamba_d_head must be "
                         "mamba_expand * hidden_size")
    kw = dict(d_model=d, num_layers=c["num_hidden_layers"],
              num_heads=c["num_attention_heads"],
              num_kv_heads=c["num_key_value_heads"],
              head_dim=d // c["num_attention_heads"],
              d_ff=c["shared_intermediate_size"],
              vocab_size=c["vocab_size"],
              tie_embeddings=c["tie_word_embeddings"],
              rope=c["position_embedding_type"] != "nope",
              rope_theta=float(c["rope_theta"]),
              norm_eps=c["rms_norm_eps"],
              embedding_multiplier=float(c["embedding_multiplier"]),
              residual_multiplier=float(c["residual_multiplier"]),
              logits_scaling=float(c["logits_scaling"]),
              attn_scale=float(c["attention_multiplier"]),
              attn_every=every, attn_offset=at,
              moe=MoEConfig(num_experts=ep["router_experts"],
                            top_k=c["num_experts_per_tok"],
                            d_ff=c["intermediate_size"],
                            dense_residual=True,
                            held=c["num_local_experts"],
                            first_held=ep["first_held"]),
              ssm=SSMConfig(d_state=c["mamba_d_state"],
                            expand=c["mamba_expand"],
                            head_dim=c["mamba_d_head"],
                            conv_kernel=c["mamba_d_conv"],
                            chunk=c["mamba_chunk_size"],
                            n_groups=c["mamba_n_groups"]))
    return with_overrides(get_arch(c["arch"]), param_dtype=dtype,
                          compute_dtype=dtype, **kw)


def _sizes(c: dict) -> dict:
    d = c["hidden_size"]
    Hq, K = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // Hq
    di = c["mamba_expand"] * d
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    n_attn = sum(t == "attention" for t in c["layer_types"])
    return dict(
        d=d, L=c["num_hidden_layers"], n_attn=n_attn,
        n_mamba=c["num_hidden_layers"] - n_attn, Hq=Hq, K=K, hd=hd, di=di,
        nh=c["mamba_n_heads"], P=c["mamba_d_head"], N=c["mamba_d_state"],
        gn=gn, ck=c["mamba_d_conv"], conv_dim=di + 2 * gn,
        E=c["expert_parallel"]["router_experts"],
        held=c["num_local_experts"], k=c["num_experts_per_tok"],
        f=c["intermediate_size"], fs=c["shared_intermediate_size"],
        V=c["vocab_size"], b=itemsize(c))


def routed(c: dict) -> tuple[float, float]:
    """(operations one row routed to an expert needs, bytes one expert hit
    is read): a SwiGLU of width ``intermediate_size``."""
    s = _sizes(c)
    return float(6 * s["d"] * s["f"]), float(3 * s["d"] * s["f"] * s["b"])


def _fixed_moe(s: dict, tokens: int) -> tuple[float, float]:
    """``avec.moe`` less its routed experts, all layers: the float32 router
    (operations and weights) and the shared expert."""
    ops = tokens * s["L"] * (2 * s["d"] * s["E"] + 6 * s["d"] * s["fs"])
    nbytes = s["L"] * (4 * s["d"] * s["E"] + s["b"] * 3 * s["d"] * s["fs"])
    return float(ops), float(nbytes)


def _sdpa(s: dict, fn: str, n: int) -> tuple[float, float]:
    """``avec.sdpa`` of the attention layers, as ``bench/arch/dense_gqa``
    counts it: causal (query, key) pairs; K/V written (prefill) or read up
    to and including position n (decode)."""
    per_pair = s["n_attn"] * 4 * s["Hq"] * s["hd"]
    kv_token = s["n_attn"] * 2 * s["K"] * s["hd"] * s["b"]
    if fn == "prefill":
        return float(per_pair * n * (n + 1) / 2), float(n * kv_token)
    return float(per_pair * (n + 1)), float((n + 1) * kv_token)


def cost(c: dict, fn: str, n: int) -> tuple[float, float]:
    if fn not in ("prefill", "decode"):
        raise ValueError(fn)
    s = _sizes(c)
    d, b = s["d"], s["b"]
    tokens = n if fn == "prefill" else 1
    mamba_w = d * (2 * s["di"] + 2 * s["gn"] + s["nh"]) + s["di"] * d
    attn_w = 2 * d * s["Hq"] * s["hd"] + 2 * d * s["K"] * s["hd"]
    row_ops, expert_bytes = routed(c)
    share = s["k"] * s["held"] / s["E"]
    hit = s["held"] * (1.0 - (1.0 - s["k"] / s["E"]) ** tokens)
    moe_ops, moe_bytes = _fixed_moe(s, tokens)
    sdpa_ops, sdpa_bytes = _sdpa(s, fn, n)
    token_ops = (s["n_mamba"] * (2 * mamba_w + 2 * s["ck"] * s["conv_dim"]
                                 + 5 * s["nh"] * s["P"] * s["N"])
                 + s["n_attn"] * 2 * attn_w + s["L"] * share * row_ops)
    weights = (b * (s["n_mamba"] * (mamba_w + s["ck"] * s["conv_dim"]
                                    + s["conv_dim"])
                    + s["n_attn"] * attn_w + s["V"] * d)
               + 4 * (s["n_mamba"] * (3 * s["nh"] + s["di"])
                      + (2 * s["L"] + 1) * d)
               + moe_bytes + s["L"] * hit * expert_bytes)
    state = s["n_mamba"] * (s["nh"] * s["P"] * s["N"]
                            + (s["ck"] - 1) * s["conv_dim"]) * b
    flops = tokens * token_ops + moe_ops + sdpa_ops + 2 * d * s["V"]
    if fn == "prefill":
        nbytes = weights + state + sdpa_bytes + 4 * n + 4 * s["V"]
    else:
        nbytes = weights + 2 * state + sdpa_bytes + 4 + 4 * s["V"]
    return float(flops), float(nbytes)


def parts(c: dict, fn: str, n: int) -> dict:
    """``avec.moe`` without its routed experts (whose rows and hits the
    destination counts; see ``routed``), and ``avec.sdpa``."""
    if fn not in ("prefill", "decode"):
        raise ValueError(fn)
    s = _sizes(c)
    return {"avec.moe": _fixed_moe(s, n if fn == "prefill" else 1),
            "avec.sdpa": _sdpa(s, fn, n)}
