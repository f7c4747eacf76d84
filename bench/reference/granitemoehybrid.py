"""Plain float32 reference of a Granite 4.0 hybrid language model
(``granitemoehybrid``: Mamba2 and attention layers, each followed by a
mixture of experts and a shared expert).

Written from the published architecture (the Hugging Face
``GraniteMoeHybrid`` model and its configuration), not from the program:

* embedding: h = E[token] * embedding_multiplier;
* every layer, with RMSNorm (eps ``rms_norm_eps``) before each sublayer:
  h += residual_multiplier * mixer(norm(h)), then
  h += residual_multiplier * (routed(x) + shared(x)) with x = norm(h);
* the mixer is Mamba2 where ``layer_types`` says "mamba": projections to
  the gate z, the input x, B, C and the step dt; a causal depthwise
  convolution with bias and SiLU over x, B and C; dt = softplus(dt +
  dt_bias); the selective state-space recurrence run step by step,
      state_t = exp(dt_t * A) * state_{t-1} + dt_t * x_t (outer) B_t,
      y_t = state_t . C_t + D * x_t,
  with A = -exp(A_log); the gated RMSNorm rmsnorm(y * silu(z)) * scale over
  the whole inner width (eps ``rms_norm_eps``); the output projection;
* and grouped-query attention where it says "attention": no position
  embedding ("nope"), scores scaled by ``attention_multiplier``, causal;
* routing in float32: the router's logits over all experts, the top
  ``num_experts_per_tok`` of them, gates = softmax over those; routed(x) =
  the sum over the chosen experts held here of gate * SwiGLU_e(x); the
  shared expert is a SwiGLU of width ``shared_intermediate_size``;
* the head: final RMSNorm, logits = h . E^T / logits_scaling (tied).

Departures from the published model, each a cut the configuration file
states: the depth is ``num_hidden_layers`` with the ``layer_types`` it
lists, and each layer holds ``num_local_experts`` of the router's
``expert_parallel.router_experts`` experts, ids from
``expert_parallel.first_held``: what the other experts would add is left
out, as on one chip of an expert-parallel deployment.  The router keeps
all its outputs.

Every product runs in float32 at ``Precision.HIGHEST``; each held expert
runs over every token (its gate zero where it was not chosen).
``mode="int8"`` is the comparison's control: int8 weights per output
channel and int8 activations per token before every linear layer.
``layout(c)`` gives the parameter tree the served program takes (stacked
over repeats of the layer period, vocabulary rows padded as served).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def period(c: dict) -> tuple[int, int]:
    """(layers in one period, index of its attention layer), read from
    ``layer_types``, which must repeat that period."""
    types = c["layer_types"]
    attn = [i for i, t in enumerate(types) if t == "attention"]
    every = attn[1] - attn[0] if len(attn) > 1 else len(types)
    if (not attn or len(types) % every
            or any((t == "attention") != (i % every == attn[0])
                   for i, t in enumerate(types))):
        raise ValueError(f"layer_types is not one repeating period with one "
                         f"attention layer: {types}")
    return every, attn[0]


def _dims(c: dict) -> dict:
    d = c["hidden_size"]
    ep = c["expert_parallel"]
    return dict(
        d=d, L=c["num_hidden_layers"], V=c["vocab_size"],
        Hq=c["num_attention_heads"], K=c["num_key_value_heads"],
        hd=d // c["num_attention_heads"],
        di=c["mamba_expand"] * d, nh=c["mamba_n_heads"], P=c["mamba_d_head"],
        G=c["mamba_n_groups"], N=c["mamba_d_state"], ck=c["mamba_d_conv"],
        E=ep["router_experts"], first=ep["first_held"],
        held=c["num_local_experts"], k=c["num_experts_per_tok"],
        f=c["intermediate_size"], fs=c["shared_intermediate_size"])


def layout(c: dict) -> dict:
    """Leaf: (shape, dtype, init, scale)."""
    k = _dims(c)
    d, di, nh, ck = k["d"], k["di"], k["nh"], k["ck"]
    gn = k["G"] * k["N"]
    Hq, K, hd = k["Hq"], k["K"], k["hd"]
    f, fs, held, E = k["f"], k["fs"], k["held"], k["E"]
    every, at = period(c)
    nb = k["L"] // every
    Vp = c["serving"]["vocab_padded_to"]
    w, f32 = c["torch_dtype"], "float32"

    def one(shape, dtype, init, scale):
        return ((nb,) + shape, dtype, init, scale)

    ffn = {
        "ffn_norm": {"scale": one((d,), f32, "one_normal", 0.1)},
        "moe": {
            "router": one((d, E), f32, "normal", d ** -0.5),
            "w_gate": one((held, d, f), w, "normal", d ** -0.5),
            "w_up": one((held, d, f), w, "normal", d ** -0.5),
            "w_down": one((held, f, d), w, "normal", f ** -0.5),
            "dense": {"w_gate": one((d, fs), w, "normal", d ** -0.5),
                      "w_up": one((d, fs), w, "normal", d ** -0.5),
                      "w_down": one((fs, d), w, "normal", fs ** -0.5)},
        },
    }
    mamba = {
        "mixer_norm": {"scale": one((d,), f32, "one_normal", 0.1)},
        "mamba": {
            "wz": one((d, di), w, "normal", d ** -0.5),
            "wx": one((d, di), w, "normal", d ** -0.5),
            "wB": one((d, gn), w, "normal", d ** -0.5),
            "wC": one((d, gn), w, "normal", d ** -0.5),
            "wdt": one((d, nh), w, "normal", d ** -0.5),
            "dt_bias": one((nh,), f32, "mamba_dt_bias", 0.0),
            "A_log": one((nh,), f32, "mamba_a_log", 0.0),
            "D": one((nh,), f32, "one_normal", 0.1),
            "conv_x": one((ck, di), w, "normal", ck ** -0.5),
            "conv_B": one((ck, gn), w, "normal", ck ** -0.5),
            "conv_C": one((ck, gn), w, "normal", ck ** -0.5),
            "conv_bx": one((di,), w, "normal", 0.02),
            "conv_bB": one((gn,), w, "normal", 0.02),
            "conv_bC": one((gn,), w, "normal", 0.02),
            "norm_scale": one((di,), f32, "one_normal", 0.1),
            "wo": one((di, d), w, "normal", di ** -0.5),
        },
        **ffn,
    }
    attn = {
        "mixer_norm": {"scale": one((d,), f32, "one_normal", 0.1)},
        "attn": {"wq": one((d, Hq, hd), w, "normal", d ** -0.5),
                 "wk": one((d, K, hd), w, "normal", d ** -0.5),
                 "wv": one((d, K, hd), w, "normal", d ** -0.5),
                 "wo": one((Hq, hd, d), w, "normal", (Hq * hd) ** -0.5)},
        **ffn,
    }
    return {
        "embed": {"tok": ((Vp, d), w, "normal", 0.02)},
        "blocks": {"layers": [attn if j == at else mamba
                              for j in range(every)]},
        "final_norm": {"scale": ((d,), f32, "one_normal", 0.1)},
    }


def _fake_int8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(a / s) * s


def linear(mode: str, x, w):
    """x (T, K) float32, w (K, N) in any float type -> (T, N) float32."""
    w = w.astype(jnp.float32)
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return jnp.dot(x, w, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def causal_conv(x, w, b):
    """x (S, C); w (ck, C): out_t = sum_k w[k] * x[t - (ck-1) + k]."""
    ck = w.shape[0]
    xp = jnp.concatenate([jnp.zeros((ck - 1, x.shape[1]), x.dtype), x], 0)
    w = w.astype(jnp.float32)
    out = sum(w[k] * xp[k:k + x.shape[0]] for k in range(ck))
    return jax.nn.silu(out + b.astype(jnp.float32))


def swiglu(mode, x, w_gate, w_up, w_down):
    return linear(mode, jax.nn.silu(linear(mode, x, w_gate))
                  * linear(mode, x, w_up), w_down)


def expert_linear(mode: str, x, w, equation: str):
    """Each expert's own linear layer, w (E, K, N): ``linear`` for every
    expert at once (int8: weights per expert and output channel)."""
    w = w.astype(jnp.float32)
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 1)
    return jnp.einsum(equation, x, w, precision=HI)


def moe(c: dict, mode: str, p: dict, x):
    """The expert sublayer on x (S, d) float32: (routed + shared output,
    the top-k expert ids (S, k))."""
    k = _dims(c)
    logits = linear(mode, x, p["router"])                  # (S, E)
    top_l, top_e = jax.lax.top_k(logits, k["k"])
    gates = jax.nn.softmax(top_l, axis=-1)
    held = k["first"] + jnp.arange(k["held"])
    gate = jnp.sum(jnp.where(top_e[None] == held[:, None, None],
                             gates[None], 0.0), -1)        # (held, S)
    a = (jax.nn.silu(expert_linear(mode, x, p["w_gate"], "sk,ekn->esn"))
         * expert_linear(mode, x, p["w_up"], "sk,ekn->esn"))
    routed = expert_linear(mode, gate[:, :, None] * a, p["w_down"],
                           "esk,ekn->sn")
    shared = swiglu(mode, x, p["dense"]["w_gate"], p["dense"]["w_up"],
                    p["dense"]["w_down"])
    return shared + routed, top_e


class Reference:
    def __init__(self, c: dict, mode: str = "f32") -> None:
        self.c, self.mode, self.k = c, mode, _dims(c)
        self.every, self.at = period(c)
        self._mamba = jax.jit(functools.partial(self._layer_fn, "mamba"))
        self._attn = jax.jit(functools.partial(self._layer_fn, "attn"))
        self._embed = jax.jit(self._embed_fn)
        self._head = jax.jit(self._head_fn)

    def _embed_fn(self, params, tokens):
        e = params["embed"]["tok"][tokens].astype(jnp.float32)
        return e * self.c["embedding_multiplier"]

    def _mixer_mamba(self, m, x):
        k, mode, eps = self.k, self.mode, self.c["rms_norm_eps"]
        S = x.shape[0]
        H, P, G, N = k["nh"], k["P"], k["G"], k["N"]
        z = linear(mode, x, m["wz"])
        xs = causal_conv(linear(mode, x, m["wx"]), m["conv_x"], m["conv_bx"])
        B = causal_conv(linear(mode, x, m["wB"]), m["conv_B"], m["conv_bB"])
        C = causal_conv(linear(mode, x, m["wC"]), m["conv_C"], m["conv_bC"])
        dt = jax.nn.softplus(linear(mode, x, m["wdt"]) + m["dt_bias"])
        A = -jnp.exp(m["A_log"])
        group = np.arange(H) // (H // G)
        xh = xs.reshape(S, H, P)
        Bh = B.reshape(S, G, N)[:, group]          # (S, H, N)
        Ch = C.reshape(S, G, N)[:, group]

        def step(state, inp):
            x_t, B_t, C_t, dt_t = inp
            state = (jnp.exp(dt_t * A)[:, None, None] * state
                     + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            return state, jnp.einsum("hpn,hn->hp", state, C_t, precision=HI)

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                            (xh, Bh, Ch, dt))
        y = (y + m["D"][None, :, None] * xh).reshape(S, H * P)
        g = rmsnorm(y * jax.nn.silu(z), m["norm_scale"], eps)
        return linear(mode, g, m["wo"])

    def _mixer_attn(self, a, x):
        c, k, mm = self.c, self.k, functools.partial(linear, self.mode)
        S, d = x.shape
        Hq, K, hd = k["Hq"], k["K"], k["hd"]
        q = mm(x, a["wq"].reshape(d, Hq * hd)).reshape(S, Hq, hd)
        kk = mm(x, a["wk"].reshape(d, K * hd)).reshape(S, K, hd)
        v = mm(x, a["wv"].reshape(d, K * hd)).reshape(S, K, hd)
        kk, v = jnp.repeat(kk, Hq // K, axis=1), jnp.repeat(v, Hq // K, axis=1)
        outs = []
        for q0 in range(0, S, Q_BLOCK):
            qb = q[q0:q0 + Q_BLOCK]
            s = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HI)
            s = s * c["attention_multiplier"]
            pos_q = q0 + jnp.arange(qb.shape[0])[:, None]
            s = jnp.where(jnp.arange(S)[None, :] <= pos_q, s, -jnp.inf)
            outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                                   precision=HI))
        o = jnp.concatenate(outs, 0).reshape(S, Hq * hd)
        return mm(o, a["wo"].reshape(Hq * hd, d))

    def _layer_fn(self, kind, lp, b, h):
        """Layer of kind ``kind`` with the ``b``-th repeat of its stacked
        parameters ``lp``: (h, the top-k expert ids of every position)."""
        c = self.c
        p = jax.tree_util.tree_map(lambda a: a[b], lp)
        eps, rm = c["rms_norm_eps"], c["residual_multiplier"]
        x = rmsnorm(h, p["mixer_norm"]["scale"], eps)
        mix = (self._mixer_mamba(p["mamba"], x) if kind == "mamba"
               else self._mixer_attn(p["attn"], x))
        h = h + rm * mix
        y, top_e = moe(c, self.mode, p["moe"],
                       rmsnorm(h, p["ffn_norm"]["scale"], eps))
        return h + rm * y, top_e

    def _head_fn(self, params, h, rows):
        """Logits over the real vocabulary at sequence positions ``rows``."""
        c = self.c
        x = rmsnorm(h[rows], params["final_norm"]["scale"], c["rms_norm_eps"])
        emb = params["embed"]["tok"][:c["vocab_size"]]
        return linear(self.mode, x, emb.T) / c["logits_scaling"]

    def _forward(self, params, tokens):
        h = self._embed(params, jnp.asarray(tokens))
        routes = []
        for i in range(self.k["L"]):
            j, b = i % self.every, i // self.every
            layer = self._attn if j == self.at else self._mamba
            h, top_e = layer(params["blocks"]["layers"][j], jnp.int32(b), h)
            routes.append(top_e)
        return h, routes

    def logits(self, params, tokens: np.ndarray, rows: np.ndarray):
        """tokens (S,) int32 (padded at the end: the model is causal);
        rows (R,) positions whose next-token logits to return -> (R, V)."""
        h, _ = self._forward(params, tokens)
        return self._head(params, h, jnp.asarray(rows))

    def routes(self, params, tokens: np.ndarray) -> list:
        """Per layer, the top-k expert ids (router-wide) of every
        position: (S, k) int32."""
        return [np.asarray(r) for r in self._forward(params, tokens)[1]]
