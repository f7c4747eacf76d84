"""Flash-decode attention as a Pallas TPU kernel.

One new query token per sequence against a long KV cache.  Grid
(B, K, ns) with the KV-block index innermost; each program cell owns one KV
head and its G grouped query heads (the whole (G, D) query tile — G is the
GQA ratio, so the MXU operates on (G,D)x(D,bk) tiles).  The valid cache
length per batch row is a scalar-prefetch operand (``kv_len``), used both to
skip fully-invalid KV blocks (``pl.when``) and to mask the tail block.

This is the TPU adaptation of split-K flash-decoding: the sequential grid
walk over KV blocks with VMEM-resident (m, l, acc) replaces the GPU's
cross-SM split + reduction pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, bk: int, ns: int):
    b = pl.program_id(0)
    s = pl.program_id(2)
    kv_len = len_ref[b]

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(s * bk < kv_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        d = q.shape[-1]
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sc = sc * (d ** -0.5)                            # (G, bk)
        cols = s * bk + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(cols < kv_len, sc, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(sc - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(s == ns - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, kv_len, *, bk: int = 512, interpret: bool = False):
    """q: (B,K,G,D); k,v: (B,K,S,D); kv_len: (B,) int32.  Returns (B,K,G,D)."""
    B, K, G, D = q.shape
    S = k.shape[2]
    bk = min(bk, S)
    assert S % bk == 0, (S, bk)
    ns = S // bk

    kernel = functools.partial(_decode_kernel, bk=bk, ns=ns)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, ns),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, s, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, s, lens: (b, h, s, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, s, lens: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, s, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), q, k, v)
