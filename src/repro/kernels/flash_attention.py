"""Flash attention (forward) as a Pallas TPU kernel.

Grid (B, H, nq, nk) with the KV-block index innermost; online-softmax
accumulators (m, l, acc) live in VMEM scratch and persist across the nk
steps of one (b, h, iq) cell.  Causal blocks above the diagonal are skipped
with ``pl.when`` (no MXU work issued).  GQA is handled by indexing the KV
head as h // (H // K) in the BlockSpec index maps.

Block shapes: q (1,1,bq,D), k/v (1,1,bk,D) — D ∈ {64,128} is MXU minor-dim
aligned; bq/bk default 128/256 keep the VMEM working set
(bq*D + 2*bk*D + bq*bk floats ≈ <1 MiB at defaults) far under the ~16 MiB/core
budget while saturating the 128x128 MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal: bool, bq: int, bk: int, nk: int, sq: int, sk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: query row i attends to key j <= i + (sk - sq)
    offset = sk - sq
    first_masked_k = (iq * bq + bq - 1 + offset) // bk  # last kv block touched

    @pl.when(jnp.logical_not(causal) | (ik <= first_masked_k))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        d = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (d ** -0.5)                             # (bq, bk)
        if causal:
            rows = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(cols <= rows + offset, s, NEG_INF)
        m_prev = m_ref[...]                             # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 256, interpret: bool = False):
    """q: (B,H,Sq,D); k,v: (B,K,Sk,D).  Returns (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    K, Sk = k.shape[1], k.shape[2]
    G = H // K
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk

    kernel = functools.partial(_flash_kernel, causal=causal, bq=bq, bk=bk,
                               nk=nk, sq=Sq, sk=Sk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m
            pltpu.VMEM((bq,), jnp.float32),       # l
            pltpu.VMEM((bq, D), jnp.float32),     # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
