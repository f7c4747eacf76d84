"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

Grid (B, H, nc) with the chunk index innermost; the (P, N) SSM state lives in
VMEM scratch and carries across chunks (the inter-chunk linear recurrence),
while each chunk's intra term is computed with three MXU matmuls:
C@B^T (L,L), scores@x (L,P), and x^T@(w*B) (P,N).  This is the TPU-native
schedule of the SSD algorithm: the GPU implementation's cross-block
state-passing via global memory becomes a sequential grid dimension with a
VMEM-resident carry.

Layouts (pre-arranged by the ``ops.ssd_scan`` wrapper):
  x  (B, H, nc, L, P)    dt/dA (B, H, nc, 1, L)    Bm/Cm (B, G, nc, L, N)
dt/dA carry a unit axis so each chunk's (1, L) block spans the array's
last two dims, as Mosaic requires of blocks not tiled to (8, 128).
Outputs: y (B, H, nc, L, P) and final state (B, H, P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, st_out_ref,
                state_ref, *, nc: int, L: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)            # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)          # (1, L)
    da = da_ref[0, 0, 0].astype(jnp.float32)          # (1, L)
    Bm = b_ref[0, 0, 0].astype(jnp.float32)           # (L, N)
    Cm = c_ref[0, 0, 0].astype(jnp.float32)           # (L, N)
    state = state_ref[...]                            # (P, N)

    # inclusive prefix sums of dA as triangular matmuls (Mosaic has no
    # cumsum), in both the row and the column orientation
    rows = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tril = (cols <= rows).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(da, tril, (((1,), (1,)), ((), ())),
                              precision=hi,
                              preferred_element_type=jnp.float32)     # (1, L)
    cum_col = jax.lax.dot_general(tril, da, (((1,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (L, 1)

    # ---- intra-chunk (quadratic attention-like term) ----------------------
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)      # (L, L)
    decay = jnp.where(cols <= rows, jnp.exp(cum_col - cum), 0.0)
    scores = cb * decay * dt
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (L, P)

    # ---- inter-chunk contribution from the carried state -------------------
    y_in = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)    # (L, P)
    y = y + y_in * jnp.exp(cum_col)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    # ---- state update -------------------------------------------------------
    w = jnp.exp(cum_col[-1:] - cum_col) * dt.T        # (L, 1)
    upd = jax.lax.dot_general(x, Bm * w, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)     # (P, N)
    state_ref[...] = state * jnp.exp(cum_col[-1:]) + upd

    @pl.when(c == nc - 1)
    def _emit_state():
        st_out_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(x, dt, dA, Bm, Cm, *, chunk: int, interpret: bool = False):
    """Kernel-layout entry (see module docstring).  Shapes:
    x (B,H,nc,L,P), dt/dA (B,H,nc,1,L), Bm/Cm (B,G,nc,L,N)."""
    B, H, nc, L, P = x.shape
    G, N = Bm.shape[1], Bm.shape[-1]
    rep = H // G
    kernel = functools.partial(_ssd_kernel, nc=nc, L=L)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, L), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N), lambda b, h, c: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, N), lambda b, h, c: (b, h // rep, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, dA, Bm, Cm)
