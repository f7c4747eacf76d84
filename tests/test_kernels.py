"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention as dec_kernel
from repro.kernels.flash_attention import flash_attention as fa_kernel
from repro.models.ssd import ssd_chunked, ssd_sequential


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,K,Sq,Sk,D,dtype", [
    (2, 4, 2, 256, 256, 64, jnp.float32),
    (1, 8, 2, 128, 512, 128, jnp.float32),
    (2, 2, 2, 512, 512, 64, jnp.float32),
    (1, 4, 4, 256, 256, 64, jnp.bfloat16),
    (1, 4, 1, 128, 256, 128, jnp.float32),   # MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, K, Sq, Sk, D, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), dtype)
    k = jax.random.normal(ks[1], (B, K, Sk, D), dtype)
    v = jax.random.normal(ks[2], (B, K, Sk, D), dtype)
    out = fa_kernel(q, k, v, causal=causal, interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_attention_block_shapes():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 512, 64))
    k = jax.random.normal(ks[1], (1, 2, 512, 64))
    v = jax.random.normal(ks[2], (1, 2, 512, 64))
    want = ref.flash_attention(q, k, v, causal=True)
    for bq, bk in [(64, 64), (128, 256), (256, 512), (512, 128)]:
        out = fa_kernel(q, k, v, causal=True, bq=bq, bk=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,K,G,S,D,bk", [
    (2, 2, 4, 1024, 64, 256),
    (1, 4, 1, 2048, 128, 512),
    (3, 2, 8, 512, 64, 128),
    (2, 8, 2, 256, 64, 64),
])
def test_decode_attention_sweep(B, K, G, S, D, bk):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, K, G, D))
    k = jax.random.normal(ks[1], (B, K, S, D))
    v = jax.random.normal(ks[2], (B, K, S, D))
    lens = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = dec_kernel(q, k, v, lens, bk=bk, interpret=True)
    want = ref.decode_attention(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_decode_attention_full_and_single_len():
    B, K, G, S, D = 2, 2, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, K, G, D))
    k = jax.random.normal(ks[1], (B, K, S, D))
    v = jax.random.normal(ks[2], (B, K, S, D))
    for lens in (jnp.full((B,), S), jnp.ones((B,), jnp.int32)):
        out = dec_kernel(q, k, v, lens, bk=128, interpret=True)
        want = ref.decode_attention(q, k, v, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,G,N,L", [
    (2, 512, 4, 64, 2, 128, 128),
    (1, 256, 2, 128, 1, 64, 256),
    (2, 300, 4, 64, 4, 32, 128),     # ragged: S % L != 0
    (1, 128, 8, 32, 2, 64, 64),
])
def test_ssd_scan_kernel_vs_sequential(B, S, H, P, G, N, L):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y_ref, st_ref = ssd_sequential(x, dt, A, Bm, Cm)
    y_k, st_k = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=L, impl="pallas",
                              interpret=True)
    scale = float(jnp.max(jnp.abs(y_ref))) + 1.0
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               atol=2e-4 * scale)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_ref), atol=2e-4)


def test_ssd_chunked_matches_sequential_jnp():
    """The model's chunked jnp path (no kernel) vs the step-by-step oracle."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    B, S, H, P, G, N = 2, 200, 4, 32, 1, 64
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.3
    y1, s1 = ssd_sequential(x, dt, A, Bm, Cm)
    y2, s2 = ssd_chunked(x, dt, A, Bm, Cm, chunk=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)


# ---------------------------------------------------------------------------
# rmsnorm / comm_quant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [(37, 512), (256, 128), (8, 2048), (1, 256)])
def test_rmsnorm_kernel(n, d):
    x = jax.random.normal(jax.random.PRNGKey(6), (n, d))
    s = jax.random.normal(jax.random.PRNGKey(7), (d,))
    out = ops.rmsnorm(x, s, impl="pallas", interpret=True)
    want = ref.rmsnorm(x, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,d", [(64, 256), (100, 128), (3, 512)])
def test_comm_quant_kernel(n, d):
    x = jax.random.normal(jax.random.PRNGKey(8), (n, d))
    q1, s1 = ops.quantize_int8(x, impl="pallas", interpret=True)
    q2, s2 = ref.quantize_int8(x)
    assert bool(jnp.all(q1 == q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    deq = ops.dequantize_int8(q1, s1, impl="pallas", interpret=True)
    # per-row error bound: scale/2 = absmax/254
    err = jnp.max(jnp.abs(deq - x), axis=-1)
    bound = jnp.max(jnp.abs(x), axis=-1) / 127.0
    assert bool(jnp.all(err <= bound))
