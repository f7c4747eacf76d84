"""Prefill + decode must reproduce the train-path logits exactly (the cache
correctness property), for every architecture family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch, reduced
from repro.models import model as M


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    cfg = reduced(get_arch(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S, Smax, P = 2, 12, 16, 8
    tok = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tok, "targets": tok}
    if cfg.family == "vlm":
        batch["vision"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.num_vision_tokens, cfg.d_model))
    if cfg.family == "encdec":
        batch["frames"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(3), (B, cfg.num_audio_frames, cfg.d_model))

    h, _ = M.forward_hidden(cfg, params, batch)
    full = M.logits_from_hidden(cfg, params, h)

    pb = dict(batch)
    pb["tokens"] = tok[:, :P]
    lg, cache = M.prefill(cfg, params, pb, Smax)
    errs = [float(np.max(np.abs(lg[:, 0] - full[:, P - 1])))]
    for t in range(P, S):
        db = {"tokens": tok[:, t:t + 1], "pos": jnp.asarray(t, jnp.int32)}
        if cfg.family == "vlm":
            db["vision"] = batch["vision"]
        lg, cache = M.decode_step(cfg, params, cache, db)
        errs.append(float(np.max(np.abs(lg[:, 0] - full[:, t]))))
    assert max(errs) < 2e-3, (arch, errs)


def test_per_row_positions_match_scalar():
    """Continuous-batching per-row pos == scalar pos when aligned."""
    cfg = reduced(get_arch("granite-3-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, cfg.vocab_size)
    _, cache = M.prefill(cfg, params, {"tokens": tok}, 16)
    nxt = tok[:, :1]
    l1, _ = M.decode_step(cfg, params, cache,
                          {"tokens": nxt, "pos": jnp.asarray(6)})
    l2, _ = M.decode_step(cfg, params, cache,
                          {"tokens": nxt, "pos": jnp.full((2,), 6, jnp.int32)})
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)
