"""Structural equivalences: chunked attention, SWA ring cache, SSD chunking."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.policy import BF16
from repro.models import blocks, model as M
from repro.models import ssd


def test_chunked_attention_equals_unchunked(monkeypatch):
    """Query-chunked path == single-block path (pure reassociation)."""
    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    p = blocks.attn_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))

    out_full, _ = blocks.attention(p, x, cfg, BF16, positions=pos)
    monkeypatch.setattr(blocks, "ATTN_CHUNK", 16)
    out_chunk, _ = blocks.attention(p, x, cfg, BF16, positions=pos)
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(out_chunk),
                               rtol=2e-5, atol=2e-5)


def test_swa_masking_matches_truncated_context():
    """With window W, output at position t only sees the last W tokens."""
    cfg = get_config("h2o-danube-1.8b").reduced().replace(
        compute_dtype="float32", swa_window=8)
    p = blocks.attn_init(jax.random.PRNGKey(0), cfg)
    S = 24
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]
    out, _ = blocks.attention(p, x, cfg, BF16, positions=pos, window=8)
    # recompute the last position using only its window
    xw = x[:, S - 8:]
    posw = jnp.arange(S - 8, S)[None]
    outw, _ = blocks.attention(p, xw, cfg, BF16, positions=posw, window=8)
    np.testing.assert_allclose(np.asarray(out[0, -1]), np.asarray(outw[0, -1]),
                               rtol=2e-5, atol=2e-5)


def test_ring_cache_decode_matches_full_cache():
    """SWA ring cache (W=window) decodes identically to a full-length cache."""
    cfg = get_config("h2o-danube-1.8b").reduced().replace(
        compute_dtype="float32", swa_window=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    B, steps = 1, 20
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, steps), 0, cfg.vocab)

    ring = M.init_cache(cfg, B, steps, dtype=jnp.float32, ring=True)
    full = M.init_cache(cfg, B, steps, dtype=jnp.float32, ring=False)
    assert ring["k"].shape[-2] == 8 and full["k"].shape[-2] == steps
    for t in range(steps):
        lr, ring = M.decode_step(params, toks[:, t:t + 1], ring,
                                 jnp.int32(t), cfg, BF16)
        lf, full = M.decode_step(params, toks[:, t:t + 1], full,
                                 jnp.int32(t), cfg, BF16)
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lf),
                                   rtol=2e-5, atol=2e-5)


def test_ssd_chunk_invariance():
    """Chunked SSD result is independent of chunk size (and == recurrence)."""
    cfg = get_config("mamba2-780m").reduced().replace(compute_dtype="float32")
    p = ssd.ssd_init(jax.random.PRNGKey(0), cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model)) * 0.5
    outs = []
    for chunk in (4, 8, 16, 32):
        c = cfg.replace(ssm_chunk=chunk)
        outs.append(np.asarray(ssd.ssd_forward(p, u, c, BF16)))
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, rtol=2e-4, atol=2e-5)


def test_ssd_prefill_state_continues_decode():
    """prefill(return_state) -> decode continues the exact recurrence."""
    cfg = get_config("mamba2-780m").reduced().replace(compute_dtype="float32",
                                                      ssm_chunk=8)
    p = ssd.ssd_init(jax.random.PRNGKey(0), cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 17, cfg.d_model)) * 0.5
    # full forward over 17 steps
    full = np.asarray(ssd.ssd_forward(p, u[:, :16], cfg, BF16))
    out16, cache = ssd.ssd_forward(p, u[:, :16], cfg, BF16, return_state=True)
    step, _ = ssd.ssd_decode_step(p, u[:, 16:17], cache, cfg, BF16)
    # decode of step 17 must equal running the recurrence token-by-token
    cache2 = ssd.ssd_init_cache(cfg, 1)
    for t in range(17):
        last, cache2 = ssd.ssd_decode_step(p, u[:, t:t + 1], cache2, cfg, BF16)
    np.testing.assert_allclose(np.asarray(step), np.asarray(last),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("arch", ["qwen2.5-32b", "gemma2-2b", "zamba2-7b",
                                  "mamba2-780m"])
def test_forward_vs_incremental_decode(arch):
    cfg = get_config(arch).reduced().replace(compute_dtype="float32")
    if cfg.ssm_chunk:
        cfg = cfg.replace(ssm_chunk=4)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    ref = M.forward(params, {"tokens": toks}, cfg, BF16)
    cache = M.init_cache(cfg, B, S, dtype=jnp.float32)
    outs = []
    for t in range(S):
        lg, cache = M.decode_step(params, toks[:, t:t + 1], cache,
                                  jnp.int32(t), cfg, BF16)
        outs.append(lg)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(ref),
                               rtol=3e-5, atol=3e-4)
