"""Decode-attention backend dispatch: packed-KV flash kernel vs jnp path.

The pallas attention backend (policy.use_pallas_attention) consumes the
MXSF-packed KV cache codes directly through kernels/mxsf_attention.py; the
jnp path dequantizes the cache and runs mx_einsum.  The two share operand
quantization (q is 1D-qdq'd along dh) but the kernel keeps softmax probs in
f32 — so parity here is tight-numeric + top-1, not bitwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.policy import MXSF_INFER, QuantPolicy
from repro.models import blocks as blk
from repro.models import model as M


def _cfg(n_kv):
    return (get_config("qwen2.5-32b").reduced()
            .replace(compute_dtype="float32", n_kv=n_kv))


def _pols():
    pol_j = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    return pol_j, pol_j.replace(backend="pallas")


def _decode_attn(cfg, pol, params, xs, W):
    """Drive blocks.attention step-by-step like decode_step does."""
    cache = {k: v[0, 0] for k, v in
             M.init_cache(cfg, xs.shape[0], W, kv_fmt="mxsf").items()}
    outs = []
    for t in range(xs.shape[1]):
        y, cache = blk.attention(params, xs[:, t:t + 1], cfg, pol,
                                 positions=None, cache=cache,
                                 cache_pos=jnp.int32(t))
        outs.append(y)
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("n_kv", [pytest.param(1, marks=pytest.mark.slow),
                                  2,
                                  pytest.param(4, marks=pytest.mark.slow)])
def test_decode_parity_gqa(n_kv):
    """jnp vs pallas decode attention across GQA group sizes (h=4)."""
    cfg = _cfg(n_kv)
    params = blk.attn_init(jax.random.PRNGKey(0), cfg)
    pol_j, pol_p = _pols()
    B, T = 2, 5
    xs = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model),
                           jnp.float32) * 0.5
    yj = _decode_attn(cfg, pol_j, params, xs, W=T)
    yp = _decode_attn(cfg, pol_p, params, xs, W=T)
    # only probs re-quantization (~2^-6 relative on an 8-bit format) differs
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yj),
                               rtol=0.1, atol=0.05)


def test_decode_parity_nonaligned_kv_len():
    """Cache width not a multiple of the kernel chunk; kv_len grows through
    non-aligned values — the ops wrapper pads and masks."""
    cfg = _cfg(2)
    params = blk.attn_init(jax.random.PRNGKey(2), cfg)
    pol_j, pol_p = _pols()
    B, T, W = 1, 7, 19
    xs = jax.random.normal(jax.random.PRNGKey(3), (B, T, cfg.d_model),
                           jnp.float32) * 0.5
    yj = _decode_attn(cfg, pol_j, params, xs, W=W)
    yp = _decode_attn(cfg, pol_p, params, xs, W=W)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yj),
                               rtol=0.1, atol=0.05)


def test_decode_step_dispatches_attention_kernel():
    """Kernel-call accounting: with use_pallas_attention the traced decode
    step contains exactly one extra pallas_call (the attention kernel inside
    the scanned layer body) vs the same policy with the attention route
    disabled."""
    cfg = _cfg(2)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol_j, pol_p = _pols()
    # same pallas linear datapath, attention route off (training-mode policy)
    pol_noattn = pol_p.replace(quantize_bwd=True)
    assert pol_p.use_pallas_attention
    assert not pol_noattn.use_pallas_attention
    assert M.decode_attn_backend(cfg, pol_p) == "pallas-packed"
    assert M.decode_attn_backend(cfg, pol_j) == "jnp"

    cache = M.init_cache(cfg, 1, 8, kv_fmt="mxsf")
    toks = jnp.zeros((1, 1), jnp.int32)

    def n_calls(pol):
        jaxpr = jax.make_jaxpr(
            lambda p, t, c: M.decode_step(p, t, c, jnp.int32(0), cfg, pol)
        )(params, toks, cache)
        return str(jaxpr).count("pallas_call")

    with_attn, without = n_calls(pol_p), n_calls(pol_noattn)
    assert with_attn == without + 1, (with_attn, without)
    assert n_calls(pol_j) == 0


def test_cache_layout_matches_row_layout():
    """The kernel's cache-layout BlockSpec index maps must agree bitwise
    with the materialized row layout from decoding.kv_cache_rows."""
    from repro.core import blocking as B
    from repro.kernels import ops
    from repro.models.decoding import kv_cache_rows

    Bsz, W, kv, dh, h = 2, 24, 2, 16, 4
    rng = np.random.default_rng(13)
    kvals = rng.standard_normal((2, Bsz, kv, W, dh)).astype(np.float32)
    cache = {}
    for nm, val in (("k", kvals[0]), ("v", kvals[1])):
        qt = B.quantize(jnp.asarray(val), "mxsf", (dh,))
        cache[f"{nm}_codes"] = qt.codes
        cache[f"{nm}_scales"] = qt.scale_e8m0[..., 0]
    q = jnp.asarray(rng.standard_normal((Bsz * h, 1, dh)).astype(np.float32))
    kvl = jnp.asarray(rng.integers(1, W + 1, size=Bsz * h), jnp.int32)
    off = kvl - 1
    y_cache = ops.mxsf_attention(q, cache["k_codes"], cache["k_scales"],
                                 cache["v_codes"], cache["v_scales"],
                                 causal=True, kv_len=kvl, q_offset=off, ck=8)
    kc, ks, vc, vs = kv_cache_rows(cache)
    # row layout is per (batch x kv-head): q rows map via bh // (h // kv)
    y_rows = ops.mxsf_attention(q, kc, ks, vc, vs, causal=True, kv_len=kvl,
                                q_offset=off, ck=8)
    np.testing.assert_array_equal(np.asarray(y_cache), np.asarray(y_rows))


def test_softcap_and_swa_fall_back():
    """Static gate: softcapped attention and windowed (SWA) patterns stay on
    the dequantize path (the kernel's masks are not ring-aware, and the
    'alternate'/'all' window masks need slot->position math)."""
    pol_p = _pols()[1]
    soft = get_config("gemma2-2b").reduced().replace(compute_dtype="float32")
    assert soft.attn_softcap
    assert M.decode_attn_backend(soft, pol_p) == "jnp"
    for pat in ("all", "alternate"):
        swa = _cfg(2).replace(swa_pattern=pat, swa_window=8)
        assert M.decode_attn_backend(swa, pol_p) == "jnp"
    # and the gated decode still runs finite
    params = M.init_params(jax.random.PRNGKey(0), soft)
    cache = M.init_cache(soft, 1, 4, kv_fmt="mxsf")
    logits, _ = M.decode_step(params, jnp.zeros((1, 1), jnp.int32), cache,
                              jnp.int32(0), soft, pol_p)
    assert bool(jnp.isfinite(logits).all())


def test_policy_gate():
    """use_pallas_attention requires pallas + packed cache + inference."""
    base = QuantPolicy(fwd_fmt="mxsf", block_mode="1d", quantize_bwd=False)
    assert not base.use_pallas_attention                      # jnp backend
    p = base.replace(backend="pallas")
    assert not p.use_pallas_attention                         # no packed KV
    p = p.replace(kv_cache_fmt="mxsf")
    assert p.use_pallas_attention
    assert not p.replace(quantize_bwd=True).use_pallas_attention
