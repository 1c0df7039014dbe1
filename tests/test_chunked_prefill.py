"""Chunked prefill: ceil(P/C) prompt dispatches, token-for-token identical
to the token-by-token path.

The engine's prefill phase (serve/engine.py) drains a P-token prompt in
C-token ``prefill_step`` dispatches.  Everything here is exact-parity
against the ``prefill_chunk=1`` fallback (the original token-by-token
schedule): same tokens out, across chunk sizes, non-chunk-aligned prompt
lengths, mixed prefill+decode batches, and both matmul backends — plus the
dispatch/trace accounting the chunking exists to improve.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.policy import BF16, MXSF_INFER
from repro.models import model as M
from repro.serve.engine import ServeEngine


def _cfg():
    return get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")


def _params(cfg):
    return M.init_params(jax.random.PRNGKey(0), cfg)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab, size=n)) for n in lengths]


def _serve(cfg, params, pol, prompts, max_new, chunk, **kw):
    eng = ServeEngine(cfg, params, pol, slots=2, max_len=32,
                      prefill_chunk=chunk, **kw)
    reqs = [eng.submit(p, max_new) for p in prompts]
    fin = eng.run()
    assert len(fin) == len(reqs) and all(r.done for r in reqs)
    return eng, [r.out for r in reqs]


@pytest.mark.parametrize("pol", [BF16,
                                 MXSF_INFER.replace(block_1d=16,
                                                    kv_cache_fmt="mxsf")],
                         ids=["bf16", "mxsf-kv"])
def test_chunk_sizes_match_token_by_token(pol):
    """Chunk sizes {1, 7, 16} x non-chunk-aligned prompt lengths: identical
    tokens (chunk=1 IS the original token-by-token schedule)."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, (1, 3, 5, 13, 16))
    outs = {}
    for chunk in (1, 7, 16):
        eng, outs[chunk] = _serve(cfg, params, pol, prompts, 4, chunk)
        if chunk > 1:
            assert eng.prefill_chunk == chunk
    assert outs[1] == outs[7] == outs[16], outs


@pytest.mark.parametrize("pol", [BF16,
                                 MXSF_INFER.replace(block_1d=16,
                                                    kv_cache_fmt="mxsf")],
                         ids=["bf16", "mxsf-kv"])
def test_final_chunk_overhanging_cache_end(pol):
    """Regression: a final partial chunk whose PADDED extent overhangs the
    cache width (pos + C - 1 >= max_len) must not perturb the mask math.
    The jnp path used to count the padded tail into ``end``, wrapping the
    ring position labels and causally masking real history away from the
    chunk's valid queries — silently wrong first generated token for any
    prompt landing within C of the cache end."""
    cfg = _cfg()
    params = _params(cfg)
    max_len, C = 16, 7
    for P in (15, 16):  # last chunk starts at 14 -> padded extent hits 20
        prompt = _prompts(cfg, (P,), seed=P)[0]
        outs = []
        for chunk in (1, C):
            eng = ServeEngine(cfg, params, pol, slots=2, max_len=max_len,
                              prefill_chunk=chunk)
            req = eng.submit(prompt, 2)
            eng.run()
            assert req.done
            outs.append(req.out)
        assert outs[0] == outs[1], (P, outs)


def test_pallas_backend_matches_and_compiles_once():
    """Chunked prefill through the MXSF kernel datapath (fused matmuls +
    packed-KV flash attention over S=C query rows): token-for-token vs the
    token-by-token pallas path, with exactly one extra attention-kernel
    compilation for the S=C prefill grid (the S=1 decode grid keeps its
    own single compile; neither retraces as prompts/caches grow)."""
    from repro.kernels import mxsf_attention as MA

    cfg = _cfg()
    params = _params(cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    prompts = _prompts(cfg, (3, 7, 10))

    t0 = MA.trace_count()
    eng1, out1 = _serve(cfg, params, pol, prompts, 3, 1, backend="pallas")
    assert eng1.attn_backend == "pallas-packed"
    d1 = MA.trace_count() - t0  # S=1 decode grid (fresh process: 1)

    t0 = MA.trace_count()
    engc, outc = _serve(cfg, params, pol, prompts, 3, 4, backend="pallas")
    assert outc == out1
    # prompts of length 3/7/10 and growing caches share ONE S=4 prefill
    # compile (dynamic kv_len/q_offset/n_valid); S=1 decode was cached above
    assert MA.trace_count() - t0 <= d1 + 1


def test_mixed_prefill_decode_batches():
    """One slot decodes while the other still prefills: the tick issues
    BOTH dispatches, and neither phase perturbs the other's tokens."""
    cfg = _cfg()
    params = _params(cfg)
    prompts = _prompts(cfg, (3, 11))
    _, out_ref = _serve(cfg, params, BF16, prompts, 5, 1)

    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=32,
                      prefill_chunk=4)
    reqs = [eng.submit(p, 5) for p in prompts]
    eng._admit()
    eng._tick()  # both slots prefill their first chunk
    assert eng.prefill_dispatches == 1 and eng.decode_dispatches == 0
    # slot 0 (P=3) finished its prompt and generated; slot 1 (P=11) has not
    assert len(reqs[0].out) == 1 and len(reqs[1].out) == 0
    assert eng.pending_prompt[1]
    eng._tick()  # mixed: slot 0 decodes, slot 1 prefills — SAME tick
    assert eng.prefill_dispatches == 2 and eng.decode_dispatches == 1
    assert len(reqs[0].out) == 2 and len(reqs[1].out) == 0
    eng.run()
    assert [r.out for r in reqs] == out_ref


def test_dispatch_accounting_and_no_retrace():
    """A P-token prompt costs exactly ceil(P/C) prefill dispatches and
    max_new-1 decode dispatches; serving different prompt lengths through
    one engine never retraces either jitted entry point."""
    cfg = _cfg()
    params = _params(cfg)
    for P, C in ((5, 4), (13, 4), (16, 4), (5, 16), (16, 16)):
        eng = ServeEngine(cfg, params, BF16, slots=2, max_len=32,
                          prefill_chunk=C)
        eng.submit(_prompts(cfg, (P,))[0], 3)
        eng.run()
        assert eng.prefill_dispatches == -(-P // C), (P, C)
        assert eng.decode_dispatches == 3 - 1, (P, C)

    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=32, prefill_chunk=4)
    for p in _prompts(cfg, (2, 9, 13)):
        eng.submit(p, 2)
    eng.run()
    for fn in (eng._prefill, eng._decode):
        n = getattr(fn, "_cache_size", lambda: 1)()
        assert n == 1, n  # pad-to-C + dynamic pos/n_valid: one trace each


def test_prefill_step_matches_decode_steps():
    """Unit parity: one prefill_step chunk == the same tokens pushed through
    decode_step one at a time — bit-identical cache, matching last logits,
    and untouched cache rows for an n_valid=0 (masked-out) slot."""
    cfg = _cfg()
    params = _params(cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    toks = _prompts(cfg, (5,))[0]
    C, W = 8, 16

    cache_seq = M.init_cache(cfg, 2, W, dtype=jnp.float32, ring=False,
                             kv_fmt="mxsf")
    logits = None
    for t, tok in enumerate(toks):
        logits, cache_seq = M.decode_step(
            params, jnp.asarray([[tok], [0]], jnp.int32), cache_seq,
            jnp.asarray([t, 0], jnp.int32), cfg, pol)

    cache_chk = M.init_cache(cfg, 2, W, dtype=jnp.float32, ring=False,
                             kv_fmt="mxsf")
    chunk = np.zeros((2, C), np.int32)
    chunk[0, : len(toks)] = toks
    logits_chk, cache_chk = M.prefill_step(
        params, jnp.asarray(chunk), cache_chk,
        jnp.asarray([0, 0], jnp.int32),
        jnp.asarray([len(toks), 0], jnp.int32), cfg, pol)

    # slot 0: the written prompt columns are bit-identical; the padded tail
    # C-columns and ALL of masked slot 1 stay at init (zeros)
    for k in cache_seq:
        a, b = np.asarray(cache_seq[k]), np.asarray(cache_chk[k])
        np.testing.assert_array_equal(
            a[:, :, 0, : len(toks)], b[:, :, 0, : len(toks)], err_msg=k)
        assert not b[:, :, 0, len(toks):].any(), k   # unwritten tail
        assert not b[:, :, 1].any(), k               # masked slot untouched
    np.testing.assert_allclose(np.asarray(logits_chk[0]),
                               np.asarray(logits[0]), rtol=1e-6, atol=1e-6)


def test_prefill_chunk_attention_kernel_vs_oracle():
    """The S=C cache-layout attention path agrees with the jnp oracle (which
    now accepts the cache pytree layout directly)."""
    from repro.core import blocking as B
    from repro.kernels import ops, ref

    rng = np.random.default_rng(7)
    Bsz, W, kv, dh, h, S = 2, 24, 2, 16, 4, 5
    kvals = rng.standard_normal((2, Bsz, kv, W, dh)).astype(np.float32)
    cache = {}
    for nm, val in (("k", kvals[0]), ("v", kvals[1])):
        qt = B.quantize(jnp.asarray(val), "mxsf", (dh,))
        cache[f"{nm}_codes"] = qt.codes
        cache[f"{nm}_scales"] = qt.scale_e8m0[..., 0]
    q = jnp.asarray(rng.standard_normal((Bsz * h, S, dh)).astype(np.float32))
    # chunk starts at position 3 with 3+S valid keys — decode-style dynamics
    off = jnp.full((Bsz * h,), 3, jnp.int32)
    kvl = off + S
    args = dict(causal=True, kv_len=kvl, q_offset=off)
    y = ops.mxsf_attention(q, cache["k_codes"], cache["k_scales"],
                           cache["v_codes"], cache["v_scales"], ck=8, **args)
    y_ref = ref.mxsf_flash_attention_ref(
        q, cache["k_codes"], cache["k_scales"],
        cache["v_codes"], cache["v_scales"], **args)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-6)


def test_moe_configs_fall_back_to_token_by_token():
    """Expert capacity is sized per dispatch: a C-token chunk can drop
    tokens the one-token path routes, so MoE engines pin chunk=1."""
    cfg = get_config("qwen2-moe-a2.7b").reduced().replace(
        compute_dtype="float32")
    assert cfg.n_experts > 0
    params = _params(cfg)
    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=16,
                      prefill_chunk=16)
    assert eng.prefill_chunk == 1
    assert eng._prefill is None
