"""Continuous batching must generate the same tokens as sequential decode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core.policy import BF16
from repro.models import model as M
from repro.serve.engine import ServeEngine


def _sequential(cfg, params, prompt, max_new, max_len):
    cache = M.init_cache(cfg, 1, max_len, ring=False, dtype=jnp.float32)
    toks = list(prompt)
    out = []
    logits = None
    for t, tok in enumerate(toks):
        logits, cache = M.decode_step(
            params, jnp.asarray([[tok]], jnp.int32), cache, jnp.int32(t),
            cfg, BF16)
    cur = int(jnp.argmax(logits[0]))
    out.append(cur)
    pos = len(toks)
    while len(out) < max_new:
        logits, cache = M.decode_step(
            params, jnp.asarray([[cur]], jnp.int32), cache, jnp.int32(pos),
            cfg, BF16)
        cur = int(jnp.argmax(logits[0]))
        out.append(cur)
        pos += 1
    return out


def test_continuous_batching_matches_sequential():
    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, size=n)) for n in (3, 5, 2, 4, 3)]
    max_new = 4

    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=32)
    reqs = [eng.submit(p, max_new) for p in prompts]
    finished = eng.run()
    assert len(finished) == len(prompts)
    assert all(r.done for r in reqs)

    for p, r in zip(prompts, reqs):
        expect = _sequential(cfg, params, p, max_new, 32)
        assert r.out == expect, (p, r.out, expect)


def test_engine_rejects_ssm():
    cfg = get_config("mamba2-780m").reduced()
    with pytest.raises(NotImplementedError):
        ServeEngine(cfg, None, BF16)


def test_engine_long_prompt_rejected_and_capped():
    """A prompt >= max_len used to spin until max_ticks, incrementing pos
    past the cache width (OOB column writes).  Now: reject at submit (or
    truncate), and positions never exceed max_len."""
    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    max_len = 8
    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=max_len)
    long_prompt = list(range(max_len + 3))
    with pytest.raises(ValueError):
        eng.submit(long_prompt, max_new=4)

    # truncate=True: keeps the first max_len tokens and still terminates
    req = eng.submit(long_prompt, max_new=4, truncate=True)
    assert len(req.prompt) == max_len
    # exactly-at-capacity prompt: one token fits, then the cache is full
    req2 = eng.submit(list(range(max_len)), max_new=4)
    fin = eng.run(max_ticks=4 * max_len)
    assert {r.uid for r in fin} == {req.uid, req2.uid}  # no hang
    assert req.done and req2.done
    assert len(req.out) == 1 and len(req2.out) == 1  # capped by the cache
    assert int(eng.pos.max()) <= max_len


def test_engine_stops_at_eos():
    """Generation ends at the request's EOS token instead of always running
    to max_new; the EOS stays in ``out``.  Regression: the engine used to
    have no stop-token support at all."""
    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = list(rng.integers(0, cfg.vocab, size=5))
    max_new = 6

    # learn what the model emits, then replay with that token as EOS
    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=32)
    free = eng.submit(prompt, max_new)
    eng.run()
    assert len(free.out) == max_new
    # a clean cut point: the first generated token not seen earlier in the
    # output (the reduced model repeats tokens, so a fixed index may not be)
    cut = next(i for i in range(1, max_new) if free.out[i] not in free.out[:i])
    eos = free.out[cut]

    for chunk in (1, 4):  # both schedules honor EOS
        eng = ServeEngine(cfg, params, BF16, slots=2, max_len=32,
                          prefill_chunk=chunk, eos_id=eos)
        req = eng.submit(prompt, max_new)
        eng.run()
        assert req.done and req.out == free.out[:cut + 1], (chunk, req.out)

    # per-request eos_id overrides the engine default
    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=32, eos_id=eos)
    req = eng.submit(prompt, max_new, eos_id=free.out[0])
    eng.run()
    assert req.out == free.out[:1]
    # and eos on the FIRST generated token (emitted by the prefill
    # dispatch) retires the request straight out of the prefill phase
    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=32,
                      prefill_chunk=4, eos_id=free.out[0])
    req = eng.submit(prompt, max_new)
    eng.run()
    assert req.done and req.out == free.out[:1]
    assert eng.decode_dispatches == 0


def test_engine_pallas_packed_kv_matches_sequential():
    """ServeEngine(backend='pallas', kv_cache_fmt='mxsf') decodes through
    the packed-KV flash kernel: one kernel compile across the whole run,
    token-for-token vs sequential decode (same policy) AND vs the jnp
    sequential reference."""
    from repro.core.policy import MXSF_INFER
    from repro.kernels import mxsf_attention as MA

    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.vocab, size=n)) for n in (3, 5, 2)]
    max_new, max_len = 3, 16

    eng = ServeEngine(cfg, params, pol, slots=2, max_len=max_len,
                      backend="pallas")
    assert eng.attn_backend == "pallas-packed"
    # count from cold caches: a test run earlier in this process may have
    # traced the kernel at these shapes already
    jax.clear_caches()
    traces0 = MA.trace_count()
    reqs = [eng.submit(p, max_new) for p in prompts]
    fin = eng.run()
    assert len(fin) == len(prompts) and all(r.done for r in reqs)
    # growing cache, two jitted entry points (S=1 decode + S=C chunked
    # prefill) -> exactly one kernel compile per grid, regardless of how
    # many prompts/tokens were served
    assert MA.trace_count() == traces0 + 2

    def sequential(policy, prompt):
        cache = M.init_cache(cfg, 1, max_len, ring=False, kv_fmt="mxsf")
        step = jax.jit(lambda p_, t, c, pos: M.decode_step(p_, t, c, pos,
                                                           cfg, policy))
        out, logits = [], None
        for t, tok in enumerate(prompt):
            logits, cache = step(params, jnp.asarray([[tok]], jnp.int32),
                                 cache, jnp.int32(t))
        cur = int(jnp.argmax(logits[0]))
        out.append(cur)
        pos = len(prompt)
        while len(out) < max_new:
            logits, cache = step(params, jnp.asarray([[cur]], jnp.int32),
                                 cache, jnp.int32(pos))
            cur = int(jnp.argmax(logits[0]))
            out.append(cur)
            pos += 1
        return out

    pol_pallas = pol.replace(backend="pallas")
    for p, r in zip(prompts, reqs):
        # same policy -> identical math -> exact token-for-token
        assert r.out == sequential(pol_pallas, p), p

    # jnp reference: teacher-forced per-step comparison (sequence-level
    # comparison compounds a single argmax flip), the only divergence being
    # the documented probs-requantization the kernel's online softmax skips
    def forced_logits(policy, stream):
        cache = M.init_cache(cfg, 1, max_len, ring=False, kv_fmt="mxsf")
        step = jax.jit(lambda p_, t, c, pos: M.decode_step(p_, t, c, pos,
                                                           cfg, policy))
        outs = []
        for t, tok in enumerate(stream):
            logits, cache = step(params, jnp.asarray([[tok]], jnp.int32),
                                 cache, jnp.int32(t))
            outs.append(logits[0])
        return jnp.stack(outs)

    stream = prompts[0] + reqs[0].out
    lj = forced_logits(pol, stream)
    lp = forced_logits(pol_pallas, stream)
    rel = float(jnp.abs(lj - lp).max() / (jnp.abs(lj).max() + 1e-9))
    agree = float((jnp.argmax(lj, -1) == jnp.argmax(lp, -1)).mean())
    assert rel < 0.1, rel
    assert agree >= 0.8, agree


def test_engine_reports_lhs_convert_share():
    """stats()["lhs_convert_share"] per compiled step: the fused matmuls'
    activation tiles converted over their grid steps, each call site
    traced once (the layer scan's body once).  The layer's seven linears
    have one N tile each (1 conversion in 1 step apiece); the 1024-wide
    head has four, served by one converted row block: 8 / 11."""
    from repro.core.policy import MXSF_INFER

    cfg = get_config("qwen2.5-32b").reduced().replace(
        compute_dtype="float32", n_layers=1, vocab=1024)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    eng = ServeEngine(cfg, params, pol, slots=2, max_len=16,
                      prefill_chunk=4, backend="pallas")
    assert eng.stats()["lhs_convert_share"] == {}  # nothing traced yet
    eng.submit([1, 2, 3, 4, 5, 6], 2)
    eng.run()
    assert eng.prefill_dispatches and eng.decode_dispatches
    assert eng.stats()["lhs_convert_share"] == {"prefill": 8 / 11,
                                                "decode": 8 / 11}
    jnp_eng = ServeEngine(cfg, params, pol, slots=2, max_len=16,
                          prefill_chunk=4)
    jnp_eng.submit([1, 2, 3], 1)
    jnp_eng.run()
    assert jnp_eng.stats()["lhs_convert_share"] == {}  # no fused matmul
