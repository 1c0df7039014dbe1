"""The serving engine's spans, named steps and attention scope, read back
from a real profiler trace.

A tiny decoder engine serves a few requests under ``jax.profiler.trace``
and ``bench/program_trace.py`` reads the trace back: the ``engine.*`` spans
nest as documented and carry the counters ``stats()`` sums, the steps
compile as ``jit_serve_decode_step`` / ``jit_serve_prefill_step``, and the
cached attention's ops (jnp path and the interpret-mode Pallas kernel) sit
under the ``attention`` scope.
"""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))

import program_trace as pt  # noqa: E402
from repro.configs.base import get_config  # noqa: E402
from repro.core.policy import MXSF_INFER  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.serve.engine import ServeEngine  # noqa: E402

PROMPTS = ([5, 6, 7, 8, 9], [3, 4], [11, 12, 13])
MAX_NEW = 3
PHASE_SPANS = {"engine.decode", "engine.decode.dispatch",
               "engine.decode.sync", "engine.prefill",
               "engine.prefill.dispatch", "engine.prefill.sync"}
COUNTERS = ("ticks", "decode_dispatches", "prefill_dispatches",
            "tokens_generated", "rows_computed", "rows_useful")


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """``get(chunk, backend)`` -> (engine, requests, trace, counts): an
    engine, warmed (compiled) outside the trace, that serves ``PROMPTS``
    (three requests on two slots, so one waits in the queue) wholly inside
    one trace; ``counts`` are its ``stats()`` counters over the trace."""
    cfg = get_config("qwen2.5-32b").reduced().replace(
        compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    done = {}

    def get(chunk, backend):
        if (chunk, backend) not in done:
            eng = ServeEngine(cfg, params, pol, slots=2, max_len=16,
                              prefill_chunk=chunk, backend=backend)
            eng.submit([1, 2, 3], 2)
            eng.run()
            before = eng.stats()
            where = tmp_path_factory.mktemp(f"trace_{chunk}_{backend}")
            with jax.profiler.trace(str(where)):
                reqs = [eng.submit(p, MAX_NEW) for p in PROMPTS]
                eng.run()
            counts = {k: v - before[k] for k, v in eng.stats().items()
                      if k in COUNTERS}
            done[chunk, backend] = eng, reqs, pt.load(str(where)), counts
        return done[chunk, backend]

    return get


def _holder(span, spans, name):
    """The span named ``name`` that holds ``span``."""
    held = [s for s in spans if s[0] == name and s[1] <= span[1]
            and span[2] <= s[2]]
    assert len(held) == 1, (span, held)
    return held[0]


@pytest.mark.parametrize("chunk", [4, 1])
def test_spans_nest_and_carry_the_counters_stats_sums(served, chunk):
    _, reqs, trace, st = served(chunk, "jnp")
    spans = trace.spans
    names = {s[0] for s in spans}
    want = {"engine.tick", "engine.admit", "engine.emit"} | PHASE_SPANS
    if chunk > 1:
        want.add("engine.prefill.pack")
    assert names == want
    ticks = [s for s in spans if s[0] == "engine.tick"]
    first = ticks[0][3]["step_num"]
    assert [s[3]["step_num"] for s in ticks] == list(
        range(first, first + st["ticks"]))
    assert ticks[0][3]["queued"] == len(PROMPTS)
    # each sync inside its dispatch's phase span, inside a tick
    for phase in ("engine.decode", "engine.prefill"):
        for sync in (s for s in spans if s[0] == phase + ".sync"):
            _holder(_holder(sync, spans, phase), spans, "engine.tick")
    for s in spans:
        if s[0] != "engine.tick":
            _holder(s, spans, "engine.tick")
    # the counters: what stats() sums, and what the requests consumed
    phases = [s for s in spans if s[0] in ("engine.decode",
                                           "engine.prefill")]
    assert sum(s[3]["rows_useful"] for s in phases) == st["rows_useful"]
    assert sum(s[3]["rows_computed"] for s in phases) == \
        st["rows_computed"]
    assert st["rows_useful"] == sum(len(r.prompt) + len(r.out) - 1
                                    for r in reqs)
    assert len(phases) == st["decode_dispatches"] + st["prefill_dispatches"]
    emits = [s[3] for s in spans if s[0] == "engine.emit"]
    assert sum(a["emitted"] for a in emits) == st["tokens_generated"]
    assert sum(a["finished"] for a in emits) == len(PROMPTS)
    admits = [s[3] for s in spans if s[0] == "engine.admit"]
    assert sum(a["admitted"] for a in admits) == len(PROMPTS)
    # the third request waited in the queue for a slot
    assert max(a["queue_wait_ms"] for a in admits) > 0


@pytest.mark.parametrize("chunk", [4, 1])
def test_steps_run_as_named_modules(served, chunk):
    _, _, trace, st = served(chunk, "jnp")
    runs = {}
    for module, a, b in trace.steps:
        assert b >= a
        runs[module] = runs.get(module, 0) + 1
    if chunk > 1:
        assert runs["jit_serve_decode_step"] == st["decode_dispatches"]
        assert runs["jit_serve_prefill_step"] == st["prefill_dispatches"]
    else:
        # the token-by-token path runs both phases through decode_step
        assert runs["jit_serve_decode_step"] == \
            st["decode_dispatches"] + st["prefill_dispatches"]
        assert "jit_serve_prefill_step" not in runs


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_attention_ops_sit_under_the_attention_scope(served, backend):
    eng, _, trace, _ = served(4, backend)
    assert eng.attn_backend == ("pallas-packed" if backend == "pallas"
                                else "jnp")
    scoped = [o for o in trace.ops if pt.in_scope(o[4], "attention")]
    for module in ("jit_serve_decode_step", "jit_serve_prefill_step"):
        assert any(o[3] == module for o in scoped), module
    if backend == "pallas":
        assert any("_flash_attention_jit" in o[4] for o in scoped)
    # the projections stay outside it
    assert any(o[4] and not pt.in_scope(o[4], "attention")
               for o in trace.ops if o[3] == "jit_serve_decode_step")
    t0 = min(s[1] for s in trace.spans)
    t1 = max(s[2] for s in trace.spans)
    assert 0 < pt.scope_time(trace.ops, "attention", t0, t1) < \
        (t1 - t0) * 1e-9


def test_steps_keep_their_names_under_a_mesh():
    """The mesh wrapper keeps the step's name, so the compiled module is
    named the same however the engine is placed."""
    from repro.core.policy import BF16
    from repro.launch import mesh as mesh_lib
    cfg = get_config("qwen2.5-32b").reduced().replace(
        compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=16,
                      prefill_chunk=4, mesh=mesh_lib.make_test_mesh(1, 1))
    assert eng._decode.__name__ == "serve_decode_step"
    assert eng._prefill.__name__ == "serve_prefill_step"
