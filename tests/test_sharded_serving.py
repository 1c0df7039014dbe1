"""Sharded serving: mesh parity, packed-store/cache layout, fallbacks,
packed checkpoint -> sharded restore.

The multi-device tests run in-process and need forced host devices
(CI: ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set before
pytest starts); on smaller boxes they skip.  The mesh-free tests
(make_test_mesh clamping, auto prefill chunk, stats accounting) run
anywhere, including the single-device tier-1 pass.
"""
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.ckpt import ckpt
from repro.configs.base import get_config
from repro.core import packed_store
from repro.core.blocking import QuantizedTensor
from repro.core.policy import BF16, MXSF_INFER
from repro.launch import mesh as mesh_lib
from repro.models import model as M
from repro.serve.engine import ServeEngine, auto_prefill_chunk

NDEV = len(jax.devices())
need2 = pytest.mark.skipif(NDEV < 2, reason="needs >= 2 (forced) devices")
need4 = pytest.mark.skipif(NDEV < 4, reason="needs >= 4 (forced) devices")


def _mesh(data, model):
    n = data * model
    return Mesh(np.asarray(jax.devices()[:n]).reshape(data, model),
                ("data", "model"))


def _cfg(**kw):
    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    return cfg.replace(**kw) if kw else cfg


def _prompts(cfg, sizes=(3, 5)):
    rng = np.random.default_rng(0)
    return [list(rng.integers(0, cfg.vocab, size=n)) for n in sizes]


def _serve(cfg, params, pol, mesh, prompts, max_new=3, **kw):
    eng = ServeEngine(cfg, params, pol, slots=2, max_len=16,
                      prefill_chunk=4, mesh=mesh, **kw)
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run()
    assert all(r.done for r in reqs)
    return eng, [r.out for r in reqs]


def _packed_leaves(params):
    return [x for x in jax.tree_util.tree_leaves(
        params, is_leaf=lambda v: isinstance(v, QuantizedTensor))
        if isinstance(x, QuantizedTensor)]


# ---------------------------------------------------------------------------
# mesh-free tests (run on any device count, incl. tier-1)
# ---------------------------------------------------------------------------

def test_make_test_mesh_clamps_both_axes():
    """A request larger than the box must clamp instead of raising — the
    old version clamped only ``data``, so 1 device + the default model=2
    raised from jax.make_mesh."""
    for data, model in ((2, 2), (1, 2), (16, 16), (1000, 3)):
        m = mesh_lib.make_test_mesh(data, model)
        sizes = dict(m.shape)
        assert set(sizes) == {"data", "model"}
        assert sizes["data"] * sizes["model"] <= max(1, NDEV)
        assert sizes["data"] >= 1 and sizes["model"] >= 1
    # the degenerate floor: with everything clamped away we get (1, 1)
    m = mesh_lib.make_test_mesh(1, 1)
    assert dict(m.shape) == {"data": 1, "model": 1}


def test_auto_prefill_chunk_heuristic(tmp_path, monkeypatch):
    # bounded by the cache width and >= 1 everywhere
    for ml, sl in ((1, 1), (8, 2), (256, 4), (4096, 16), (16, 64)):
        c = auto_prefill_chunk(ml, sl)
        assert 1 <= c <= ml, (ml, sl, c)
    # the shape heuristic: fill one fused-matmul M tile across slots,
    # drain a full prompt in >= 4 chunks
    assert auto_prefill_chunk(256, 4) == 64
    assert auto_prefill_chunk(16, 2) == 4
    # a kernel-bench file in the working directory (or named by the
    # variable the bench writes to) does not change the pick
    bench = tmp_path / "BENCH_kernel.json"
    bench.write_text(json.dumps({"rows": [
        {"name": "kernel_prefill_chunked_dispatches", "derived": "P=12,C=8"},
    ]}))
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("BENCH_KERNEL_JSON", str(bench))
    assert auto_prefill_chunk(16, 64) == 4
    # integer values keep exact current behavior (no heuristic involved)
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=16,
                      prefill_chunk=7)
    assert eng.prefill_chunk == 7
    eng = ServeEngine(cfg, params, BF16, slots=2, max_len=16,
                      prefill_chunk="auto")
    assert 1 <= eng.prefill_chunk <= 16
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, BF16, slots=2, max_len=16,
                    prefill_chunk="huge")


def test_engine_stats_accounting():
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    eng, toks = _serve(cfg, params, BF16, None, _prompts(cfg), max_new=3)
    st = eng.stats()
    assert st["tokens_generated"] == sum(len(t) for t in toks)
    assert st["prefill_dispatches"] == eng.prefill_dispatches > 0
    assert st["decode_dispatches"] == eng.decode_dispatches > 0
    assert st["ticks"] == eng.ticks > 0
    # rows handed to the steps and rows that did work: every prompt token
    # and every decode row (the first token comes from the prefill)
    assert st["rows_computed"] == eng.slots * (
        st["decode_dispatches"] + eng.prefill_chunk
        * st["prefill_dispatches"])
    assert st["rows_useful"] == sum(len(p) for p in _prompts(cfg)) + sum(
        len(t) - 1 for t in toks)
    assert 0.0 < st["occupancy"] <= 1.0
    assert st["mesh"] is None and st["shard_fallback"] is None
    assert st["live"] == 0 and st["queued"] == 0
    # per-device accounting covers every byte of the (unsharded) store
    assert sum(st["store_nbytes_per_device"].values()) == \
        st["store_nbytes"]["total"]
    assert sum(st["cache_nbytes_per_device"].values()) > 0


def test_packed_spec_grid_divisibility_fallback():
    """Packed-layout rule: a dim splits only when the SCALE GRID divides
    the mesh axis — judged on padded extents, so a (64, N) weight under
    24-row blocks (grid 3) replicates on a 2-way axis even though
    64 % 2 == 0; under 16-row blocks (grid 4) it shards."""
    w = jnp.asarray(np.random.default_rng(0).standard_normal((64, 64)),
                    jnp.float32)
    pol24 = MXSF_INFER.replace(block_1d=24)
    pol16 = MXSF_INFER.replace(block_1d=16)
    qt24 = packed_store.pack_leaf(w, pol24)
    qt16 = packed_store.pack_leaf(w, pol16)
    assert qt24.scale_e8m0.shape[0] == 3  # ceil(64/24) blocks
    base = jax.sharding.PartitionSpec(("data",), None)
    axis = {"data": 2, "model": 1}
    # compare PartitionSpecs, not tuples: JAX normalises ("data",) to "data"
    assert packed_store.packed_spec(qt24, base, axis) == P(None, None)
    assert packed_store.packed_spec(qt16, base, axis) == P(("data",), None)
    # the kernel-gate check agrees with the spec builder
    assert packed_store.shard_block_aligned(qt16, base, axis)
    assert not packed_store.shard_block_aligned(qt24, base, axis)


# ---------------------------------------------------------------------------
# multi-device tests (forced host devices; CI runs them per push)
# ---------------------------------------------------------------------------

@need4
@pytest.mark.slow
def test_sharded_engine_token_parity_across_meshes():
    """Token-for-token vs the single-device engine on every mesh shape,
    full packed datapath (pallas fused matmul + packed-KV flash kernel +
    pack-once store); on 2x2 the store and cache must ACTUALLY shard."""
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    prompts = _prompts(cfg)
    base, want = _serve(cfg, params, pol, None, prompts, backend="pallas")
    assert base.attn_backend == "pallas-packed"

    for data, model in ((1, 1), (2, 1), (1, 2), (2, 2)):
        eng, got = _serve(cfg, params, pol, _mesh(data, model), prompts,
                          backend="pallas")
        assert got == want, (data, model, got, want)
        assert eng.attn_backend == "pallas-packed"
        assert eng.shard_fallback is None

    # layout asserts on the live 2x2 arrays
    eng, got = _serve(cfg, params, pol, _mesh(2, 2), prompts,
                      backend="pallas")
    kc = eng.cache["k_codes"]
    spec = tuple(kc.sharding.spec)
    assert P(spec[-4]) == P(("data",))  # slot batch over the data axes
    assert spec[-3] == "model"          # kv heads over the model axis
    assert spec[-2] is None             # position axis NEVER sharded here
    assert kc.sharding.num_devices == 4
    qts = _packed_leaves(eng.params)
    assert qts, "pack-once store missing"
    sharded = [q for q in qts
               if any(s is not None for s in tuple(q.codes.sharding.spec))]
    assert sharded, "no packed leaf actually sharded on the 2x2 mesh"
    for q in qts:
        assert q.codes.sharding.num_devices == 4
        # codes and scales split together (same spec) so every device
        # holds the shared exponents for exactly its own code blocks
        assert tuple(q.codes.sharding.spec) == \
            tuple(q.scale_e8m0.sharding.spec)
    # per-device store bytes really dropped vs the single-device engine
    per_dev = eng.stats()["store_nbytes_per_device"]
    assert max(per_dev.values()) < base.stats()["store_nbytes_per_device"][
        str(jax.devices()[0])]


@need4
@pytest.mark.slow
def test_sharded_engine_bf16_value_cache_parity():
    """The mesh path is not packed-store-specific: the bf16 baseline
    policy (value-domain cache, no packed leaves) shards and matches."""
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompts = _prompts(cfg)
    _, want = _serve(cfg, params, BF16, None, prompts)
    eng, got = _serve(cfg, params, BF16, _mesh(2, 2), prompts)
    assert got == want
    assert P(tuple(eng.cache["k"].sharding.spec)[-4]) == P(("data",))


@need2
@pytest.mark.slow
def test_uneven_kv_heads_sequence_parallel_fallback():
    """kv=1 cannot split a 2-way model axis: the cache falls back to
    sequence parallelism (position axis sharded), which the flash kernel
    cannot consume shard-local — the engine must record the per-config
    jnp fallback and still match the single-device jnp-attention path
    token-for-token."""
    cfg = _cfg(n_kv=1)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    prompts = _prompts(cfg)
    # baseline: same policy, packed-attention kernel disabled -> the exact
    # numerics the fallback path runs (kernel vs jnp attention differ by
    # the documented probs-requantization, so compare like with like)
    base, want = _serve(cfg, params, pol.replace(pallas_attention=False),
                        None, prompts, backend="pallas")
    assert base.attn_backend == "jnp"
    eng, got = _serve(cfg, params, pol, _mesh(1, 2), prompts,
                      backend="pallas")
    assert eng.attn_backend == "jnp"
    assert eng.shard_fallback and "position axis" in eng.shard_fallback
    assert got == want, (got, want)
    # the cache really took the sequence-parallel layout
    spec = tuple(eng.cache["k_codes"].sharding.spec)
    assert P(spec[-2]) == P("model")


@need4
@pytest.mark.slow
def test_static_gate_jnp_not_misattributed_to_mesh():
    """A config the STATIC attention gate already rejects (SWA) must not
    be reported as a mesh-layout fallback: shard_fallback stays None even
    though attn_backend is 'jnp' under the mesh."""
    cfg = _cfg(swa_pattern="all", swa_window=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    eng = ServeEngine(cfg, params, pol, slots=2, max_len=16,
                      prefill_chunk=4, backend="pallas", mesh=_mesh(2, 2))
    assert eng.attn_backend == "jnp"
    assert eng.shard_fallback is None


@need4
@pytest.mark.slow
def test_packed_ckpt_restores_sharded_bitwise():
    """save packed store -> restore straight onto a 2x2 mesh (per-shard
    uint8 placement, no host f32) -> decode bitwise vs the source engine."""
    cfg = _cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    prompts = _prompts(cfg)
    src, want = _serve(cfg, params, pol, None, prompts, backend="pallas")
    assert src.packed

    with tempfile.TemporaryDirectory() as td:
        ckpt.save(td, 0, src.params)
        mesh = _mesh(2, 2)
        eng = ServeEngine.from_checkpoint(
            cfg, td, pol, mesh=mesh, backend="pallas",
            slots=2, max_len=16, prefill_chunk=4)
        # restored packed leaves are uint8 on their serving shards —
        # full-precision weights never existed on host or device
        qts = _packed_leaves(eng.params)
        assert qts
        for q in qts:
            assert q.codes.dtype == jnp.uint8
            assert q.scale_e8m0.dtype == jnp.uint8
            assert q.codes.sharding.num_devices == 4
        # bitwise-identical store after the round trip
        src_qts = _packed_leaves(src.params)
        for a, b in zip(src_qts, qts):
            assert bool(jnp.array_equal(a.codes, b.codes))
            assert bool(jnp.array_equal(a.scale_e8m0, b.scale_e8m0))
        reqs = [eng.submit(p, 3) for p in prompts]
        eng.run()
        assert [r.out for r in reqs] == want
