"""The MXSF kernels and serving steps compile for a TPU v5e.

Interpret-mode parity cannot see what the TPU compiler refuses (block
shapes off the (8, 128) tiling, shape casts Mosaic cannot lower, scalar
blocks in SMEM), so these tests hand every kernel of the main path to the
real compiler for a *described* v5e chip, at the published qwen2.5-32b
widths ``chip_smoke.py`` serves.  Nothing runs: no chip is needed.

The topology is described inside a module fixture, never at import time
(the TPU library may be loaded by one process at a time, and every test
worker imports this file).  The kernels' interpret switch reads the CPU
backend, so each test forces compiled mode through ``ops._interpret``.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.policy import MXSF_INFER
from repro.kernels import mxsf_attention, ops
from repro.models import model as M

# the shapes chip_smoke.py serves (one chip): qwen2.5-32b cut to 4 layers,
# 4 slots, a 512-column cache, 128-token prefill chunks
D, FF, H, KV, DH = 5120, 27648, 40, 8, 128
LAYERS, SLOTS, MAX_LEN, CHUNK = 4, 4, 512, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


@pytest.mark.parametrize("block", [(1, 64), (64, 1), (8, 8)])
def test_quantize_compiles(one_chip, compiled_kernels, block):
    x = _spec(one_chip, (256, D), "float32")
    _compile(lambda x: ops.mxsf_quantize(x, block=block), x)


@pytest.mark.parametrize("from_block,to_block",
                         [((64, 1), (1, 64)), ((8, 8), (1, 64))])
def test_requantize_compiles(one_chip, compiled_kernels, from_block,
                             to_block):
    m, k = D, 1024
    codes = _spec(one_chip, (m, k), "uint8")
    scales = _spec(one_chip, (m // from_block[0], k // from_block[1]),
                   "uint8")
    _compile(lambda c, s: ops.mxsf_requantize(c, s, from_block, to_block),
             codes, scales)


@pytest.mark.parametrize("xblk,wblk", [((1, 64), (64, 1)), ((8, 8), (8, 8))])
def test_dequant_matmul_compiles(one_chip, compiled_kernels, xblk, wblk):
    m, k, n = 256, D, 1024
    args = [_spec(one_chip, (m, k), "uint8"),
            _spec(one_chip, (m // xblk[0], k // xblk[1]), "uint8"),
            _spec(one_chip, (k, n), "uint8"),
            _spec(one_chip, (k // wblk[0], n // wblk[1]), "uint8")]
    _compile(lambda *a: ops.mxsf_matmul(*a, xblk=xblk, wblk=wblk), *args)


@pytest.mark.parametrize("m,k,n,xblk,wblk,emit", [
    (SLOTS, D, FF, (1, 64), (64, 1), False),           # decode: wg/wu
    (SLOTS * CHUNK, FF, D, (1, 64), (64, 1), False),   # prefill chunk: wd
    (256, D, 1024, (8, 8), (8, 8), True),              # training forward
    # the benchmark's largest linears, whose resident row blocks take the
    # most VMEM: qwen2.5-32b's wd and head at 2048 prefill rows, danube's
    # wd at 4096
    (2048, FF, D, (1, 64), (64, 1), False),
    (2048, D, 153600, (1, 64), (64, 1), False),
    (4096, 6912, 2560, (1, 64), (64, 1), False),
])
def test_fused_matmul_compiles(one_chip, compiled_kernels, m, k, n, xblk,
                               wblk, emit):
    args = [_spec(one_chip, (m, k), "bfloat16"),
            _spec(one_chip, (k, n), "uint8"),
            _spec(one_chip, (k // wblk[0], n // wblk[1]), "uint8")]
    compiled = _compile(lambda *a: ops.mxsf_fused_matmul(
        *a, xblk, wblk, emit_codes=emit), *args)
    # the benchmark finds the kernel by this op name and reads (m, n) from
    # its first output and (m, k) from its first operand
    call = [ln for ln in compiled.as_text().splitlines()
            if "tpu_custom_call" in ln and "custom-call(" in ln]
    assert len(call) == 1, call
    name, rest = call[0].split(" = ", 1)
    assert name.split()[-1].startswith("%mxsf_fused_matmul_pallas."), name
    np_ = -(-n // 256) * 256
    assert rest.lstrip("(").startswith(f"f32[{m},{np_}]"), rest[:80]
    assert f"operand_layout_constraints={{bf16[{m}," in rest, rest


@pytest.mark.parametrize("s", [1, CHUNK])  # decode step, prefill chunk
def test_packed_kv_attention_compiles(one_chip, compiled_kernels, s):
    bh = SLOTS * H
    q = _spec(one_chip, (bh, s, DH), "bfloat16")
    codes = _spec(one_chip, (SLOTS, KV, MAX_LEN, DH), "uint8")
    scales = _spec(one_chip, (SLOTS, KV, MAX_LEN), "uint8")
    vec = _spec(one_chip, (bh,), "int32")
    compiled = _compile(
        lambda q, kc, ks, vc, vs, kvl, off: ops.mxsf_attention(
            q, kc, ks, vc, vs, kv_len=kvl, q_offset=off),
        q, codes, scales, codes, scales, vec, vec)
    # the uint8 cache feeds the kernel as stored: no relaid copy of it
    copies = [ln for ln in compiled.as_text().splitlines()
              if " copy(" in ln and "= u8[" in ln]
    assert not copies, copies
    assert mxsf_attention.trace_count() > 0


def _serving_specs(place):
    """Shapes of the packed store and packed cache ``chip_smoke.py`` serves,
    placed by ``place(shape_tree) -> ShapeDtypeStruct tree``."""
    cfg = get_config("qwen2.5-32b").replace(n_layers=LAYERS)
    policy = MXSF_INFER.replace(kv_cache_fmt="mxsf", backend="pallas")
    params = place(jax.eval_shape(
        lambda k: M.pack_model_params(cfg, M.init_params(k, cfg), policy),
        jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: M.init_cache(cfg, SLOTS, MAX_LEN, dtype=cfg.compute_dtype,
                             ring=False, kv_fmt="mxsf")))
    return cfg, policy, params, cache


def _steps(cfg, policy):
    return {
        "decode": lambda p, t, c, pos: M.decode_step(p, t, c, pos, cfg,
                                                     policy),
        "prefill": lambda p, t, c, pos, nv: M.prefill_step(p, t, c, pos, nv,
                                                           cfg, policy),
    }


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_serving_step_compiles(one_chip, compiled_kernels, phase):
    """The engine's jitted decode and prefill steps at the published widths
    (depth cut to ``LAYERS``) compile with the MXSF kernels inside."""
    place = lambda tree: jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    cfg, policy, params, cache = _serving_specs(place)
    assert M.decode_attn_backend(cfg, policy) == "pallas-packed"
    vec = _spec(one_chip, (SLOTS,), "int32")
    toks = _spec(one_chip, (SLOTS, 1 if phase == "decode" else CHUNK),
                 "int32")
    args = (params, toks, cache, vec) + ((vec,) if phase == "prefill" else ())
    compiled = _compile(_steps(cfg, policy)[phase], *args)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4 * 2**30, mem


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_sharded_serving_step_compiles(topo, compiled_kernels, phase):
    """The same steps on a 2x2 ("data", "model") mesh, laid out as the
    sharded ``ServeEngine`` lays them out.  The TPU compiler cannot
    partition a Pallas kernel, so this fails unless every kernel call runs
    shard-local (``core/sharding.shard_local``)."""
    from jax.sharding import Mesh

    from repro.core import sharding as shd
    from repro.launch import mesh as mesh_lib

    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                ("data", "model"))
    rules = mesh_lib.MeshRules(mesh)
    shapes = lambda tree: tree
    cfg, policy, pshape, cshape = _serving_specs(shapes)
    psh = rules.param_sharding_tree(pshape)
    csh = mesh_lib.cache_shardings(rules, cshape, SLOTS)
    assert M.decode_attn_backend(cfg, policy, csh) == "pallas-packed"
    put = lambda tree, sh: jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, sh)
    width = 1 if phase == "decode" else CHUNK
    tok = rules.named(rules.data_spec((SLOTS, width)))
    vec = rules.named(rules.data_spec((SLOTS,)))
    logit = rules.named(rules.data_spec((SLOTS, cfg.padded_vocab)))
    ints = lambda shape, sh: jax.ShapeDtypeStruct(shape, jnp.int32,
                                                  sharding=sh)
    args = (put(pshape, psh), ints((SLOTS, width), tok), put(cshape, csh),
            ints((SLOTS,), vec))
    in_sh = (psh, tok, csh, vec)
    if phase == "prefill":
        args, in_sh = args + (ints((SLOTS,), vec),), in_sh + (vec,)
    with shd.mesh_context(mesh, rules.dp, rules.tp):
        step = jax.jit(_steps(cfg, policy)[phase], in_shardings=in_sh,
                       out_shardings=(logit, csh))
        text = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
