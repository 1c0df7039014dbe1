"""Compile a cell's two serving steps for a described TPU v5e and print
their memory analysis.  Nothing runs; no chip is needed.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/rehearse.py qwen32b-chat ...

The weights are the configuration's architecture module's, packed as a run
packs them.  For each cell: the packed store's bytes, the packed cache's
bytes, and per step the compiled argument, output and temporary bytes.  The
engine's jit donates nothing, so a step's peak is about argument + output +
temporary bytes.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import spec  # noqa: E402


def rehearse(cell: str) -> dict:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import ops
    from repro.models import model as M
    from weights import root_key

    c = spec.load_cell(cell)
    A = spec.arch(c["config"])
    cfg, policy = A.program_config(c["config"]), spec.policy().replace(
        backend="pallas")
    s = A.sizes(c["config"])
    eng = c["engine"]
    slots, max_len, chunk = eng["slots"], eng["max_len"], eng["prefill_chunk"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    ops._interpret = lambda: False
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = place(jax.eval_shape(
        lambda k: M.pack_model_params(
            cfg, A.program_params(k, s, cfg.padded_vocab), policy),
        root_key(0)))
    cache = place(jax.eval_shape(
        lambda: M.init_cache(cfg, slots, max_len, dtype=cfg.compute_dtype,
                             ring=False, kv_fmt=policy.kv_cache_fmt)))
    nbytes = lambda t: sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(t))
    ints = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    steps = {
        "decode": (lambda p, t, ca, pos: M.decode_step(p, t, ca, pos, cfg,
                                                       policy),
                   (params, ints((slots, 1)), cache, ints((slots,)))),
        "prefill": (lambda p, t, ca, pos, nv: M.prefill_step(
            p, t, ca, pos, nv, cfg, policy),
            (params, ints((slots, chunk)), cache, ints((slots,)),
             ints((slots,)))),
    }
    out = {"cell": cell, "store_bytes": nbytes(params),
           "cache_bytes": nbytes(cache),
           "attn_backend": M.decode_attn_backend(cfg, policy)}
    for name, (fn, args) in steps.items():
        compiled = jax.jit(fn).lower(*args).compile()
        mem = compiled.memory_analysis()
        out[name] = {"argument_bytes": mem.argument_size_in_bytes,
                     "output_bytes": mem.output_size_in_bytes,
                     "temp_bytes": mem.temp_size_in_bytes,
                     "tpu_custom_calls":
                         compiled.as_text().count("tpu_custom_call")}
    return out


def main(argv):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    for cell in argv:
        print(json.dumps(rehearse(cell)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
