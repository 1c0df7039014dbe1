"""Sharded-serving benchmark: single-device vs mesh engine, same requests.

Wall-clock on forced host devices is NOT pod performance (every "device"
is a slice of one CPU); what transfers are the STRUCTURAL rows this file
emits — per-device store/cache bytes (does the memory actually split?),
dispatch counts (sharding must not change the schedule), and the
token-for-token parity bit (sharding must not change the tokens).
Emits ``BENCH_shard.json`` (override with ``$BENCH_SHARD_JSON``).

Run under forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -c "from benchmarks import shard_bench; shard_bench.run()"

or let ``python -m benchmarks.shard_bench`` re-exec itself with the flag.
"""
from __future__ import annotations

import os
import sys


def _reexec_with_devices(n: int = 8):
    """Set the fake-device flag BEFORE jax initializes and re-exec."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={n}").strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    os.execvpe(sys.executable, [sys.executable, "-m", "benchmarks.shard_bench"],
               env)


def run():
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import get_config
    from repro.core.policy import MXSF_INFER
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    from . import common
    from .common import emit, time_call, write_json

    json_start = len(common.ROWS_JSON)
    devices = jax.devices()
    if len(devices) < 4:
        emit("shard_bench_skipped", 0.0,
             f"needs >= 4 devices, have {len(devices)} (run under "
             "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        # still write the JSON so the skip is observable (and the CI
        # artifact upload that follows has a file to upload)
        write_json(os.environ.get("BENCH_SHARD_JSON", "BENCH_shard.json"),
                   start=json_start)
        return

    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    rng = np.random.default_rng(0)
    slots, max_len, max_new = 2, 16, 2
    prompts = [list(rng.integers(0, cfg.vocab, size=n)) for n in (5, 3)]

    def serve(mesh):
        eng = ServeEngine(cfg, params, pol, slots=slots, max_len=max_len,
                          backend="pallas", prefill_chunk=4, mesh=mesh)
        reqs = [eng.submit(p, max_new) for p in prompts]
        us, _ = time_call(lambda: eng.run(), iters=1, warmup=0)
        return eng, [r.out for r in reqs], us

    eng1, toks1, us1 = serve(None)
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "model"))
    eng4, toks4, us4 = serve(mesh)

    st1, st4 = eng1.stats(), eng4.stats()
    equal = toks1 == toks4
    emit("shard_serve_tokens_equal", 0.0, str(equal))
    assert equal, (toks1, toks4)
    assert (st1["prefill_dispatches"], st1["decode_dispatches"]) == \
           (st4["prefill_dispatches"], st4["decode_dispatches"])
    emit("shard_serve_dispatches", 0.0,
         f"prefill={st4['prefill_dispatches']},"
         f"decode={st4['decode_dispatches']}(same_as_single_device)",
         dispatches=st4["prefill_dispatches"] + st4["decode_dispatches"])

    # per-device memory: the headline structural win.  Store bytes follow
    # the packed-layout MeshRules shards; the cache splits its slot batch
    # over "data" and kv heads over "model".
    s1 = max(st1["store_nbytes_per_device"].values())
    s4 = max(st4["store_nbytes_per_device"].values())
    c1 = max(st1["cache_nbytes_per_device"].values())
    c4 = max(st4["cache_nbytes_per_device"].values())
    emit("shard_store_bytes_per_device_1dev", 0.0, str(s1), hbm_bytes=s1)
    emit("shard_store_bytes_per_device_2x2", 0.0, str(s4), hbm_bytes=s4)
    emit("shard_cache_bytes_per_device_1dev", 0.0, str(c1), hbm_bytes=c1)
    emit("shard_cache_bytes_per_device_2x2", 0.0, str(c4), hbm_bytes=c4)
    assert s4 < s1 and c4 < c1, (s1, s4, c1, c4)
    emit("shard_serve_below_single_device", 0.0,
         f"store/dev={s4}<{s1}({s1 / s4:.1f}x),"
         f"cache/dev={c4}<{c1}({c1 / c4:.1f}x),"
         f"attn={st4['attn_backend']},tokens_equal={equal}")
    emit("shard_serve_1dev_interp", us1, "")
    emit("shard_serve_2x2_interp", us4,
         "forced-host-device wall clock: NOT pod performance")

    write_json(os.environ.get("BENCH_SHARD_JSON", "BENCH_shard.json"),
               start=json_start)


if __name__ == "__main__":
    _reexec_with_devices()
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    run()
