"""Serving demo: continuous batching through ``ServeEngine`` with the MXSF
inference policy (1x64 blocks), a packed KV cache, the pack-once weight
store (weights quantized ONCE to resident MXSF codes) — and, with
``--mesh``, the whole stack sharded over a data x model device mesh (slot
batch over "data", kv heads + weight shards over "model"; token-for-token
identical to the single-host engine).

    PYTHONPATH=src python examples/serve_decode.py [--arch h2o-danube-1.8b-reduced]
    # sharded (forced host devices stand in for a real pod):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/serve_decode.py --mesh 2x2
"""
import argparse
import time

import jax

from repro.configs.base import get_config
from repro.core.policy import MXSF_INFER
from repro.launch.mesh import make_test_mesh
from repro.models import model as M
from repro.runtime.compile_cache import use_compile_cache
from repro.serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b-reduced")
    ap.add_argument("--batch", type=int, default=4, help="engine slots")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prefill-chunk", default="auto",
                    help="int or 'auto' (heuristic from max_len/slots + "
                    "measured BENCH_kernel.json prefill rows)")
    ap.add_argument("--mesh", default=None,
                    help="DxM mesh, e.g. 2x2 (axes data x model; clamps "
                    "to the available devices)")
    ap.add_argument("--backend", default="pallas", choices=("jnp", "pallas"),
                    help="mx_dot datapath; pallas also engages the "
                    "packed-KV flash-attention kernel where eligible")
    ap.add_argument("--no-pack", action="store_true",
                    help="keep full-precision weights (re-quantize per call)")
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    policy = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    mesh = None
    if args.mesh:
        d, m = (int(v) for v in args.mesh.lower().split("x"))
        mesh = make_test_mesh(d, m)
        print(f"mesh: {dict(mesh.shape)} over {len(mesh.devices.ravel())} "
              "devices")
    chunk = (args.prefill_chunk if args.prefill_chunk == "auto"
             else int(args.prefill_chunk))
    max_len = args.prompt_len + args.gen
    eng = ServeEngine(cfg, params, policy, slots=args.batch, max_len=max_len,
                      pack_weights=not args.no_pack, prefill_chunk=chunk,
                      backend=args.backend, mesh=mesh)
    nb = eng.store_nbytes
    print(f"weight store: {nb['packed'] / 1e6:.2f} MB packed "
          f"(+{nb['value'] / 1e6:.2f} MB value leaves) vs "
          f"{nb['value_f32'] / 1e6:.2f} MB f32 "
          f"({nb['value_f32'] / max(nb['packed'], 1):.1f}x smaller); "
          f"attn={eng.attn_backend} prefill_chunk={eng.prefill_chunk}")

    rng = jax.random.PRNGKey(1)
    for i in range(args.requests):
        rng, k = jax.random.split(rng)
        prompt = jax.random.randint(k, (args.prompt_len,), 0,
                                    cfg.vocab).tolist()
        eng.submit(prompt, args.gen)
    print(f"serving {args.requests} x ({args.prompt_len} prompt + "
          f"{args.gen} gen) on {args.batch} slots ...")
    t0 = time.time()
    finished = eng.run()
    dt = time.time() - t0

    st = eng.stats()
    tps = st["tokens_generated"] / dt
    print(f"generated {st['tokens_generated']} tokens in {dt:.2f}s "
          f"({tps:.1f} tok/s interpret-mode MX) — "
          f"{st['prefill_dispatches']} prefill + "
          f"{st['decode_dispatches']} decode dispatches over "
          f"{st['ticks']} ticks, occupancy {st['occupancy']:.2f}")
    for dev, nbytes in sorted(st["store_nbytes_per_device"].items()):
        cache_b = st["cache_nbytes_per_device"].get(dev, 0)
        print(f"  {dev}: store {nbytes / 1e6:.2f} MB, "
              f"cache {cache_b / 1e6:.2f} MB")
    if st["shard_fallback"]:
        print("shard fallback:", st["shard_fallback"])
    print("sample:", finished[0].out[:16])


if __name__ == "__main__":
    main()
