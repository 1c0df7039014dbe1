"""Time per grid step of the fused MXSF matmul at the serving benchmark's
shapes, on whatever device JAX finds (the TPU on a chip host).

Prints one ``KB|`` line per shape: the median wall time of a call, that
time over the grid's steps (tiles 256 x 256 x 512, as ``ops`` picks them
at these shapes), the converted activation tiles over grid steps where the
tree counts them (``ops.fused_lhs_converts``), and checksums of every
output, so two checkouts can be compared bit for bit and step for step:

    PYTHONPATH=src python -m benchmarks.fused_matmul_steps
    PYTHONPATH=src python -m benchmarks.fused_matmul_steps --src <other>/src

Wall time includes the host's dispatch of each call, so a step time read
from a call of a few hundred steps (a decode shape) is an upper bound.
"""
from __future__ import annotations

import argparse
import statistics
import sys
import time

SERVE = ((1, 64), (64, 1))
# (name, m, k, n, xblk, wblk, emit_codes, quantize_lhs)
SHAPES = [("qwen head prefill", 2048, 5120, 153600, *SERVE, False, True),
          ("qwen wg prefill", 2048, 5120, 27648, *SERVE, False, True),
          ("qwen wd prefill", 2048, 27648, 5120, *SERVE, False, True),
          ("qwen wq prefill", 2048, 5120, 5120, *SERVE, False, True),
          ("qwen head decode", 8, 5120, 153600, *SERVE, False, True),
          ("qwen wg decode", 8, 5120, 27648, *SERVE, False, True),
          ("danube wd prefill", 4096, 6912, 2560, *SERVE, False, True),
          ("danube wg prefill", 4096, 2560, 6912, *SERVE, False, True),
          ("danube wg decode", 16, 2560, 6912, *SERVE, False, True),
          ("train 2d emit", 512, 2048, 1024, (8, 8), (8, 8), True, True),
          ("train 1d emit", 512, 2048, 1024, (1, 32), (32, 1), True, True),
          ("train raw lhs", 512, 2048, 1024, (1, 32), (32, 1), False, False)]
TINY = [("tiny", 16, 256, 1024, *SERVE, False, True),
        ("tiny emit", 16, 256, 1024, (8, 8), (8, 8), True, True)]


def checksum(a):
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(
        a.astype(jnp.uint32) if a.dtype == jnp.uint8
        else a.astype(jnp.float32), jnp.uint32)
    idx = (jax.lax.broadcasted_iota(jnp.uint32, bits.shape, 0) * 7919
           + jax.lax.broadcasted_iota(jnp.uint32, bits.shape, bits.ndim - 1))
    return int(jnp.sum(bits)), int(jnp.sum(bits * (idx % 65521 + 1)))


def timed(f, *args, reps: int) -> float:
    import jax
    jax.block_until_ready(f(*args))
    ts = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t)
    return statistics.median(ts)


def run(shapes, reps: int = 5):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    counter = getattr(ops, "fused_lhs_converts", None)
    for name, m, k, n, xb, wb, emit, qlhs in shapes:
        k1, k2 = jax.random.split(jax.random.PRNGKey(m + k + n))
        # wide-range activations, so every scale of the converter is used
        x = jax.random.normal(k1, (m, k), jnp.float32)
        x = (x * jnp.exp(2 * jax.random.normal(k2, (m, k)))).astype(
            jnp.bfloat16)
        wc, ws = jax.jit(lambda w: ops.mxsf_quantize(w, block=wb))(
            jax.random.normal(k2, (k, n), jnp.float32))
        f = jax.jit(lambda x, c, s: ops.mxsf_fused_matmul(
            x, c, s, xb, wb, emit_codes=emit, quantize_lhs=qlhs))
        c0 = counter() if counter else None
        out = f(x, wc, ws)
        counted = (f"{counter()[0] - c0[0]}/{counter()[1] - c0[1]}"
                   if counter else "-")
        t = timed(f, x, wc, ws, reps=reps)
        steps = -(-m // min(256, m)) * -(-n // 256) * -(-k // 512)
        sums = " ".join(f"{a}/{b}" for a, b in map(
            checksum, out if isinstance(out, tuple) else (out,)))
        print(f"KB|{name}|{m}x{k}x{n}|t_ms={t * 1e3:.4f}|"
              f"us_per_step={t / steps * 1e6:.4f}|steps={steps}|"
              f"counted={counted}|sums={sums}", flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--src", help="the src/ of another checkout to time")
    ap.add_argument("--tiny", action="store_true",
                    help="two small shapes (a check on the CPU)")
    a = ap.parse_args(argv)
    if a.src:
        sys.path.insert(0, a.src)
    run(TINY if a.tiny else SHAPES)


if __name__ == "__main__":
    main()
