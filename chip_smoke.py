"""Serve the packed-MXSF path once on a TPU, end to end, and check it.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --chips 4         # single device vs a 2x2 mesh

One chip: qwen2.5-32b at its published widths (depth cut to fit 16 GB) with
random weights from ``--seed``, packed once into the MXSF store, served by
``ServeEngine(..., MXSF_INFER + packed KV cache, backend="pallas")``: a
few requests of a few hundred prompt tokens run through chunked prefill
and decode.  The two serving kernels are then checked at these widths
against their float32 oracles (``kernels/ref.py``) on a layer of the
store and on the served cache, and the engine's logits against the engine
built on ``backend="jnp"`` from the same store.

``--chips 4``: the same requests on the single-device engine (device 0)
and on a 2x2 ("data", "model") mesh of the first four devices; tokens must
agree, logits within a stated tolerance, and the store and cache bytes per
device must split.

Exits non-zero, printing no result, when JAX finds no TPU, when the
``repro`` package is not beside this script, or when any check fails.  The
last line of a passing run is ``{"ok": true, "device": {...}}``.  Wall
times printed on the way include compilation and are not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

MODEL = "qwen2.5-32b"
LAYERS = 4            # of 64: the f32 embedding, the packed store of 4
                      # layers and both engines' working sets fit in 16 GB
SLOTS, MAX_LEN, CHUNK = 4, 512, 128
REQUESTS, PROMPT_LEN, MAX_NEW = 6, (200, 320), 24
# jnp-vs-pallas: the kernel keeps softmax probabilities in f32 where the
# jnp path re-quantizes them (tests/test_attention_backend.py tolerance)
JNP_TOL = 1e-1
# kernels vs their float32 oracles, max|diff| / max|ref|.  Matmul: both
# sides decode the same MXSF operands exactly, only the f32 summation
# order differs.  Attention: the MXU may round the f32 probabilities to
# bf16 (2^-9 relative) in the P.V product.  A wrong scale, head or mask
# is an O(1) error.
MATMUL_TOL, ATTN_TOL = 1e-4, 1e-2
# single device vs 2x2 mesh: the model-axis psum of the row-parallel
# linears reorders f32 sums, which can move an activation across an MXSF
# rounding boundary at the next quantization (one code step, 2^-5 relative)
MESH_TOL = 1e-2


class Failed(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise Failed(what)


def log(*parts):
    print(*parts, flush=True)


class Recorder:
    """Sampler that keeps the first dispatch's logits and checks every
    dispatch's logits are finite."""

    def __init__(self, vocab: int):
        self.vocab = vocab
        self.first = None
        self.finite = True

    def __call__(self, logits):
        import jax.numpy as jnp
        import numpy as np
        if self.first is None:
            self.first = np.asarray(logits[:, : self.vocab], np.float32)
        self.finite = self.finite and bool(jnp.isfinite(logits).all())
        return jnp.argmax(logits, -1)


def build(seed: int):
    """Config, policy, packed params (init and pack in one program, so the
    f32 tree never sits on the chip whole) and the seeded requests."""
    import jax
    import numpy as np
    from repro.configs.base import get_config
    from repro.core.policy import MXSF_INFER
    from repro.models import model as M

    full = get_config(MODEL)
    cfg = full.replace(n_layers=LAYERS)
    log(f"model: {MODEL} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv} dh={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab} "
        f"(published widths); depth cut {full.n_layers} -> {cfg.n_layers} "
        f"layers to fit one 16 GB chip")
    policy = MXSF_INFER.replace(kv_cache_fmt="mxsf")
    log(f"policy: MXSF 1x{policy.block_1d} blocks, packed KV cache "
        f"({policy.kv_cache_fmt})")
    t0 = time.perf_counter()
    params = jax.jit(lambda k: M.pack_model_params(
        cfg, M.init_params(k, cfg), policy))(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(f"init+pack wall s (incl. compile): {time.perf_counter() - t0:.1f}")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=int(n)).tolist()
               for n in rng.integers(*PROMPT_LEN, size=REQUESTS)]
    log(f"requests: {REQUESTS}, prompt tokens {[len(p) for p in prompts]}, "
        f"max_new {MAX_NEW}; engine slots={SLOTS} max_len={MAX_LEN} "
        f"prefill_chunk={CHUNK}")
    return cfg, policy, params, prompts


def serve(name, cfg, params, policy, prompts, **engine_kw):
    """Build an engine, check its compiled steps, serve the requests."""
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine

    rec = Recorder(cfg.vocab)
    eng = ServeEngine(cfg, params, policy, slots=SLOTS, max_len=MAX_LEN,
                      prefill_chunk=CHUNK, sampler=rec, **engine_kw)
    log(f"[{name}] attn_backend={eng.attn_backend} "
        f"shard_fallback={eng.shard_fallback}")
    check(eng.shard_fallback is None, f"{name}: {eng.shard_fallback}")
    if engine_kw.get("backend") == "pallas":
        check(eng.attn_backend == "pallas-packed",
              f"{name}: attention left the packed-KV kernel")
        vec = jnp.zeros((SLOTS,), jnp.int32)
        steps = {
            "decode": (eng._decode, (eng.params, jnp.zeros((SLOTS, 1),
                                     jnp.int32), eng.cache, vec)),
            "prefill": (eng._prefill, (eng.params, jnp.zeros(
                (SLOTS, CHUNK), jnp.int32), eng.cache, vec, vec)),
        }
        for step, (fn, args) in steps.items():
            t0 = time.perf_counter()
            compiled = fn.lower(*args).compile()
            n = compiled.as_text().count("tpu_custom_call")
            mem = compiled.memory_analysis()
            log(f"[{name}] {step} step: compile s "
                f"{time.perf_counter() - t0:.1f}, tpu_custom_call count {n}, "
                f"temp bytes {mem.temp_size_in_bytes}")
            check(n > 0, f"{name}: compiled {step} step has no Pallas kernel")
    reqs = [eng.submit(p, MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    eng.run()
    st = eng.stats()
    log(f"[{name}] served {st['tokens_generated']} tokens in "
        f"{st['prefill_dispatches']} prefill + {st['decode_dispatches']} "
        f"decode dispatches; wall s (incl. compile, not a benchmark) "
        f"{time.perf_counter() - t0:.1f}")
    log(f"[{name}] store bytes {st['store_nbytes']['total']} "
        f"(packed {st['store_nbytes']['packed']}, values "
        f"{st['store_nbytes']['value']}); per device "
        f"{st['store_nbytes_per_device']}")
    log(f"[{name}] cache bytes per device {st['cache_nbytes_per_device']}")
    check(all(r.done and len(r.out) == MAX_NEW for r in reqs),
          f"{name}: a request did not finish")
    check(rec.finite, f"{name}: non-finite logits")
    return eng, rec, [r.out for r in reqs]


def compare(what, rec_a, rec_b, out_a, out_b, tol):
    import numpy as np
    a, b = rec_a.first, rec_b.first
    err = float(np.abs(a - b).max() / np.abs(b).max())
    top1 = bool((a.argmax(-1) == b.argmax(-1)).all())
    first = [o[0] for o in out_a] == [o[0] for o in out_b]
    same = sum(x == y for oa, ob in zip(out_a, out_b)
               for x, y in zip(oa, ob))
    srt = np.sort(b, axis=-1)
    margin = float((srt[:, -1] - srt[:, -2]).min() / np.abs(b).max())
    log(f"{what}: first-dispatch logits max|diff|/max|ref| = {err:.3e} "
        f"(tolerance {tol:g}), bitwise={bool((a == b).all())}, top-1 equal "
        f"{top1} (smallest reference top-1 margin {margin:.3e} of "
        f"max|ref|), first generated tokens equal {first}, tokens equal "
        f"{same}/{sum(len(o) for o in out_b)}")
    check(err <= tol, f"{what}: logit error {err:.3e} above {tol:g}")
    return first, same == sum(len(o) for o in out_b)


def kernel_parity(cfg, policy, eng, seed: int):
    """The fused matmul on layer 0's packed MLP weights and the packed-KV
    attention on the served layer-0 cache, against ``kernels/ref.py``
    under float32 matmul precision, at the decode and prefill shapes."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    def rel_err(got, want):
        got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want,
                                                               jnp.float32)
        return float(jnp.abs(got - want).max() / jnp.abs(want).max())

    key = jax.random.PRNGKey(seed + 1)
    ffn = eng.params["layers"]["sub0"]["ffn"]
    xblk, wblk = (1, policy.block_1d), (policy.block_1d, 1)
    for name in ("wg", "wd"):
        qw = ffn[name]
        codes, scales = qw.codes[0], qw.scale_e8m0[0]
        for m in (SLOTS, SLOTS * CHUNK):
            x = jax.random.normal(key, (m, codes.shape[0]), jnp.bfloat16)
            got = jax.jit(lambda x, c, s: ops.mxsf_fused_matmul(
                x, c, s, xblk, wblk))(x, codes, scales)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(lambda x, c, s: ref.mxsf_fused_matmul_ref(
                    x, c, s, xblk, wblk))(x, codes, scales)
            err = rel_err(got, want)
            log(f"kernel parity: fused matmul {name} ({m}x{codes.shape[0]} "
                f"@ {codes.shape[0]}x{codes.shape[1]}) max|diff|/max|ref| "
                f"= {err:.3e} (tolerance {MATMUL_TOL:g})")
            check(err <= MATMUL_TOL, f"fused matmul {name} M={m}: {err:.3e}")
    cache = {k: v[0, 0] for k, v in eng.cache.items()}
    W = cache["k_codes"].shape[2]
    bh = SLOTS * cfg.n_heads
    for s in (1, CHUNK):
        q = jax.random.normal(key, (bh, s, cfg.head_dim), jnp.bfloat16)
        kw = dict(causal=True, kv_len=W, q_offset=W - s)
        args = (q, cache["k_codes"], cache["k_scales"], cache["v_codes"],
                cache["v_scales"])
        got = jax.jit(lambda *a: ops.mxsf_attention(*a, **kw))(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: ref.mxsf_flash_attention_ref(
                *a, **kw))(*args)
        err = rel_err(got, want)
        log(f"kernel parity: packed-KV attention S={s} over the served "
            f"cache ({bh} rows, L={W}) max|diff|/max|ref| = {err:.3e} "
            f"(tolerance {ATTN_TOL:g})")
        check(err <= ATTN_TOL, f"attention S={s}: {err:.3e}")


def one_chip(seed: int):
    cfg, policy, params, prompts = build(seed)
    eng, rec_p, out_p = serve("pallas", cfg, params, policy, prompts,
                              backend="pallas")
    kernel_parity(cfg, policy, eng, seed)
    del eng
    _, rec_j, out_j = serve("jnp", cfg, params, policy, prompts,
                            backend="jnp")
    compare("pallas vs jnp", rec_p, rec_j, out_p, out_j, JNP_TOL)


def four_chips(seed: int):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 TPU devices, found "
          f"{len(devices)}")
    cfg, policy, params, prompts = build(seed)
    single, rec_1, out_1 = serve("1 device", cfg, params, policy, prompts,
                                 backend="pallas")
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("data", "model"))
    sharded, rec_4, out_4 = serve("2x2 mesh", cfg, params, policy, prompts,
                                  backend="pallas", mesh=mesh)
    _, all_equal = compare("2x2 mesh vs 1 device", rec_4, rec_1, out_4,
                           out_1, MESH_TOL)
    check(all_equal, "2x2 mesh vs 1 device: generated tokens differ")
    s1, s4 = single.stats(), sharded.stats()
    for kind in ("store", "cache"):
        one = max(s1[f"{kind}_nbytes_per_device"].values())
        most = max(s4[f"{kind}_nbytes_per_device"].values())
        log(f"{kind} bytes: 1 device {one}, largest 2x2 shard {most} "
            f"({one / most:.2f}x less per device)")
        check(most < one, f"{kind} bytes did not split over the mesh")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("FAIL: the repro package is not beside chip_smoke.py",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.runtime.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    import jax

    dev = jax.devices()[0]
    log(f"devices: {len(jax.devices())} x {dev.platform} ({dev.device_kind})")
    try:
        check(dev.platform == "tpu", f"no TPU: JAX found {dev.platform!r}")
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except Failed as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
