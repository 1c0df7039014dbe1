"""Pallas kernel benchmarks: interpret-mode timing + structural roofline.

Wall-clock on CPU interpret mode is NOT TPU performance; the structural
numbers (VMEM working set per tile, bytes moved, MXU-aligned dims, FLOPs)
are what transfer.  Emits both, as CSV log lines and as a machine-readable
``BENCH_kernel.json`` (override the path with ``$BENCH_KERNEL_JSON``) so
the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from . import common
from .common import emit, time_call, write_json


def run():
    json_start = len(common.ROWS_JSON)  # scope the JSON export to our rows
    rng = np.random.default_rng(0)
    M, K, N = 256, 512, 256
    x = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))

    us, (codes, scales) = time_call(
        lambda: ops.mxsf_quantize(x, block=(1, 32), tm=128, tk=256), iters=3)
    emit("kernel_mxsf_quantize_interp", us, f"shape={M}x{K}")
    cr, sr = ref.mxsf_quantize_ref(x, (1, 32))
    emit("kernel_mxsf_quantize_bitexact", 0.0,
         str(bool(jnp.array_equal(codes, cr) & jnp.array_equal(scales, sr))))

    xc, xs = ref.mxsf_quantize_ref(x, (1, 32))
    wc, ws = ref.mxsf_quantize_ref(w, (32, 1))
    us, y = time_call(lambda: ops.mxsf_matmul(xc, xs, wc, ws, tm=128, tn=128,
                                              tk=128), iters=3)
    yr = ref.mxsf_matmul_ref(xc, xs, wc, ws, (1, 32), (32, 1))
    rel = float(jnp.max(jnp.abs(y - yr)) / (jnp.max(jnp.abs(yr)) + 1e-9))
    emit("kernel_mxsf_matmul_interp", us, f"rel_err_vs_ref={rel:.2e}")

    # ---- fused vs unfused quantize->matmul (activation-side datapath) ----
    # Unfused: quantizer kernel writes x codes/scales to HBM, matmul kernel
    # reads them back.  Fused: one kernel reads raw x once and quantizes in
    # the matmul prologue — codes never touch HBM on the value path.
    wc, ws = ref.mxsf_quantize_ref(w, (32, 1))

    def unfused(xv):
        c, s = ops.mxsf_quantize(xv, block=(1, 32))
        return ops.mxsf_matmul(c, s, wc, ws, xblk=(1, 32), wblk=(32, 1))

    def fused(xv):
        return ops.mxsf_fused_matmul(xv, wc, ws, xblk=(1, 32), wblk=(32, 1))

    def n_dispatch(fn, *args):
        return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")

    d_unf, d_fus = n_dispatch(unfused, x), n_dispatch(fused, x)
    # HBM bytes on the activation side (w codes/scales identical in both):
    # unfused moves x f32 in + codes/scales out + codes/scales back in
    xbytes, cbytes, sbytes = M * K * 4, M * K, M * K // 32
    hbm_unf = xbytes + 2 * (cbytes + sbytes)
    hbm_fus = xbytes
    emit("kernel_unfused_qmm_dispatches", 0.0, str(d_unf))
    emit("kernel_fused_qmm_dispatches", 0.0, str(d_fus))
    emit("kernel_unfused_qmm_act_hbm_bytes", 0.0, str(hbm_unf))
    emit("kernel_fused_qmm_act_hbm_bytes", 0.0, str(hbm_fus))
    assert d_fus < d_unf and hbm_fus < hbm_unf
    emit("kernel_fused_below_unfused", 0.0,
         f"dispatches={d_fus}<{d_unf},hbm={hbm_fus}<{hbm_unf}"
         f"({100 * (1 - hbm_fus / hbm_unf):.0f}%_less_act_traffic)")
    us_u, yu = time_call(lambda: unfused(x), iters=3)
    us_f, yf = time_call(lambda: fused(x), iters=3)
    emit("kernel_unfused_qmm_interp", us_u, "")
    emit("kernel_fused_qmm_interp", us_f,
         f"bitexact_vs_unfused={bool(jnp.array_equal(yu, yf))}")

    # ---- pack-once weight store vs per-call requantize vs bf16 ----------
    # Steady-state decode serves every linear from resident MXSF codes
    # (core/packed_store.py).  The per-call path pays an extra quantizer
    # dispatch per matmul and streams the f32 master weights through HBM
    # plus a codes write+readback; the packed store reads 1-byte codes
    # only; the bf16 baseline reads 2-byte values.  Weight side only —
    # activation traffic is identical across the three.
    from repro.core import packed_store as PS
    from repro.core.mx_dot import mx_dot
    from repro.core.policy import QuantPolicy

    pol = QuantPolicy(block_mode="1d", block_1d=32, quantize_bwd=False,
                      backend="pallas")
    qw = PS.pack_leaf(w, pol)

    def percall(xv):
        return mx_dot(xv, w, pol)

    def packed(xv):
        return mx_dot(xv, qw, pol)

    d_pc, d_pk = n_dispatch(percall, x), n_dispatch(packed, x)
    wcodes = K * N + K * N // 32              # codes + E8M0 scale bytes
    hbm_pc = K * N * 4 + 2 * wcodes           # f32 read + codes write+read
    hbm_pk = wcodes                           # resident codes read
    hbm_bf16 = K * N * 2                      # bf16-resident baseline
    emit("kernel_weight_percall_dispatches", 0.0, str(d_pc), dispatches=d_pc)
    emit("kernel_weight_packed_dispatches", 0.0, str(d_pk), dispatches=d_pk)
    emit("kernel_weight_percall_hbm_bytes_per_tok", 0.0, str(hbm_pc),
         hbm_bytes=hbm_pc)
    emit("kernel_weight_packed_hbm_bytes_per_tok", 0.0, str(hbm_pk),
         hbm_bytes=hbm_pk)
    emit("kernel_weight_bf16_hbm_bytes_per_tok", 0.0, str(hbm_bf16),
         hbm_bytes=hbm_bf16)
    assert d_pk < d_pc and hbm_pk < hbm_pc and hbm_pk < hbm_bf16
    us_pc, y_pc = time_call(lambda: percall(x), iters=3)
    us_pk, y_pk = time_call(lambda: packed(x), iters=3)
    emit("kernel_weight_percall_interp", us_pc, "")
    emit("kernel_weight_packed_interp", us_pk,
         f"bitexact_vs_percall={bool(jnp.array_equal(y_pc, y_pk))}")
    emit("kernel_weight_packed_below_percall", 0.0,
         f"dispatches={d_pk}<{d_pc},hbm={hbm_pk}<{hbm_pc}"
         f"({hbm_pc / hbm_pk:.1f}x_less_weight_traffic_per_call,"
         f"{hbm_bf16 / hbm_pk:.1f}x_below_bf16_resident)",
         dispatches=d_pk, hbm_bytes=hbm_pk)

    # ---- packed->packed requantize vs dequantize->quantize roundtrip ----
    # The Fig. 4a backward re-blocks x/w along the transposed contraction
    # dim.  The requantize kernel keeps codes uint8 end-to-end; the old
    # path materialized the full f32 tensor in HBM between a jnp dequantize
    # graph and the quantizer dispatch (1 pallas dispatch either way — the
    # win is the HBM traffic, tracked in the *_hbm_bytes rows below).
    from repro.core import blocking as B

    qt = B.quantize(w, "mxsf", (32, 1))

    def requant_kernel(c, s):
        return ops.mxsf_requantize(c, s, (32, 1), (1, 32))

    def requant_roundtrip(c, s):
        v = B.dequantize(B.QuantizedTensor(c, s, "mxsf", (32, 1),
                                           (K, N), "float32"))
        return ops.mxsf_quantize(v, block=(1, 32))

    d_rq = n_dispatch(requant_kernel, qt.codes, qt.scale_e8m0)
    d_rt = n_dispatch(requant_roundtrip, qt.codes, qt.scale_e8m0)
    hbm_rq = 2 * wcodes                       # codes in + codes out
    hbm_rt = wcodes + 2 * K * N * 4 + wcodes  # + f32 write & read between
    emit("kernel_requant_packed_dispatches", 0.0, str(d_rq), dispatches=d_rq)
    emit("kernel_requant_roundtrip_dispatches", 0.0, str(d_rt),
         dispatches=d_rt)
    emit("kernel_requant_packed_hbm_bytes", 0.0, str(hbm_rq),
         hbm_bytes=hbm_rq)
    emit("kernel_requant_roundtrip_hbm_bytes", 0.0, str(hbm_rt),
         hbm_bytes=hbm_rt)
    us_rq, (rc, rs) = time_call(
        lambda: requant_kernel(qt.codes, qt.scale_e8m0), iters=3)
    us_rt, (tc, ts) = time_call(
        lambda: requant_roundtrip(qt.codes, qt.scale_e8m0), iters=3)
    bitexact = bool(jnp.array_equal(rc, tc) & jnp.array_equal(rs, ts))
    emit("kernel_requant_packed_interp", us_rq,
         f"bitexact_vs_roundtrip={bitexact}")
    emit("kernel_requant_roundtrip_interp", us_rt, "")
    assert bitexact and hbm_rq < hbm_rt
    emit("kernel_requant_below_roundtrip", 0.0,
         f"hbm={hbm_rq}<{hbm_rt}({hbm_rt / hbm_rq:.1f}x_less_traffic)",
         dispatches=d_rq, hbm_bytes=hbm_rq)

    # ---- packed-KV decode attention: flash kernel vs dequantize+einsum ----
    # Serving hot path (models/blocks.py::_attend_packed): the kernel reads
    # the cache as 1-byte MXSF codes and decodes in VMEM; the jnp path
    # dequantizes the whole cache to f32 values and materializes the
    # (BH x L) score/probs rows through HBM.
    BKV, L, dh, g = 2, 512, 64, 2
    BH = BKV * g
    q = jnp.asarray(rng.standard_normal((BH, 1, dh)).astype(np.float32))
    from repro.core import blocking as B

    kv = rng.standard_normal((2, BKV, L, dh)).astype(np.float32)
    qk = B.quantize(jnp.asarray(kv[0]), "mxsf", (dh,))
    qv = B.quantize(jnp.asarray(kv[1]), "mxsf", (dh,))
    kc, ks = qk.codes, qk.scale_e8m0[..., 0]
    vc, vs = qv.codes, qv.scale_e8m0[..., 0]

    def attn_kernel(qv_):
        return ops.mxsf_attention(qv_, kc, ks, vc, vs, causal=False,
                                  kv_len=L, cq=1, ck=256)

    def attn_dequant(qv_):
        return ref.mxsf_flash_attention_ref(qv_, kc, ks, vc, vs,
                                            causal=False, kv_len=L)

    d_ker = n_dispatch(attn_kernel, q)
    d_deq = n_dispatch(attn_dequant, q)
    # HBM bytes per decoded token, cache side (q/out negligible at S=1):
    #   kernel : K+V codes at 1 B/elem + one E8M0 scale byte per (pos, head)
    #   dequant: same code reads + f32 value write + read-back into the
    #            einsums + (BH x L) f32 scores AND probs written + read
    cache_codes = 2 * BKV * L * dh
    cache_scales = 2 * BKV * L
    hbm_ker = cache_codes + cache_scales
    hbm_deq = (cache_codes + cache_scales + 2 * 2 * BKV * L * dh * 4
               + 2 * 2 * BH * L * 4)
    emit("kernel_attn_packed_dispatches", 0.0, str(d_ker))
    emit("kernel_attn_dequant_dispatches", 0.0, str(d_deq))
    emit("kernel_attn_packed_hbm_bytes_per_tok", 0.0, str(hbm_ker))
    emit("kernel_attn_dequant_hbm_bytes_per_tok", 0.0, str(hbm_deq))
    assert d_ker == 1 and d_deq == 0 and hbm_ker < hbm_deq
    us_k, yk = time_call(lambda: attn_kernel(q), iters=3)
    us_d, yd = time_call(lambda: attn_dequant(q), iters=3)
    rel = float(jnp.max(jnp.abs(yk - yd)) / (jnp.max(jnp.abs(yd)) + 1e-9))
    emit("kernel_attn_packed_interp", us_k, f"rel_err_vs_dequant={rel:.2e}")
    emit("kernel_attn_dequant_interp", us_d, "")
    emit("kernel_attn_packed_below_dequant", 0.0,
         f"1_fused_dispatch,hbm={hbm_ker}<{hbm_deq}"
         f"({hbm_deq / hbm_ker:.1f}x_less_cache_traffic_per_decoded_token)")

    # ---- chunked prefill: ceil(P/C) prompt dispatches vs P ---------------
    # The serving engine's prompt phase (serve/engine.py): token-by-token
    # prefill pays one full model dispatch per prompt token — every weight
    # byte streams from HBM P times before the first generated token.
    # Chunked prefill (prefill_step, C tokens/dispatch) reads the resident
    # packed store once per CHUNK, so weight-side HBM traffic per prompt
    # token drops by ~C (and dispatch latency overhead with it).
    from repro.configs.base import get_config
    from repro.core.policy import MXSF_INFER
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg = get_config("qwen2.5-32b").reduced().replace(compute_dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pol_kv = MXSF_INFER.replace(block_1d=16, kv_cache_fmt="mxsf")
    P, C, max_new = 12, 4, 2
    prompt = list(rng.integers(0, cfg.vocab, size=P))

    def serve(chunk):
        eng = ServeEngine(cfg, params, pol_kv, slots=2, max_len=16,
                          prefill_chunk=chunk)
        req = eng.submit(prompt, max_new)
        # warmup=0: an engine drains on its first run() — a warmed-up call
        # would time an empty queue (includes jit compile; informational)
        us, _ = time_call(lambda: eng.run(), iters=1, warmup=0)
        return eng, req, us

    eng_t, req_t, us_t = serve(1)
    eng_c, req_c, us_c = serve(C)
    d_tok, d_chk = eng_t.prefill_dispatches, eng_c.prefill_dispatches
    # weight-side HBM bytes per prompt token: the packed store streams once
    # per prefill dispatch (activation/cache traffic is identical per token)
    store = eng_t.store_nbytes["total"]
    hbm_tok = store * d_tok // P
    hbm_chk = store * d_chk // P
    emit("kernel_prefill_tokstep_dispatches", 0.0, f"P={P}",
         dispatches=d_tok)
    emit("kernel_prefill_chunked_dispatches", 0.0, f"P={P},C={C}",
         dispatches=d_chk)
    emit("kernel_prefill_tokstep_weight_hbm_bytes_per_prompt_tok", 0.0,
         str(hbm_tok), hbm_bytes=hbm_tok)
    emit("kernel_prefill_chunked_weight_hbm_bytes_per_prompt_tok", 0.0,
         str(hbm_chk), hbm_bytes=hbm_chk)
    assert d_tok == P and d_chk == -(-P // C) and hbm_chk < hbm_tok
    assert req_c.out == req_t.out  # token-for-token across schedules
    emit("kernel_prefill_tokstep_interp", us_t, "")
    emit("kernel_prefill_chunked_interp", us_c,
         f"tokens_equal_tokstep={req_c.out == req_t.out}")
    emit("kernel_prefill_chunked_below_tokstep", 0.0,
         f"dispatches={d_chk}<{d_tok},weight_hbm/tok={hbm_chk}<{hbm_tok}"
         f"({hbm_tok / hbm_chk:.1f}x_less_weight_traffic_per_prompt_token)",
         dispatches=d_chk, hbm_bytes=hbm_chk)

    # prefill_chunk="auto" resolution (serve/engine.auto_prefill_chunk):
    # what the engine picks when no explicit C is given — a shape
    # heuristic (fill one fused-matmul M tile across the slot batch, drain
    # a full prompt in >= 4 chunks)
    from repro.serve.engine import auto_prefill_chunk
    for ml, sl in ((256, 4), (4096, 16)):
        ac = auto_prefill_chunk(ml, sl)
        emit(f"kernel_prefill_auto_chunk_maxlen{ml}_slots{sl}", 0.0,
             f"C={ac}", chunk=ac)

    # structural roofline of the dequant-matmul (TPU v5e targets).
    # With a TM x TN output tile resident in VMEM and K streamed, HBM bytes
    # per tile ~ (TM + TN) * K of 1-byte codes (+ scales/32), so
    #   AI ~ 2*TM*TN / (TM + TN)  flops/byte.
    # The v5e ridge is 197e12/819e9 ~ 241 -> 128x128 tiles (AI 124) leave the
    # kernel memory-bound even on packed operands; 256x256 tiles (AI 248)
    # cross the ridge. That tiling is the §Perf kernel recommendation; the
    # same matmul on bf16 operands would need 512x512 tiles to get there —
    # the 8-bit format HALVES the tile size needed to reach compute-bound.
    for t in (128, 256):
        vmem = 2 * (t * 256) * 1 + (t * t) * 4  # two code slabs + f32 acc
        ai = 2 * t * t / (2 * t * (1 + 1 / 32))
        emit(f"kernel_matmul_tile{t}_vmem_bytes", 0.0, str(vmem))
        emit(f"kernel_matmul_tile{t}_arith_intensity", 0.0,
             f"{ai:.0f}flops/byte(vs_v5e_ridge={197e12/819e9:.0f})")

    write_json(os.environ.get("BENCH_KERNEL_JSON", "BENCH_kernel.json"),
               start=json_start)


if __name__ == "__main__":
    run()
