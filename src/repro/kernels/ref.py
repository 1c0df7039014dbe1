"""Pure-jnp oracles for the Pallas kernels (tested bit-exact vs interpret)."""
from __future__ import annotations

import jax.numpy as jnp

from ..core import blocking as B


def mxsf_quantize_ref(x, block=(1, 32)):
    """Oracle for mxsf_quantize_pallas: packed codes + E8M0 scales."""
    qt = B.quantize(x, "mxsf", tuple(block))
    return qt.codes, qt.scale_e8m0


def mxsf_requantize_ref(codes, scales, from_block=(32, 1), to_block=(1, 32)):
    """Oracle for mxsf_requantize_pallas: dequantize the code grid (treated
    as the value domain), re-quantize under the new block orientation."""
    m, k = codes.shape
    qt = B.QuantizedTensor(codes, scales, "mxsf", tuple(from_block), (m, k),
                           "float32")
    out = B.quantize(B.dequantize(qt), "mxsf", tuple(to_block))
    return out.codes, out.scale_e8m0


def mxsf_matmul_ref(x_codes, x_scales, w_codes, w_scales, xblk, wblk):
    """Oracle for mxsf_matmul_pallas: dequantize both operands, f32 matmul."""
    m, k = x_codes.shape
    _, n = w_codes.shape
    qx = B.QuantizedTensor(x_codes, x_scales, "mxsf", tuple(xblk), (m, k), "float32")
    qw = B.QuantizedTensor(w_codes, w_scales, "mxsf", tuple(wblk), (k, n), "float32")
    return jnp.matmul(B.dequantize(qx), B.dequantize(qw),
                      preferred_element_type=jnp.float32)


def mxsf_fused_matmul_ref(x, w_codes, w_scales, xblk=(1, 32), wblk=(32, 1),
                          quantize_lhs=True):
    """Oracle for mxsf_fused_matmul_pallas: qdq the raw LHS (bit-identical
    to packed encode/decode), dequantize the packed RHS, f32 matmul."""
    m, k = x.shape
    kw, n = w_codes.shape
    if kw > k:
        x = jnp.pad(x, ((0, 0), (0, kw - k)))
    xv = x.astype(jnp.float32)
    if quantize_lhs:
        xv = B.qdq(xv, "mxsf", tuple(xblk))
    qw = B.QuantizedTensor(w_codes, w_scales, "mxsf", tuple(wblk), (kw, n),
                           "float32")
    return jnp.matmul(xv, B.dequantize(qw),
                      preferred_element_type=jnp.float32)


def mxsf_qdq_matmul_ref(x, w, xblk=(1, 32), wblk=(32, 1)):
    """End-to-end oracle: quantize f32 inputs then matmul."""
    xq = B.qdq(x, "mxsf", tuple(xblk))
    wq = B.qdq(w, "mxsf", tuple(wblk))
    return jnp.matmul(xq, wq, preferred_element_type=jnp.float32)


def mxsf_flash_attention_ref(q, k_codes, k_scales, v_codes, v_scales,
                             causal=True, kv_len=None, q_offset=None,
                             window=None):
    """Oracle: dequantize the packed cache, plain softmax attention.

    ``kv_len``/``q_offset``/``window`` mirror the kernel's per-row dynamic
    scalars (python int, scalar, or (BH,) array); fully-masked rows return 0
    (not a uniform average) — same contract as the kernel's masked-tile fix.
    Accepts both kernel operand layouts: row layout (BKV, L, dh)/(BKV, L)
    and the KV-cache pytree layout (B, KV, L, dh)/(B, KV, L), merged into
    rows exactly like ``models/decoding.py::kv_cache_rows`` so prefill/
    decode tests can feed the cache buffers straight to the oracle.
    """
    from .mxsf_attention import NO_WINDOW, per_row_scalar
    BH, S, dh = q.shape
    if k_codes.ndim == 4:  # cache layout -> (batch x kv-head) rows
        Bc, KV, L, _ = k_codes.shape
        k_codes, v_codes = (c.reshape(Bc * KV, L, dh)
                            for c in (k_codes, v_codes))
        k_scales, v_scales = (s.reshape(Bc * KV, L)
                              for s in (k_scales, v_scales))
    BKV, L, _ = k_codes.shape
    g = BH // BKV
    kvl = jnp.minimum(per_row_scalar(kv_len, L, BH), L)
    off = per_row_scalar(q_offset, 0, BH)
    win = per_row_scalar(window, NO_WINDOW, BH)
    k = B.dequantize(B.QuantizedTensor(k_codes, k_scales[..., None], "mxsf",
                                       (dh,), k_codes.shape, "float32"))
    v = B.dequantize(B.QuantizedTensor(v_codes, v_scales[..., None], "mxsf",
                                       (dh,), v_codes.shape, "float32"))
    k = jnp.repeat(k, g, axis=0)
    v = jnp.repeat(v, g, axis=0)
    s = jnp.einsum("bsd,bld->bsl", q.astype(jnp.float32), k) / (dh ** 0.5)
    qpos = off[:, None, None] + jnp.arange(S)[None, :, None]  # (BH, S, 1)
    kpos = jnp.arange(L)[None, None, :]
    mask = kpos < kvl[:, None, None]
    if causal:
        mask = mask & (kpos <= qpos)
    mask = mask & (kpos > qpos - win[:, None, None])
    s = jnp.where(mask, s, -1e30)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bsl,bld->bsd", p, v).astype(q.dtype)
