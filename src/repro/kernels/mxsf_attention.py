"""Pallas TPU kernel: flash attention over an MXSF-packed KV cache.

The serving-side §Perf result (EXPERIMENTS.md cell C) stores the KV cache as
MXSF codes; this kernel consumes the codes *directly* — decode happens in
VMEM per tile, the S x L score matrix never exists, and HBM reads of the
cache are 1 byte/element (+1/dh scale). This is the SAFE-MAC dataflow
(decode feeding the MAC array) mapped onto MXU tiles.

Both serving phases run through it: S=1 decode steps and S=C prefill
chunks (serve/engine.py chunked prefill) — the q-side grid tiles S into
Cq-row query blocks, and the same ``q_offset``-anchored causal mask covers
chunk-internal causality (query at absolute position p sees keys <= p,
including the chunk rows written just before it).

Layout:
  q        : (BH, S, dh)      bf16/f32 — one row per (batch x q-head)
  k/v codes: (B, KV, L, dh)   uint8    — the packed KV cache as stored
                                         (models/decoding.py), position-major
                                         per (batch, kv-head)
  k/v scale: (B, KV, L)       uint8    — E8M0 per (position, head) row,
                                         positions on the lanes
GQA: q row bh = b*h + head reads kv head ``head // (h // KV)`` of batch b.
The (BKV, L, dh) / (BKV, L) row layout is the same tensor with KV = 1.

Per-row dynamic scalars (``(BH,)`` int32, scalar-prefetched into SMEM — NOT
static, so a cache that grows by one position per decode step reuses one
compilation):
  kv_len   : number of valid cache positions for this row (rest masked)
  q_offset : absolute position of this row's first query; the causal and
             window masks compare ``kpos`` against ``q_offset + iq`` so a
             single decoded token at position p passes ``q_offset=p, S=1``
  window   : SWA width (``kpos > qpos_abs - window``); ``NO_WINDOW`` = off

Grid (BH, S/Cq, L/Ck), L innermost; VMEM scratch carries the online-softmax
state (m, l, acc) across the L loop.  The K/V scale bytes of a chunk arrive
as one lane-dense (KV, Ck) block; the kernel picks its head's row and
applies the powers of two to the score and probability columns, which is
exact (a power-of-two factor commutes with every f32 product).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import decode_mxsf, exp2i

SCALE_BIAS = 127
NEG_INF = -1e30
NO_WINDOW = 1 << 30  # matches models/transformer.py sentinel

# traces of the inner jitted kernel wrapper == XLA compilations; tests
# assert a growing-cache decode adds exactly one (see trace_count())
_TRACE_COUNT = 0


def trace_count() -> int:
    """Number of times the kernel wrapper has been (re)traced/compiled."""
    return _TRACE_COUNT


def _head_row(s_ref, hk):
    """Row ``hk`` of a (1, KV, Ck) scale block as (1, Ck) int32 exponents."""
    s = s_ref[0].astype(jnp.int32)                        # (KV, Ck)
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    row = jnp.sum(jnp.where(rows == hk, s, 0), axis=0, keepdims=True)
    return row - SCALE_BIAS


def _attn_kernel(kvl_ref, off_ref, win_ref, q_ref, kc_ref, ks_ref, vc_ref,
                 vs_ref, o_ref, m_ref, l_ref, acc_ref, *, nk: int, cq: int,
                 ck: int, dh: int, h: int, g: int, causal: bool):
    b = pl.program_id(0)
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hk = (b % h) // g
    q = q_ref[0].astype(jnp.float32)                      # (Cq, dh)
    k = decode_mxsf(kc_ref[0, 0])                         # (Ck, dh)
    v = decode_mxsf(vc_ref[0, 0])
    kscale = exp2i(_head_row(ks_ref, hk))                 # (1, Ck)
    vscale = exp2i(_head_row(vs_ref, hk))

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * kscale / math.sqrt(dh)                        # (Cq, Ck)
    kv_len = kvl_ref[b]
    off = off_ref[b]
    win = win_ref[b]
    qpos = off + iq * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
    kpos = jk * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
    mask = kpos < kv_len
    if causal:
        mask &= kpos <= qpos
    mask &= kpos > qpos - win
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (Cq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # zero p under the mask: a fully-masked tile leaves m_new at NEG_INF,
    # where exp(s - m_new) = exp(0) = 1 would pull masked V rows into acc/l
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p * vscale, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(jk == nk - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "cq", "ck",
                                             "interpret"))
def _flash_attention_jit(kv_len, q_offset, window, q, k_codes, k_scales,
                         v_codes, v_scales, *, causal, cq, ck, interpret):
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    BH, S, dh = q.shape
    B, KV, L, _ = k_codes.shape
    h = BH // B
    g = h // KV
    nk = L // ck

    def code_map(b, i, j, *_):
        return (b // h, (b % h) // g, j, 0)

    def scale_map(b, i, j, *_):
        return (b // h, 0, j)

    def q_map(b, i, j, *_):
        return (b, i, 0)

    kernel = functools.partial(_attn_kernel, nk=nk, cq=cq, ck=ck, dh=dh,
                               h=h, g=g, causal=causal)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # kv_len, q_offset, window
        grid=(BH, S // cq, nk),
        in_specs=[
            pl.BlockSpec((1, cq, dh), q_map),
            pl.BlockSpec((1, 1, ck, dh), code_map),
            pl.BlockSpec((1, KV, ck), scale_map),
            pl.BlockSpec((1, 1, ck, dh), code_map),
            pl.BlockSpec((1, KV, ck), scale_map),
        ],
        out_specs=pl.BlockSpec((1, cq, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((cq, 1), jnp.float32),     # running max
            pltpu.VMEM((cq, 1), jnp.float32),     # running denom
            pltpu.VMEM((cq, dh), jnp.float32),    # accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), q.dtype),
        interpret=interpret,
    )(kv_len, q_offset, window, q, k_codes, k_scales, v_codes, v_scales)


def per_row_scalar(val, default, BH: int):
    """Normalize None / python int / scalar / (BH,) array -> (BH,) i32.

    Negative entries (python or traced, scalar or per-row) mean "use the
    default" — the kv_len=-1 = "all of L" convention.  Shared by the kernel
    wrapper, ops.mxsf_attention and the jnp oracle so the contract can't
    drift between them.
    """
    if val is None:
        return jnp.full((BH,), default, jnp.int32)
    val = jnp.asarray(val, jnp.int32)
    val = jnp.where(val < 0, default, val)
    return jnp.broadcast_to(val, (BH,))


def mxsf_flash_attention(q, k_codes, k_scales, v_codes, v_scales, *,
                         causal: bool = True, cq: int = 256, ck: int = 256,
                         kv_len=None, q_offset=None, window=None,
                         interpret: bool = False):
    """Flash attention over MXSF-packed K/V.

    q: (BH, S, dh).  Two K/V layouts, told apart by ndim:
      * cache layout: codes (B, KV, L, dh), scales (B, KV, L) — the KV cache
        pytree as stored by models/decoding.py, fed as-is (no copy);
      * row layout  : codes (BKV, L, dh), scales (BKV, L) — the same with
        KV = 1 (a free reshape).
    ``kv_len``/``q_offset``/``window`` are *dynamic* per-row scalars (python
    int, scalar, or (BH,) array; negative ``kv_len`` = all of L) — a
    growing decode cache does NOT recompile the kernel.
    Returns (BH, S, dh) in q.dtype.
    """
    if k_codes.ndim == 3:
        k_codes, v_codes = k_codes[:, None], v_codes[:, None]
        k_scales, v_scales = k_scales[:, None], v_scales[:, None]
    BH, S, dh = q.shape
    B, KV, L, dh2 = k_codes.shape
    assert dh == dh2 and BH % B == 0 and (BH // B) % KV == 0, \
        (q.shape, k_codes.shape)
    assert k_scales.shape == (B, KV, L), (k_scales.shape, k_codes.shape)
    cq = min(cq, S)
    ck = min(ck, L)
    assert S % cq == 0 and L % ck == 0, (S, cq, L, ck)
    kvl = jnp.minimum(per_row_scalar(kv_len, L, BH), L)
    off = per_row_scalar(q_offset, 0, BH)
    win = per_row_scalar(window, NO_WINDOW, BH)
    return _flash_attention_jit(kvl, off, win, q, k_codes, k_scales, v_codes,
                                v_scales, causal=causal, cq=cq, ck=ck,
                                interpret=interpret)
