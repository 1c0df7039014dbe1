"""Jit'd public wrappers around the Pallas kernels.

Handles padding to tile multiples, the scale-layout conversion at the
kernel boundary (``common.to_kernel_scales``) and backend dispatch: on TPU
the kernels run compiled; on the CPU they run in ``interpret=True`` mode
(Python emulation of the kernel body), which is how the tests validate
them.  Any other platform is an error, never a silent fallback.

Compiled tiles follow the TPU's block rule (the last two dims of every
block a multiple of (8, 128) or the whole array); interpret mode keeps the
caller's tiles, so small-shape tests still cover multi-tile grids.

Padding is always with zeros: zero elements never raise a block amax, zero
codes decode to exactly 0.0, and adding 0.0 terms to an f32 accumulation is
the identity — so the padded kernels match the block-padded jnp reference
bitwise on the cropped region.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .common import from_kernel_scales, tile_multiples, to_kernel_scales
from .mx_matmul import mxsf_matmul_pallas
from .mxsf_attention import mxsf_flash_attention, per_row_scalar
from .mxsf_fused_matmul import mxsf_fused_matmul_pallas
from .mxsf_quant import mxsf_quantize_pallas, mxsf_requantize_pallas


# (activation tiles the fused matmul's converter runs on, grid steps) of
# every mxsf_fused_matmul call, counted as it is traced (or run eagerly)
_FUSED_TILES = [0, 0]


def fused_lhs_converts():
    """(activation tiles converted, grid steps) summed over the fused
    matmul calls traced so far; the kernel converts each (row block, K
    tile) once, whatever the number of output tiles along N."""
    return tuple(_FUSED_TILES)


def _interpret() -> bool:
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(f"the MXSF Pallas kernels run compiled on TPU or "
                           f"interpreted on CPU; got platform {platform!r}")
    return platform == "cpu"


def _ceil_to(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _tile_for(dim: int, tile: int, block: int, align: int = 1):
    """Effective tile edge and padded dim: the tile shrinks to the
    block-padded dim for small inputs (a whole-extent block is always
    legal), else rounds up to a multiple of ``align``; the dim pads up to
    a tile multiple."""
    t = min(_ceil_to(tile, math.lcm(block, align)), _ceil_to(dim, block))
    assert t % block == 0, (dim, tile, block)
    return t, _ceil_to(dim, t)


def _align(interpret: bool, *blocks):
    """(row, col) tile multiples for compiled kernels (``common.
    tile_multiples``); interpret mode takes any tile."""
    return (1, 1) if interpret else tile_multiples(*blocks)


def _pad2d(x, m_to, k_to, fill=0):
    m, k = x.shape
    if m_to > m or k_to > k:
        x = jnp.pad(x, ((0, m_to - m), (0, k_to - k)),
                    constant_values=fill)
    return x


def mxsf_quantize(x: jax.Array, block=(1, 32), tm: int = 256, tk: int = 512):
    """MXSF-quantize a 2D array via the Pallas kernel.

    Returns ``(codes, scales)`` cropped to the *block-padded* shape — the
    same shape ``blocking.quantize`` produces, so the outputs drop straight
    into a ``QuantizedTensor``.
    """
    m, k = x.shape
    bm, bk = block
    interpret = _interpret()
    ar, ac = _align(interpret, block)
    tm, mp = _tile_for(m, tm, bm, ar)
    tk, kp = _tile_for(k, tk, bk, ac)
    codes, scales = mxsf_quantize_pallas(_pad2d(x, mp, kp),
                                         block=tuple(block), tm=tm, tk=tk,
                                         interpret=interpret)
    scales = from_kernel_scales(scales, block)
    mb, kb = _ceil_to(m, bm), _ceil_to(k, bk)
    return codes[:mb, :kb], scales[: mb // bm, : kb // bk]


def mxsf_requantize(codes, scales, from_block=(32, 1), to_block=(1, 32),
                    tm: int = 256, tk: int = 512):
    """Re-block a packed MXSF tensor through the requantize kernel.

    Input codes are the *from*-block-padded array ``blocking.quantize`` /
    ``mxsf_quantize`` produce; the code grid itself is treated as the value
    domain (padded entries are zero codes, which decode to 0.0 and never
    raise a block amax).  Returns ``(codes, scales)`` cropped to the
    ``to_block``-padded shape of the input code grid — bit-identical to
    ``mxsf_quantize(dequantize(qt), to_block)`` on the overlap.
    """
    m, k = codes.shape
    fbm, fbk = from_block
    tbm, tbk = to_block
    assert m % fbm == 0 and k % fbk == 0, (codes.shape, from_block)
    bm = math.lcm(fbm, tbm)
    bk = math.lcm(fbk, tbk)
    interpret = _interpret()
    ar, ac = _align(interpret, from_block, to_block)
    tm, mp = _tile_for(m, tm, bm, ar)
    tk, kp = _tile_for(k, tk, bk, ac)
    c = _pad2d(codes, mp, kp)
    s = to_kernel_scales(_pad2d(scales, mp // fbm, kp // fbk), from_block)
    oc, os_ = mxsf_requantize_pallas(c, s, from_block=tuple(from_block),
                                     to_block=tuple(to_block), tm=tm, tk=tk,
                                     interpret=interpret)
    os_ = from_kernel_scales(os_, to_block)
    mb, kb = _ceil_to(m, tbm), _ceil_to(k, tbk)
    return oc[:mb, :kb], os_[: mb // tbm, : kb // tbk]


def mxsf_matmul(x_codes, x_scales, w_codes, w_scales, xblk=(1, 32),
                wblk=(32, 1), tm: int = 256, tn: int = 256, tk: int = 256):
    """Packed MXSF (M,K)@(K,N) via the Pallas dequant-matmul kernel.

    Accepts block-aligned but non-tile-aligned operands: pads codes/scales
    with zeros (decode to 0.0) and crops the output back to (M, N).
    """
    m, k = x_codes.shape
    k2, n = w_codes.shape
    assert k == k2, (k, k2)
    interpret = _interpret()
    xr, xc = _align(interpret, xblk)
    wr, wc = _align(interpret, wblk)
    tm, mp = _tile_for(m, tm, xblk[0], xr)
    tn, np_ = _tile_for(n, tn, wblk[1], wc)
    kblk = max(xblk[1], wblk[0])
    assert kblk % xblk[1] == 0 and kblk % wblk[0] == 0, (xblk, wblk)
    tk, kp = _tile_for(k, tk, kblk, math.lcm(xc, wr))
    y = mxsf_matmul_pallas(
        _pad2d(x_codes, mp, kp),
        to_kernel_scales(_pad2d(x_scales, mp // xblk[0], kp // xblk[1]),
                         xblk),
        _pad2d(w_codes, kp, np_),
        to_kernel_scales(_pad2d(w_scales, kp // wblk[0], np_ // wblk[1]),
                         wblk),
        xblk=tuple(xblk), wblk=tuple(wblk),
        tm=tm, tn=tn, tk=tk, interpret=interpret)
    return y[:m, :n]


def mxsf_fused_matmul(x, w_codes, w_scales, xblk=(1, 32), wblk=(32, 1),
                      tm: int = 256, tn: int = 256, tk: int = 512,
                      quantize_lhs: bool = True, emit_codes: bool = False):
    """Fused quantize->matmul: unquantized x, packed w (see
    ``mxsf_fused_matmul.py``).

    ``x`` may have fewer K columns than ``w_codes`` has rows (packed weights
    are block-padded); the gap is zero-filled.  Returns ``y[M, N]`` or, with
    ``emit_codes``, ``(y, x_codes, x_scales)`` with codes cropped to x's
    block-padded shape (``QuantizedTensor``-ready).
    """
    m, k = x.shape
    kw, n = w_codes.shape
    assert kw >= k and kw % wblk[0] == 0, (k, kw, wblk)
    interpret = _interpret()
    xr, xc = _align(interpret, *([xblk] if emit_codes else []))
    wr, wc = _align(interpret, wblk)
    tm, mp = _tile_for(m, tm, xblk[0], xr)
    tn, np_ = _tile_for(n, tn, wblk[1], wc)
    kblk = max(xblk[1], wblk[0])
    assert kblk % xblk[1] == 0 and kblk % wblk[0] == 0, (xblk, wblk)
    tk, kp = _tile_for(kw, tk, kblk, math.lcm(xc, wr))
    mt, nk = mp // tm, kp // tk
    _FUSED_TILES[0] += mt * nk if quantize_lhs else 0
    _FUSED_TILES[1] += mt * (np_ // tn) * nk
    # no host-side upcast: the kernel casts per-tile in VMEM, so bf16
    # activations stream 2 bytes/elem from HBM, not 4
    out = mxsf_fused_matmul_pallas(
        _pad2d(x, mp, kp),
        _pad2d(w_codes, kp, np_),
        to_kernel_scales(_pad2d(w_scales, kp // wblk[0], np_ // wblk[1]),
                         wblk),
        xblk=tuple(xblk), wblk=tuple(wblk), tm=tm, tn=tn, tk=tk,
        quantize_lhs=quantize_lhs, emit_codes=emit_codes,
        interpret=interpret)
    if not emit_codes:
        return out[:m, :n]
    y, codes, scales = out
    scales = from_kernel_scales(scales, xblk)
    mb, kb = _ceil_to(m, xblk[0]), _ceil_to(k, xblk[1])
    return (y[:m, :n], codes[:mb, :kb],
            scales[: mb // xblk[0], : kb // xblk[1]])


def mxsf_attention(q, k_codes, k_scales, v_codes, v_scales, *, causal=True,
                   cq: int = 256, ck: int = 256, kv_len=None, q_offset=None,
                   window=None):
    """Flash attention over an MXSF-packed KV cache (serving hot path:
    S=1 decode steps and S=C prefill chunks alike).

    Accepts any (S, L): pads queries/cache up to chunk multiples (zero codes
    decode to 0.0, padded cache columns sit beyond ``kv_len``, and padded
    query rows are cropped before anyone reads them) and crops the output
    back to (BH, S, dh).  K/V may be in row layout (BKV, L, dh) or cache
    layout (B, KV, L, dh) — see ``mxsf_flash_attention``.  ``kv_len``/
    ``q_offset``/``window`` are dynamic per-row scalars; a growing decode
    cache — or a prefill chunk at any position — reuses one compile.
    """
    BH, S, dh = q.shape
    L = k_codes.shape[-2]
    interpret = _interpret()
    cq_, sp = _tile_for(S, cq, 1, 1 if interpret else 8)
    # the (KV, Ck) scale block puts Ck on the lanes
    ck_, lp = _tile_for(L, ck, 1, 1 if interpret else 128)
    if sp > S:
        q = jnp.pad(q, ((0, 0), (0, sp - S), (0, 0)))
    if lp > L:
        cpad = [(0, 0)] * k_codes.ndim
        cpad[-2] = (0, lp - L)
        spad = [(0, 0)] * k_scales.ndim
        spad[-1] = (0, lp - L)
        k_codes = jnp.pad(k_codes, cpad)
        v_codes = jnp.pad(v_codes, cpad)
        k_scales = jnp.pad(k_scales, spad)
        v_scales = jnp.pad(v_scales, spad)
    # resolve negative/None kv_len against the UNPADDED width so the padded
    # columns always stay masked
    kvl = jnp.minimum(per_row_scalar(kv_len, L, BH), L)
    y = mxsf_flash_attention(q, k_codes, k_scales, v_codes, v_scales,
                             causal=causal, cq=cq_, ck=ck_, kv_len=kvl,
                             q_offset=q_offset, window=window,
                             interpret=interpret)
    return y[:, :S]
