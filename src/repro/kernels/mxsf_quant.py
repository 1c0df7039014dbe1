"""Pallas TPU kernels: MXSF block quantization (the paper's MXSF Converter).

Two kernels share the converter body:

  * ``mxsf_quantize_pallas`` — raw f32/bf16 in, codes + E8M0 scales out.
    Tiles the input over a (rows, cols) grid; each kernel invocation loads
    a (TM, TK) tile into VMEM, computes per-block shared exponents (block =
    ``(bm, bk)`` elements, e.g. (1, 32) rows or (8, 8) training tiles),
    encodes every element into the MXSF byte, and writes the uint8 code
    tile plus the E8M0 scale tile.
  * ``mxsf_requantize_pallas`` — *packed* codes + scales in, packed codes +
    scales out under a different block orientation.  The decode (codes ×
    2^S_e) and the re-encode both happen in VMEM, so re-blocking a resident
    MXSF tensor (the Fig. 4a backward's "re-quantize along the transposed
    contraction dim") moves 1-byte codes through HBM twice instead of the
    dequantize→HBM→quantize double f32 roundtrip.  Bit-identical to
    ``mxsf_quantize(dequantize(qt))`` by construction: the decode is the
    same exp2i product ``blocking.dequantize`` uses and the encode is the
    shared converter.

Scales cross the kernel boundary in the lane-dense layout of
``common.py`` (``to_kernel_scales`` / ``from_kernel_scales``); ``ops.py``
converts to and from the ``QuantizedTensor`` block grid and picks tiles
the TPU's (8, 128) block rule accepts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .common import (block_exponents, decode_mxsf, encode_mxsf, exp2i,
                     expand_scales, scale_block_spec, scale_by_exp2,
                     scale_shape)

SCALE_BIAS = 127

# quantize/requantize dispatches seen at trace time: the counter lives in
# the UNjitted wrapper so it ticks once per call site on every outer trace
# (an inner-jit cache hit would otherwise hide the dispatch); tests assert
# a packed-weight decode step traces ZERO of these (see trace_count(),
# mirroring kernels/mxsf_attention.py)
_TRACE_COUNT = 0


def trace_count() -> int:
    """Quantize-kernel dispatches recorded while tracing (or eagerly)."""
    return _TRACE_COUNT


def _encode_tile(x, bm: int, bk: int):
    """The shared MXSF Converter body: f32 tile -> (codes, scale bytes).

    Used by both the raw-input quantize kernel and the packed->packed
    requantize kernel, so converter fixes (subnormal flog2, -0.0 signs, ...)
    apply to both by construction.  Scale bytes come out in the kernel
    scale layout (``common.block_exponents``).
    """
    se, se_el = block_exponents(x, bm, bk)
    # scale each element by 2^-S_e and encode
    xa = scale_by_exp2(x, -se_el)  # exact even for |S_e| > 126 (subnormal amax)
    codes = encode_mxsf(xa)
    scales = jnp.clip(se + SCALE_BIAS, 0, 255).astype(jnp.uint8)
    return codes, scales


def _quant_kernel(x_ref, codes_ref, scale_ref, *, bm: int, bk: int):
    codes_ref[...], scale_ref[...] = _encode_tile(
        x_ref[...].astype(jnp.float32), bm, bk)


def mxsf_quantize_pallas(x: jax.Array, *, block=(1, 32), tm: int = 256,
                         tk: int = 512, interpret: bool = False):
    """Quantize a 2D f32/bf16 array to MXSF codes + E8M0 scales.

    Returns ``(codes[M, K] uint8, scales)`` with the scales in the kernel
    layout (``common.scale_shape``).  Shapes must be multiples of the tile;
    ``ops.py`` handles padding and the layout conversion.
    """
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    return _mxsf_quantize_jit(x, block=tuple(block), tm=tm, tk=tk,
                              interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block", "tm", "tk", "interpret"))
def _mxsf_quantize_jit(x: jax.Array, *, block, tm: int, tk: int,
                       interpret: bool):
    m, k = x.shape
    bm, bk = block
    tm = min(tm, m)
    tk = min(tk, k)
    assert m % tm == 0 and k % tk == 0, (m, k, tm, tk)
    assert tm % bm == 0 and tk % bk == 0, (tm, tk, block)
    grid = (m // tm, k // tk)
    kernel = functools.partial(_quant_kernel, bm=bm, bk=bk)
    tile = lambda i, j: (i, j)
    codes, scales = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tm, tk), tile)],
        out_specs=[
            pl.BlockSpec((tm, tk), tile),
            scale_block_spec(block, tm, tk, tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.uint8),
            jax.ShapeDtypeStruct(scale_shape(block, m, k), jnp.uint8),
        ],
        interpret=interpret,
    )(x)
    return codes, scales


def _requant_kernel(c_ref, s_ref, codes_ref, scale_ref, *, from_block,
                    to_block):
    # decode the resident codes in VMEM — same exp2i product as
    # blocking.dequantize, so the value set is bit-identical
    fse = s_ref[...].astype(jnp.int32) - SCALE_BIAS
    x = decode_mxsf(c_ref[...]) * exp2i(expand_scales(fse, *from_block))
    # re-encode under the new block orientation (the shared converter body)
    codes_ref[...], scale_ref[...] = _encode_tile(x, *to_block)


def mxsf_requantize_pallas(codes: jax.Array, scales: jax.Array, *,
                           from_block=(32, 1), to_block=(1, 32),
                           tm: int = 256, tk: int = 512,
                           interpret: bool = False):
    """Re-block a packed MXSF tensor: codes+scales in, codes+scales out.

    One dispatch, 1-byte traffic both ways — replaces the
    ``dequantize`` → f32 HBM → ``quantize`` pair.  Returns
    ``(codes[M, K], scales)`` for ``to_block``; scales in and out are in
    the kernel layout (``common.scale_shape``).
    Shapes must be multiples of the tile and of both blocks;
    ``ops.mxsf_requantize`` handles padding.
    """
    global _TRACE_COUNT
    _TRACE_COUNT += 1
    return _mxsf_requantize_jit(codes, scales, from_block=tuple(from_block),
                                to_block=tuple(to_block), tm=tm, tk=tk,
                                interpret=interpret)


@functools.partial(jax.jit, static_argnames=("from_block", "to_block", "tm",
                                             "tk", "interpret"))
def _mxsf_requantize_jit(codes: jax.Array, scales: jax.Array, *,
                         from_block, to_block, tm: int, tk: int,
                         interpret: bool):
    m, k = codes.shape
    tm = min(tm, m)
    tk = min(tk, k)
    assert m % tm == 0 and k % tk == 0, (m, k, tm, tk)
    for bm, bk in (from_block, to_block):
        assert tm % bm == 0 and tk % bk == 0, (tm, tk, from_block, to_block)
    grid = (m // tm, k // tk)
    kernel = functools.partial(_requant_kernel, from_block=tuple(from_block),
                               to_block=tuple(to_block))
    tile = lambda i, j: (i, j)
    out_codes, out_scales = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), tile),
            scale_block_spec(from_block, tm, tk, tile),
        ],
        out_specs=[
            pl.BlockSpec((tm, tk), tile),
            scale_block_spec(to_block, tm, tk, tile),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.uint8),
            jax.ShapeDtypeStruct(scale_shape(to_block, m, k), jnp.uint8),
        ],
        interpret=interpret,
    )(codes, scales)
    return out_codes, out_scales
