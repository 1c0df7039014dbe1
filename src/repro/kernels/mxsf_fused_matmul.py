"""Pallas TPU kernel: fused MXSF quantize->matmul (SAFE-MAC prologue fusion).

The paper's energy win comes from keeping operands packed end-to-end and
decoding inside the MAC array.  The unfused datapath (``mxsf_quantize`` then
``mxsf_matmul``) still pays one full HBM roundtrip for the activation side:
codes + scales are written by the quantizer and immediately re-read by the
matmul.  This kernel folds the MXSF Converter into the matmul prologue:

  * LHS ``x`` arrives *unquantized* (f32/bf16).  Each (TM, TK) tile computes
    its per-block shared exponents and MXSF byte codes in VMEM, decodes them
    right back (the SAFE-MAC decode-in-MAC step), and feeds the MXU — the
    activation codes never touch HBM on the forward value path.
  * RHS ``w`` arrives *packed* (uint8 codes + E8M0 scales), exactly like
    ``mxsf_matmul``: weights are quantized once and stay packed in HBM.

Quantize->decode through the byte codec (not a value-domain shortcut) keeps
the result bit-identical to ``blocking.quantize`` + ``blocking.dequantize``.

The converter's output depends only on the activation tile ``(i, kk)``,
not on the output tile ``j``.  So the kernel converts each activation tile
once, at ``j == 0``, into a VMEM scratch that holds the decoded row block
``(nk, TM, TK)`` in f32 (the operand the MXU saw before, so the result
stays bit-identical), and every later ``j`` reads it back; x's index map
stays on the last block fetched once ``j > 0``, so x streams from HBM
once per row block.  The scratch takes ``K * TM * 4`` bytes (28.3 MB for a
27648-wide K at TM = 256); the call raises its scoped VMEM limit by that
much, and asserts that the row block fits the core's VMEM.

Two static switches cover the training datapath:

  * ``emit_codes``: additionally write the LHS codes + scales (the packed
    residual the custom-VJP backward needs), at ``j == 0`` with the row
    block (their index maps, like x's, stay on the last block once
    ``j > 0``, so each block is written back once).
  * ``quantize_lhs=False``: skip the converter and feed raw f32 (the
    ``quantize_bwd=False`` gradient path: unquantized g against packed w).

Grid: (M/TM, N/TN, K/TK), K innermost; f32 accumulator in VMEM scratch.
MX blocks must tile evenly (TM % bm == 0, TK % bk == 0), so tile-local
shared exponents equal the global block quantization.  With a single K tile
the accumulation order matches one jnp.matmul bitwise; multiple K tiles
accumulate tile-by-tile (f32 tolerance).  ``ops.mxsf_fused_matmul`` handles
padding and crop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (block_exponents, decode_mxsf, encode_mxsf, exp2i,
                     expand_scales, scale_block_spec, scale_by_exp2,
                     scale_shape)

SCALE_BIAS = 127

# VMEM of a v5e TensorCore.  The kernel's other buffers -- pipeline
# buffers, accumulator, the converter's temporaries -- fit the 16 MiB
# default scoped limit; a call asks for twice that on top of its resident
# row block.
VMEM_BYTES = 128 << 20
OTHER_VMEM_BYTES = 32 << 20


def _fused_kernel(x_ref, wc_ref, ws_ref, o_ref, *rest, nk: int, xblk, wblk,
                  quantize_lhs: bool, emit_codes: bool):
    if emit_codes:
        xc_ref, xs_ref, *rest = rest
    acc_ref, *rest = rest
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if quantize_lhs:
        (xq_ref,) = rest

        @pl.when(pl.program_id(1) == 0)
        def _convert():
            # --- MXSF Converter, fused into the matmul prologue -----------
            x = x_ref[...].astype(jnp.float32)
            se, se_el = block_exponents(x, *xblk)
            codes = encode_mxsf(scale_by_exp2(x, -se_el))
            if emit_codes:
                xc_ref[...] = codes
                xs_ref[...] = jnp.clip(se + SCALE_BIAS, 0,
                                       255).astype(jnp.uint8)
            # decode-in-MAC: reconstruct through the byte codec so the
            # operand is bit-identical to the packed reference path
            xq_ref[kk] = decode_mxsf(codes) * exp2i(se_el)

        xv = xq_ref[kk]
    else:
        xv = x_ref[...].astype(jnp.float32)

    wse = ws_ref[...].astype(jnp.int32) - SCALE_BIAS
    wv = decode_mxsf(wc_ref[...]) * exp2i(expand_scales(wse, *wblk))
    acc_ref[...] += jnp.dot(xv, wv, preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("xblk", "wblk", "tm", "tn", "tk",
                                             "quantize_lhs", "emit_codes",
                                             "interpret"))
def mxsf_fused_matmul_pallas(x, w_codes, w_scales, *,
                             xblk=(1, 32), wblk=(32, 1),
                             tm: int = 256, tn: int = 256, tk: int = 512,
                             quantize_lhs: bool = True,
                             emit_codes: bool = False,
                             interpret: bool = False):
    """Unquantized (M,K) x @ packed (K,N) w -> f32 (M,N).

    Returns ``y`` or, with ``emit_codes``, ``(y, x_codes, x_scales)``.
    Scales in and out are in the kernel layout (``common.scale_shape``).
    Shapes must be tile multiples; ``ops.mxsf_fused_matmul`` pads/crops.
    """
    m, k = x.shape
    k2, n = w_codes.shape
    assert k == k2, (k, k2)
    assert quantize_lhs or not emit_codes, "emit_codes requires quantize_lhs"
    tm, tn, tk = min(tm, m), min(tn, n), min(tk, k)
    assert m % tm == 0 and n % tn == 0 and k % tk == 0, (m, n, k, tm, tn, tk)
    assert tm % xblk[0] == 0 and tk % xblk[1] == 0, (xblk, tm, tk)
    assert tk % wblk[0] == 0 and tn % wblk[1] == 0, (wblk, tk, tn)
    nk = k // tk
    kernel = functools.partial(_fused_kernel, nk=nk, xblk=xblk, wblk=wblk,
                               quantize_lhs=quantize_lhs,
                               emit_codes=emit_codes)
    if quantize_lhs:
        # once j > 0 the x (and emitted codes) block stays on the last one
        # visited: no fetch of x, and the codes are written back once
        x_tile = lambda i, j, kk: (i, jnp.where(j == 0, kk, nk - 1))
    else:
        x_tile = lambda i, j, kk: (i, kk)
    w_tile = lambda i, j, kk: (kk, j)
    out_shape = [jax.ShapeDtypeStruct((m, n), jnp.float32)]
    out_specs = [pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j))]
    if emit_codes:
        out_shape += [
            jax.ShapeDtypeStruct((m, k), jnp.uint8),
            jax.ShapeDtypeStruct(scale_shape(xblk, m, k), jnp.uint8),
        ]
        out_specs += [
            pl.BlockSpec((tm, tk), x_tile),
            scale_block_spec(xblk, tm, tk, x_tile),
        ]
    scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    params = None
    if quantize_lhs:
        row_block = tm * k * 4
        assert row_block + OTHER_VMEM_BYTES <= VMEM_BYTES, (
            f"decoded row block of {tm}x{k} takes {row_block} B of VMEM")
        scratch.append(pltpu.VMEM((nk, tm, tk), jnp.float32))
        # j carries the row block from j == 0 on: it stays sequential
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=OTHER_VMEM_BYTES + row_block)
    out = pl.pallas_call(
        kernel,
        grid=(m // tm, n // tn, nk),
        in_specs=[
            pl.BlockSpec((tm, tk), x_tile),
            pl.BlockSpec((tk, tn), w_tile),
            scale_block_spec(wblk, tk, tn, w_tile),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(x, w_codes, w_scales)
    return tuple(out) if emit_codes else out[0]
