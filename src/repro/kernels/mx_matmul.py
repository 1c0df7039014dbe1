"""Pallas TPU kernel: MXSF dequant-matmul (the SAFE-MAC array, TPU-adapted).

The paper's systolic tensor array decodes MXSF operands in the MAC and
multiplies in an E4M5-covering multiplier with FP12 accumulation.  The TPU
adaptation (DESIGN.md §3) keeps operands packed (uint8 codes + E8M0 block
scales) in HBM, decodes tile-by-tile in VMEM, and feeds the MXU with f32
accumulation — preserving the off-chip-traffic win that dominates the
paper's energy table.

Grid: (M/TM, N/TN, K/TK), K innermost; f32 accumulator lives in VMEM
scratch across the K loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import decode_mxsf, exp2i, expand_scales, scale_block_spec

SCALE_BIAS = 127


def _matmul_kernel(xc_ref, xs_ref, wc_ref, ws_ref, o_ref, acc_ref, *,
                   nk: int, xblk, wblk):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xse = xs_ref[...].astype(jnp.int32) - SCALE_BIAS
    wse = ws_ref[...].astype(jnp.int32) - SCALE_BIAS
    xv = decode_mxsf(xc_ref[...]) * exp2i(expand_scales(xse, *xblk))
    wv = decode_mxsf(wc_ref[...]) * exp2i(expand_scales(wse, *wblk))
    acc_ref[...] += jnp.dot(xv, wv, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("xblk", "wblk", "tm", "tn", "tk",
                                             "interpret"))
def mxsf_matmul_pallas(x_codes, x_scales, w_codes, w_scales, *,
                       xblk=(1, 32), wblk=(32, 1),
                       tm: int = 256, tn: int = 256, tk: int = 256,
                       interpret: bool = False):
    # 256x256 output tiles put the packed dequant-matmul past the v5e
    # roofline ridge (AI ~248 vs 241); see benchmarks/kernel_bench.py.
    """(M,K) @ (K,N) on MXSF-packed operands -> f32.

    ``xblk``/``wblk`` are the MX block shapes of each operand: (1, B)/(B, 1)
    for 1D inference layout, (T, T)/(T, T) for the 2D training tiles.
    Scales are in the kernel layout (``common.to_kernel_scales``).
    """
    m, k = x_codes.shape
    k2, n = w_codes.shape
    assert k == k2
    tm, tn, tk = min(tm, m), min(tn, n), min(tk, k)
    assert m % tm == 0 and n % tn == 0 and k % tk == 0
    nk = k // tk
    kernel = functools.partial(_matmul_kernel, nk=nk, xblk=xblk, wblk=wblk)
    x_tile = lambda i, j, kk: (i, kk)
    w_tile = lambda i, j, kk: (kk, j)
    return pl.pallas_call(
        kernel,
        grid=(m // tm, n // tn, nk),
        in_specs=[
            pl.BlockSpec((tm, tk), x_tile),
            scale_block_spec(xblk, tm, tk, x_tile),
            pl.BlockSpec((tk, tn), w_tile),
            scale_block_spec(wblk, tk, tn, w_tile),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        interpret=interpret,
    )(x_codes, x_scales, w_codes, w_scales)
