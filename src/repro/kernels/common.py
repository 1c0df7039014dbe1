"""Bit-level float helpers and the E8M0 scale layout shared by the Pallas
kernels.

TPU Pallas has no frexp/ldexp lowering, so exponent extraction and
power-of-two construction are done by bit-casting — identical semantics in
interpret mode (CPU validation) and on real TPUs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flog2", "exp2i", "rne", "scale_by_exp2", "block_exponents",
           "expand_scales", "scale_block_spec", "to_kernel_scales",
           "from_kernel_scales", "scale_shape", "tile_multiples",
           "decode_mxsf", "encode_mxsf"]


def flog2(a: jax.Array) -> jax.Array:
    """floor(log2(a)) for a >= 0 f32, exact down to subnormals; -149-ish
    for the smallest denormals, -127 for zero.

    Subnormals have a zero exponent field, so the plain bitcast trick reads
    them as -127; renormalizing by 2^24 first (exact: integer-mantissa shift
    into the normal range) recovers the true exponent and keeps the kernels
    bit-identical to the frexp-based ``formats.floor_log2`` reference.
    """
    a = a.astype(jnp.float32)
    sub = (a > 0) & (a < 2.0 ** -126)
    an = jnp.where(sub, a * jnp.float32(2.0 ** 24), a)
    bits = jax.lax.bitcast_convert_type(an, jnp.int32)
    return ((bits >> 23) & 0xFF) - 127 - jnp.where(sub, 24, 0)


def exp2i(e: jax.Array) -> jax.Array:
    """Exact 2^e for integer e in [-126, 127]."""
    e = jnp.clip(e, -126, 127).astype(jnp.int32)
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def scale_by_exp2(x: jax.Array, e: jax.Array) -> jax.Array:
    """x * 2^e for integer e in [-252, 252], split so each factor is a
    representable power of two (exp2i alone clips outside [-126, 127],
    which breaks blocks whose shared exponent is +-127-ish)."""
    e = e.astype(jnp.int32)
    e1 = e // 2
    return x * exp2i(e1) * exp2i(e - e1)


# ---------------------------------------------------------------------------
# E8M0 scales at the kernel boundary
# ---------------------------------------------------------------------------
#
# Mosaic blocks the last two dims of every operand in (8, 128) units, so a
# block-grid scale tile (tm/bm, tk/bk) -- (256, 16) for 1x32 row blocks --
# is refused.  The kernels therefore see scales in a lane-dense layout:
#
#   * bk == 1 (blocks run down the rows: the (B, 1) weight layout): the
#     block grid itself, (R/br, C); the pack-once store feeds it as-is.
#   * bk > 1 (blocks run along the lanes: 1xB activation rows, TxT tiles):
#     the transposed grid with one column per operand row, (C/bc, R); for
#     TxT tiles each column repeats its tile's exponent over the tile's rows.
#
# Inside a kernel, block reductions and broadcasts only ever split or merge
# the sublane dim in whole groups (the 4-D (tm, tk) -> (gm, bm, gk, bk)
# reshape is an "unsupported shape cast"); lane blocks are moved onto the
# sublanes by a 2-D transpose first.


def _group_max_rows(a: jax.Array, b: int) -> jax.Array:
    """Max over aligned groups of ``b`` rows: (n, w) -> (n // b, w)."""
    if b == 1:
        return a
    n, w = a.shape
    return a.reshape(n // b, b, w).max(axis=1)


def _repeat_rows(s: jax.Array, b: int) -> jax.Array:
    """Every row ``b`` times: (g, w) -> (g * b, w)."""
    if b == 1:
        return s
    g, w = s.shape
    return jnp.broadcast_to(s[:, None, :], (g, b, w)).reshape(g * b, w)


def block_exponents(x: jax.Array, bm: int, bk: int):
    """Shared block exponents of an f32 tile.

    Returns ``(se, se_el)``: ``se`` in the kernel scale layout (see above)
    and ``se_el`` the (tm, tk) per-element map.  All-zero blocks get -127,
    matching ``formats.shared_exponent``.
    """
    a = jnp.abs(x)
    if bk == 1:
        amax = _group_max_rows(a, bm)                       # (tm/bm, tk)
    else:
        amax = _group_max_rows(a.T, bk)                     # (tk/bk, tm)
        if bm > 1:
            amax = _repeat_rows(_group_max_rows(amax.T, bm), bm).T
    se = jnp.where(amax > 0, flog2(amax), -127)
    return se, expand_scales(se, bm, bk)


def expand_scales(se: jax.Array, bm: int, bk: int) -> jax.Array:
    """Kernel-layout block exponents -> the (tm, tk) per-element map."""
    if bk == 1:
        return _repeat_rows(se, bm)
    return _repeat_rows(se, bk).T


def scale_block_spec(block, tr: int, tc: int, index_map):
    """BlockSpec of the scales of an operand tiled (tr, tc) with MX block
    ``block``; ``index_map`` is the operand's own (grid -> (I, J))."""
    br, bc = block
    if bc == 1:
        return pl.BlockSpec((tr // br, tc), index_map)
    return pl.BlockSpec((tc // bc, tr),
                        lambda *g: tuple(reversed(index_map(*g))))


def tile_multiples(*blocks):
    """(row, col) multiples a compiled tile of an operand needs: (8, 128)
    for its codes, and for the kernel-layout scales of each MX block in
    ``blocks`` whatever keeps their blocks on the (8, 128) rule too."""
    r, c = 8, 128
    for br, bc in blocks:
        r = math.lcm(r, 8 * br if bc == 1 else 128)
        c = math.lcm(c, 128 if bc == 1 else 8 * bc)
    return r, c


def scale_shape(block, rows: int, cols: int):
    """Shape of the kernel-layout scale array of a (rows, cols) operand."""
    br, bc = block
    return (rows // br, cols) if bc == 1 else (cols // bc, rows)


def to_kernel_scales(s: jax.Array, block) -> jax.Array:
    """Block-grid scales (``QuantizedTensor.scale_e8m0``) -> kernel layout."""
    br, bc = block
    if bc == 1:
        return s
    return jnp.repeat(s, br, axis=0).T


def from_kernel_scales(s: jax.Array, block) -> jax.Array:
    """Kernel-layout scales -> the block grid."""
    br, bc = block
    if bc == 1:
        return s
    return s.T[::br]


def rne(x: jax.Array) -> jax.Array:
    return jax.lax.round(x, jax.lax.RoundingMethod.TO_NEAREST_EVEN)


def decode_mxsf(code: jax.Array) -> jax.Array:
    """MXSF byte -> value relative to the shared exponent (f32)."""
    c = code.astype(jnp.int32)
    s = (c >> 7) & 1
    ee = (c >> 5) & 3
    m5 = (c & 31).astype(jnp.float32)
    eee = (c >> 2) & 7
    m2 = (c & 3).astype(jnp.float32)
    v25 = (1.0 + m5 / 32.0) * exp2i(ee - 3)
    v32n = (1.0 + m2 / 4.0) * exp2i(eee - 10)
    v32s = (m2 / 4.0) * jnp.float32(2.0 ** -9)
    mag = jnp.where(ee > 0, v25, jnp.where(eee > 0, v32n, v32s))
    return jnp.where(s == 1, -mag, mag)


def encode_mxsf(xa: jax.Array) -> jax.Array:
    """Relative value (|xa| < 2) -> MXSF byte.  Mirrors formats._encode_safe_rel."""
    xa = xa.astype(jnp.float32)
    # sign straight from the bit pattern so -0.0 keeps its sign byte
    # (tiny negatives can underflow to -0.0 in the 2^-S_e scaling)
    s = (jax.lax.bitcast_convert_type(xa, jnp.int32) >> 31) & 1
    a = jnp.abs(xa)
    e = flog2(a)

    # E2M5 regime (gap < 3)
    e25 = jnp.clip(e, -2, 0)
    m25 = rne(a * exp2i(5 - e25))
    ovf = m25 >= 64
    e25 = jnp.where(ovf, e25 + 1, e25)
    m25 = jnp.where(ovf, 32.0, m25)
    top = e25 > 0
    e25 = jnp.where(top, 0, e25)
    m25 = jnp.where(top, 63.0, m25)
    code25 = ((e25 + 3) << 5) | (m25.astype(jnp.int32) - 32)

    # E3M2 regime (gap >= 3)
    e32 = jnp.clip(e, -9, -3)
    sub = a < 2.0 ** -9
    step = jnp.where(sub, jnp.float32(2.0 ** -11), exp2i(e32 - 2))
    q = rne(a / step)
    promote = sub & (q >= 4)
    q = jnp.where(promote, 4.0, q)
    e32 = jnp.where(promote, -9, e32)
    sub = sub & ~promote
    novf = (~sub) & (q >= 8)
    e32 = jnp.where(novf, e32 + 1, e32)
    q = jnp.where(novf, 4.0, q)
    cross = e32 > -3
    eee = jnp.where(sub, 0, e32 + 10)
    m2 = jnp.where(sub, q, q - 4.0).astype(jnp.int32)
    code32 = (eee << 2) | m2
    code32 = jnp.where(cross, 1 << 5, code32)

    code = jnp.where(a == 0, 0, jnp.where(e >= -2, code25, code32))
    return (code | (s << 7)).astype(jnp.uint8)
