"""Quantized matmul with custom VJP — the paper's training datapath.

``mx_dot(x, w, policy)`` quantizes both operands to the policy's MX format
before the matmul and (optionally) quantizes the incoming gradient in the
backward pass.  Two block layouts (paper Fig. 4):

  * 1D row blocks: forward quantizes along the contraction dim; the backward
    pass must RE-quantize x, w, g along their transposed contraction dims
    (6 quantization passes / layer / step).
  * 2D TxT tiles: quantize once, reuse via ``transpose_qt`` in the backward
    (3 passes) — the paper's tiling contribution.

Residuals are stored *packed* (uint8 codes + E8M0 scales) when
``policy.save_packed``, which is what gives the memory saving on real
hardware; packed and value-domain residuals are bit-identical (tested).

A trace-time counter (``quant_pass_count``) reproduces the Fig. 4
quantization-pass accounting.

Backend dispatch (``policy.backend``):

  * ``'jnp'``    : pure-jnp quantize/dequantize roundtrips (reference).
  * ``'pallas'`` : the Pallas datapath (``kernels/``).  Weights are packed
    once by the quantizer kernel and stay uint8 in HBM; activations are
    quantized *inside* the matmul prologue by the fused quantize->matmul
    kernel (``kernels/mxsf_fused_matmul.py``), which also emits the packed
    activation residual for the backward pass.  The backward reuses 2D tiles
    via ``transpose_qt`` (packed dequant-matmul) and re-quantizes through
    the packed->packed requantize kernel in the 1D layout (codes in, codes
    out — no f32 HBM roundtrip).  Off-TPU the kernels run in
    ``interpret=True`` mode; forward outputs are bit-identical to the jnp
    reference whenever K fits one kernel tile (gradients match to f32
    accumulation tolerance).  Pass accounting is unchanged: 1D=6, 2D=3.

Packed weight operand (the pack-once store, ``core/packed_store.py``):

``mx_dot(x, w, policy)`` also accepts ``w`` as a resident
``blocking.QuantizedTensor``.  That path performs ZERO weight-quantize
dispatches per call — the fused kernel consumes the resident codes
directly (and the jnp backend dequantizes them, bit-identical to the
per-call quantize).  The custom-VJP residual IS the resident tensor: the
2D backward transposes its tiles via ``transpose_qt``, the 1D backward
re-blocks it with the requantize kernel, and no activation residual is
emitted at all because packed weights are frozen — their cotangent is
symbolically zero (float0), so ``dw`` is never computed.  Pass accounting
with a packed weight: 1D = 3 (x fwd, w re-block, g), 2D = 2 (x fwd, g).

Trace stability under serving shapes: ``mx_dot`` flattens every leading
dim into rows (``(B, S, K) -> (B*S, K)``), so the serving engine's two
entry points each hit exactly one compilation — decode steps are ``B*1``
rows and prefill chunks are ``B*C`` rows with C *static* (the engine pads
the final partial chunk to C and masks, rather than tracing a fresh kernel
per ragged chunk length).  1D activation row-blocks run along K, so a
chunk's C rows quantize exactly like C separate single-token calls —
chunked and token-by-token prefill are bit-identical through the linears.
"""
from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import blocking as B
from . import sharding as shd
from .policy import QuantPolicy

__all__ = ["mx_dot", "mx_einsum", "qdq_along", "count_quant_passes",
           "quant_pass_count"]

# ---------------------------------------------------------------------------
# trace-time quantization-pass accounting (paper Fig. 4)
# ---------------------------------------------------------------------------

_COUNTER = {"n": 0, "active": False}


@contextlib.contextmanager
def count_quant_passes():
    """Count quantize ops added to the traced graph inside this context."""
    prev = dict(_COUNTER)
    _COUNTER.update(n=0, active=True)
    try:
        yield _COUNTER
    finally:
        _COUNTER["active"] = prev["active"]


def quant_pass_count() -> int:
    return _COUNTER["n"]


def _tick():
    if _COUNTER["active"]:
        _COUNTER["n"] += 1


def _qdq(x, fmt, block):
    _tick()
    return B.qdq(x, fmt, block)


def _quantize(x, fmt, block):
    _tick()
    return B.quantize(x, fmt, block)


def qdq_along(x: jax.Array, fmt: str, policy: QuantPolicy, axis: int = -1):
    """Quantize-dequantize with 1D blocks along ``axis`` (-1 or -2)."""
    if not policy.enabled:
        return x
    blk = (policy.block_1d,) if axis in (-1, x.ndim - 1) else (policy.block_1d, 1)
    return _qdq(x, fmt, blk)


# ---------------------------------------------------------------------------
# mx_dot: x (..., K) @ w (K, N)
# ---------------------------------------------------------------------------

def _flatten_lead(x):
    lead = x.shape[:-1]
    # explicit product: reshape(-1, 0) is ill-defined for zero-size dims
    return x.reshape(math.prod(lead), x.shape[-1]), lead


def _pol_blocks(policy: QuantPolicy):
    """(xblk, wblk) 2D block shapes for the kernel datapath."""
    if policy.block_mode == "2d":
        t = (policy.tile, policy.tile)
        return t, t
    return (1, policy.block_1d), (policy.block_1d, 1)


def _pallas_fwd(policy: QuantPolicy, xm, w, with_residuals: bool):
    """Fused-kernel forward: pack w once, quantize x inside the matmul."""
    from ..kernels import ops as K
    xblk, wblk = _pol_blocks(policy)
    _tick()  # w quantized (packed) by the quantizer kernel
    wc, ws = K.mxsf_quantize(w, block=wblk)
    _tick()  # x quantized on the fly in the fused matmul prologue
    if with_residuals:
        y, xc, xs = K.mxsf_fused_matmul(xm, wc, ws, xblk, wblk,
                                        emit_codes=True)
        res = (B.QuantizedTensor(xc, xs, policy.fwd_fmt, xblk,
                                 tuple(xm.shape), str(xm.dtype)),
               B.QuantizedTensor(wc, ws, policy.fwd_fmt, wblk,
                                 tuple(w.shape), str(w.dtype)))
    else:
        y = K.mxsf_fused_matmul(xm, wc, ws, xblk, wblk, emit_codes=False)
        res = None
    y = y[:, : w.shape[-1]].astype(jnp.result_type(xm.dtype, w.dtype))
    return y, res


def _pallas_dx_2d(policy: QuantPolicy, qtw, gm):
    """Fig. 4b dx: reuse the resident/residual w tiles via transpose_qt.

    Shared by the raw-weight backward and the packed-store backward.
    Returns ``(dx_uncropped, (gc, gs) or None)`` — the quantized g is
    handed back so the raw path can reuse it for dw (g quantized ONCE).
    """
    from ..kernels import ops as K
    blk = (policy.tile, policy.tile)
    qwT = B.transpose_qt(qtw)
    if policy.quantize_bwd:
        _tick()
        gc, gs = K.mxsf_quantize(gm, block=blk)
        return K.mxsf_matmul(gc, gs, qwT.codes, qwT.scale_e8m0, blk, blk), \
            (gc, gs)
    return K.mxsf_fused_matmul(gm, qwT.codes, qwT.scale_e8m0, blk, blk,
                               quantize_lhs=False), None


def _pallas_dx_1d(policy: QuantPolicy, qtw, gm):
    """Fig. 4a dx: re-block w along N packed->packed through the
    requantize kernel (codes in, codes out in VMEM — the old dequantize ->
    f32 HBM -> quantize pair paid a double full-precision roundtrip).

    Shared by the raw-weight backward and the packed-store backward.
    """
    from ..kernels import ops as K
    b = policy.block_1d
    _tick()  # w re-blocked along N (still one Fig. 4a quantize pass)
    wrc, wrs = K.mxsf_requantize(qtw.codes, qtw.scale_e8m0, qtw.block, (1, b))
    if policy.quantize_bwd:
        _tick()  # g quantized along N inside the fused prologue
    return K.mxsf_fused_matmul(gm, wrc.T, wrs.T, (1, b), (b, 1),
                               quantize_lhs=policy.quantize_bwd)


def _pallas_bwd(policy: QuantPolicy, qtx, qtw, gm):
    """Kernel-datapath backward for both layouts (see module docstring)."""
    from ..kernels import ops as K
    m, k = qtx.shape
    n = qtw.shape[-1]
    gm = gm.astype(jnp.float32)
    if policy.block_mode == "2d":
        # Fig. 4b: quantize g ONCE as TxT tiles, reuse x/w via transpose_qt
        blk = (policy.tile, policy.tile)
        dx, g_packed = _pallas_dx_2d(policy, qtw, gm)
        qxT = B.transpose_qt(qtx)
        if g_packed is not None:
            gc, gs = g_packed
            dw = K.mxsf_matmul(qxT.codes, qxT.scale_e8m0, gc, gs, blk, blk)
        else:
            dw = K.mxsf_fused_matmul(gm.T, qtx.codes, qtx.scale_e8m0, blk,
                                     blk, quantize_lhs=False)[:n, :k].T
        return dx[:m, :k], dw[:k, :n]
    # Fig. 4a: re-quantize x, w, g along the transposed contraction dims
    b = policy.block_1d
    quant_g = policy.quantize_bwd
    dx = _pallas_dx_1d(policy, qtw, gm)
    _tick()  # x re-blocked along M (packed->packed, like w above)
    xrc, xrs = K.mxsf_requantize(qtx.codes, qtx.scale_e8m0, qtx.block, (b, 1))
    if quant_g:
        _tick()  # g quantized along M inside the fused prologue
    dw = K.mxsf_fused_matmul(gm.T, xrc, xrs, (1, b), (b, 1),
                             quantize_lhs=quant_g)[:n, :k].T
    return dx[:m, :k], dw


def _kernel_shapes_ok(x, w) -> bool:
    """Zero-sized operands have nothing to quantize; the jnp path already
    produces the (empty) result, so skip the kernel dispatch."""
    return (math.prod(x.shape[:-1]) > 0 and x.shape[-1] > 0
            and w.shape[-1] > 0)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mx_dot(policy: QuantPolicy, x: jax.Array, w: jax.Array) -> jax.Array:
    if policy.use_pallas and _kernel_shapes_ok(x, w):
        # primal-only call (no grad trace): skip the residual emission
        xm, lead = _flatten_lead(x)
        y, _ = _pallas_fwd(policy, xm, w, with_residuals=False)
        return y.reshape(*lead, w.shape[-1])
    y, _ = _mx_dot_fwd(policy, x, w)
    return y


def _mx_dot_fwd(policy: QuantPolicy, x, w):
    xm, lead = _flatten_lead(x)
    if policy.use_pallas and _kernel_shapes_ok(x, w):
        y, res = _pallas_fwd(policy, xm, w, with_residuals=True)
        return y.reshape(*lead, w.shape[-1]), (res, lead)
    if policy.block_mode == "2d":
        blk = (policy.tile, policy.tile)
    else:
        blk = None
    if policy.save_packed:
        if policy.block_mode == "2d":
            qtx = _quantize(xm, policy.fwd_fmt, blk)
            qtw = _quantize(w, policy.fwd_fmt, blk)
        else:  # 1d: x blocks along K (last), w blocks along K (rows)
            qtx = _quantize(xm, policy.fwd_fmt, (policy.block_1d,))
            qtw = _quantize(w, policy.fwd_fmt, (policy.block_1d, 1))
        xq = B.dequantize(qtx)
        wq = B.dequantize(qtw)
        res = (qtx, qtw)
    else:
        if policy.block_mode == "2d":
            xq = _qdq(xm, policy.fwd_fmt, blk)
            wq = _qdq(w, policy.fwd_fmt, blk)
        else:
            xq = _qdq(xm, policy.fwd_fmt, (policy.block_1d,))
            wq = _qdq(w, policy.fwd_fmt, (policy.block_1d, 1))
        res = (xq, wq)
    y = jnp.matmul(xq, wq)
    return y.reshape(*lead, w.shape[-1]), (res, lead)


def _mx_dot_bwd(policy: QuantPolicy, carry, g):
    res, lead = carry
    gm, _ = _flatten_lead(g)  # (M, N)

    # res[0] is a QuantizedTensor (pallas / packed) or array (jnp value
    # residual); .shape[-1] = K either way, mirroring the forward guard
    if policy.use_pallas and gm.shape[0] > 0 and gm.shape[1] > 0 \
            and res[0].shape[-1] > 0:
        qtx, qtw = res
        dx, dw = _pallas_bwd(policy, qtx, qtw, gm)
        return (dx.reshape(*lead, dx.shape[-1]).astype(g.dtype),
                dw.astype(g.dtype))

    if policy.save_packed:
        qtx, qtw = res
    else:
        xq, wq = res

    if policy.block_mode == "2d":
        # quantize g once as TxT tiles; reuse x/w tiles transposed (Fig. 4b)
        blk = (policy.tile, policy.tile)
        if policy.quantize_bwd:
            gq = _qdq(gm, policy.bwd_fmt, blk)
        else:
            gq = gm
        if policy.save_packed:
            wTq = B.dequantize(B.transpose_qt(qtw))   # (N, K), no requant
            xTq = B.dequantize(B.transpose_qt(qtx))   # (K, M), no requant
        else:
            wTq, xTq = wq.T, xq.T
        dx = jnp.matmul(gq, wTq)
        dw = jnp.matmul(xTq, gq)
    else:
        # 1D: re-quantize along the new contraction dims (Fig. 4a)
        if policy.save_packed:
            xq = B.dequantize(qtx)
            wq = B.dequantize(qtw)
        b = policy.block_1d
        if policy.quantize_bwd:
            g_for_dx = _qdq(gm, policy.bwd_fmt, (b,))       # blocks along N
            g_for_dw = _qdq(gm, policy.bwd_fmt, (b, 1))     # blocks along M
        else:
            g_for_dx = g_for_dw = gm
        w_re = _qdq(wq, policy.fwd_fmt, (1, b))             # blocks along N
        x_re = _qdq(xq, policy.fwd_fmt, (b, 1))             # blocks along M
        dx = jnp.matmul(g_for_dx, w_re.T)
        dw = jnp.matmul(x_re.T, g_for_dw)

    dx = dx.reshape(*lead, dx.shape[-1]).astype(g.dtype)
    return dx, dw.astype(g.dtype)


_mx_dot.defvjp(_mx_dot_fwd, _mx_dot_bwd)


# ---------------------------------------------------------------------------
# packed weight operand: serve/train from resident MXSF codes
# ---------------------------------------------------------------------------

def _layer_qt(qt: B.QuantizedTensor) -> B.QuantizedTensor:
    """Re-align static metadata after ``lax.scan`` slices a stacked store.

    Scanning over a layer-stacked ``QuantizedTensor`` slices the codes /
    scales arrays but rebuilds the dataclass with the stacked static
    ``shape``; drop the consumed leading dims so ``dequantize`` crops and
    ``transpose_qt`` swaps the right axes.
    """
    drop = len(qt.shape) - qt.codes.ndim
    if drop <= 0:
        return qt
    return B.QuantizedTensor(qt.codes, qt.scale_e8m0, qt.fmt, qt.block,
                             tuple(qt.shape[drop:]), qt.dtype)


def _check_packed(policy: QuantPolicy, qw: B.QuantizedTensor):
    if len(qw.shape) != 2:
        raise ValueError(f"packed mx_dot weight must be 2D after layer "
                         f"slicing; got shape {qw.shape}")
    if not policy.enabled:
        return
    if qw.fmt != policy.fwd_fmt:
        raise ValueError(f"packed weight format {qw.fmt!r} != policy "
                         f"fwd_fmt {policy.fwd_fmt!r}; re-pack the store "
                         "for this policy")
    _, wblk = _pol_blocks(policy)
    if tuple(qw.block) != tuple(wblk):
        raise ValueError(f"packed weight block {tuple(qw.block)} != the "
                         f"policy's kernel layout {tuple(wblk)} "
                         f"(block_mode={policy.block_mode!r}); re-pack the "
                         "store for this policy")


def _qt_zero_cot(qt: B.QuantizedTensor) -> B.QuantizedTensor:
    """Symbolic-zero cotangent for a resident packed weight: uint8 codes
    and scales are non-differentiable, so their tangent dtype is float0."""
    zero = lambda a: np.zeros(np.shape(a), jax.dtypes.float0)
    return B.QuantizedTensor(zero(qt.codes), zero(qt.scale_e8m0), qt.fmt,
                             qt.block, qt.shape, qt.dtype)


def _fused_packed(policy: QuantPolicy, xm, qw: B.QuantizedTensor,
                  tp_in: bool):
    """The fused kernel against resident codes; under a mesh context it
    runs shard-local (``sharding.shard_local``): rows over the DP axes and
    the weight's output dim over TP, or with ``tp_in`` (row-parallel
    weights: wo, wd) its contraction dim over TP — in whole MX blocks —
    and the partial products summed over TP."""
    from ..kernels import ops as K
    xblk, wblk = _pol_blocks(policy)
    call = lambda x, c, s: K.mxsf_fused_matmul(x, c, s, xblk, wblk,
                                               emit_codes=False)
    if shd.active() is None:
        return call(xm, qw.codes, qw.scale_e8m0)
    P = jax.sharding.PartitionSpec
    rows = shd.split_axes(xm.shape[0], "batch")
    if not tp_in:
        tp = shd.split_axes(qw.scale_e8m0.shape[1], "hidden")
        return shd.shard_local(call, (P(rows, None), P(None, tp),
                                      P(None, tp)),
                               P(rows, tp))(xm, qw.codes, qw.scale_e8m0)
    tp = shd.split_axes(qw.scale_e8m0.shape[0], "hidden")
    kw = qw.codes.shape[0]
    if xm.shape[1] < kw:  # split x's K exactly like the block-padded codes
        xm = jnp.pad(xm, ((0, 0), (0, kw - xm.shape[1])))

    def local(x, c, s):
        y = call(x, c, s)
        return y if tp is None else jax.lax.psum(y, tp)

    return shd.shard_local(local, (P(rows, tp), P(tp, None), P(tp, None)),
                           P(rows, None))(xm, qw.codes, qw.scale_e8m0)


def _packed_fwd(policy: QuantPolicy, xm, qw: B.QuantizedTensor,
                tp_in: bool = False):
    """Forward against resident codes: ZERO weight-quantize dispatches."""
    k, n = qw.shape
    if policy.use_pallas and xm.shape[0] > 0 and k > 0 and n > 0:
        _tick()  # x quantized on the fly; w codes are resident, no dispatch
        y = _fused_packed(policy, xm, qw, tp_in)
        return y[:, :n].astype(jnp.result_type(xm.dtype, qw.dtype))
    wq = B.dequantize(qw)
    if not policy.enabled:
        return jnp.matmul(xm, wq.astype(xm.dtype))
    if policy.block_mode == "2d":
        xq = _qdq(xm, policy.fwd_fmt, (policy.tile, policy.tile))
    else:
        xq = _qdq(xm, policy.fwd_fmt, (policy.block_1d,))
    return jnp.matmul(xq, wq)


def _jnp_packed_dx(policy: QuantPolicy, qw: B.QuantizedTensor, gm):
    if policy.block_mode == "2d":
        blk = (policy.tile, policy.tile)
        gq = _qdq(gm, policy.bwd_fmt, blk) if policy.quantize_bwd else gm
        return jnp.matmul(gq, B.dequantize(B.transpose_qt(qw)))
    b = policy.block_1d
    g_for_dx = (_qdq(gm, policy.bwd_fmt, (b,)) if policy.quantize_bwd
                else gm)
    w_re = _qdq(B.dequantize(qw), policy.fwd_fmt, (1, b))
    return jnp.matmul(g_for_dx, w_re.T)


def _pallas_packed_dx(policy: QuantPolicy, qw: B.QuantizedTensor, gm):
    """dx against the resident store — the same shared dx halves as the
    raw-weight backward, minus any dw work (packed weights are frozen)."""
    m = gm.shape[0]
    k, _ = qw.shape
    gm = gm.astype(jnp.float32)
    if policy.block_mode == "2d":
        dx, _ = _pallas_dx_2d(policy, qw, gm)
    else:
        dx = _pallas_dx_1d(policy, qw, gm)
    return dx[:m, :k]


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mx_dot_packed(policy: QuantPolicy, tp_in: bool, x: jax.Array,
                   qw: B.QuantizedTensor) -> jax.Array:
    xm, lead = _flatten_lead(x)
    y = _packed_fwd(policy, xm, qw, tp_in)
    return y.reshape(*lead, qw.shape[-1])


def _mx_dot_packed_fwd(policy: QuantPolicy, tp_in: bool, x, qw):
    # the residual IS the resident store: no activation codes are emitted
    # (packed weights are frozen -> dw is a symbolic zero -> x is unused)
    xm, lead = _flatten_lead(x)
    y = _packed_fwd(policy, xm, qw, tp_in)
    return y.reshape(*lead, qw.shape[-1]), qw


def _mx_dot_packed_bwd(policy: QuantPolicy, tp_in: bool, qw, g):
    gm, lead = _flatten_lead(g)
    k = qw.shape[0]
    if policy.use_pallas and gm.shape[0] > 0 and gm.shape[1] > 0 and k > 0:
        dx = _pallas_packed_dx(policy, qw, gm)
    elif policy.enabled:
        dx = _jnp_packed_dx(policy, qw, gm)
    else:
        dx = jnp.matmul(gm, B.dequantize(qw).astype(gm.dtype).T)
    return (dx.reshape(*lead, k).astype(g.dtype), _qt_zero_cot(qw))


_mx_dot_packed.defvjp(_mx_dot_packed_fwd, _mx_dot_packed_bwd)


def mx_dot(x: jax.Array, w, policy: QuantPolicy,
           tp_in: bool = False) -> jax.Array:
    """Quantized ``x @ w`` (x: (..., K), w: (K, N)) per the MX policy.

    ``w`` may be a raw array (quantized per call) or a resident
    ``blocking.QuantizedTensor`` from the pack-once store
    (``core/packed_store.py``) — the packed path performs zero
    weight-quantize dispatches and treats the weight as frozen (its
    cotangent is a symbolic zero).  ``tp_in`` marks a row-parallel weight
    (contraction dim over the TP axis, ``launch/mesh.py``): the kernel
    datapath under a mesh then sums partial products over TP.
    """
    if isinstance(w, B.QuantizedTensor):
        qw = _layer_qt(w)
        _check_packed(policy, qw)
        return _mx_dot_packed(policy, bool(tp_in), x, qw)
    if not policy.enabled:
        return jnp.matmul(x, w)
    return _mx_dot(policy, x, w)


# ---------------------------------------------------------------------------
# mx_einsum: generic two-operand quantized einsum (attention matmuls)
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _mx_einsum(subs, policy, axes, g_axes, quant_ops, a, b):
    y, _ = _mx_einsum_fwd(subs, policy, axes, g_axes, quant_ops, a, b)
    return y


def _mx_einsum_fwd(subs, policy: QuantPolicy, axes, g_axes, quant_ops, a, b):
    qa = qdq_along(a, policy.fwd_fmt, policy, axes[0]) if quant_ops[0] else a
    qb = qdq_along(b, policy.fwd_fmt, policy, axes[1]) if quant_ops[1] else b
    return jnp.einsum(subs, qa, qb), (qa, qb)


def _mx_einsum_bwd(subs, policy: QuantPolicy, axes, g_axes, quant_ops, res, g):
    qa, qb = res
    f = lambda a_, b_: jnp.einsum(subs, a_, b_)
    _, vjp = jax.vjp(f, qa, qb)
    if policy.quantize_bwd:
        # hardware re-quantizes g along each backward contraction dim
        da = vjp(qdq_along(g, policy.bwd_fmt, policy, g_axes[0]))[0]
        db = vjp(qdq_along(g, policy.bwd_fmt, policy, g_axes[1]))[1]
    else:
        da, db = vjp(g)
    return da, db


_mx_einsum.defvjp(_mx_einsum_fwd, _mx_einsum_bwd)


def mx_einsum(subs: str, a: jax.Array, b: jax.Array, policy: QuantPolicy,
              axes: Tuple[int, int] = (-1, -1),
              g_axes: Tuple[int, int] = (-1, -2),
              quant_ops: Tuple[bool, bool] = (True, True)) -> jax.Array:
    """Two-operand einsum with MX-quantized operands (and gradients).

    ``axes``  : contraction axis of each forward operand (-1 or -2), used to
                orient the 1D quantization blocks.
    ``g_axes``: contraction axis of the incoming gradient for (da, db).
    ``quant_ops``: per-operand quantization; False marks an operand that is
                ALREADY quantized (e.g. a dequantized MXSF KV cache read —
                the accelerator feeds cache codes straight into the MAC).
    """
    if not policy.enabled or not policy.attn_matmuls:
        return jnp.einsum(subs, a, b)
    return _mx_einsum(subs, policy, tuple(axes), tuple(g_axes),
                      tuple(quant_ops), a, b)
