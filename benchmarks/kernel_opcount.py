"""Element-ops of the fused MXSF matmul's vector work per grid step.

Counts, from the jaxpr of the functions ``kernels/mxsf_fused_matmul.py``
runs in its body, the elements every equation writes: the activation
converter on a (TM, TK) tile (block exponents, encode, decode, rescale)
and the weight decode on a (TK, TN) tile.  Shape-only equations
(reshape, broadcast, squeeze, slice) count nothing; every other equation
counts its output's elements, once.  Nested jaxprs (``pjit``,
``custom_jvp_call``) are walked.  It is a count of the vector work the
body asks for, not of the instructions Mosaic emits.

    PYTHONPATH=src python -m benchmarks.kernel_opcount [--tm 256 8]
"""
from __future__ import annotations

import argparse
import math

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from repro.kernels.common import (block_exponents, decode_mxsf, encode_mxsf,
                                  exp2i, expand_scales, scale_by_exp2)

SHAPE_ONLY = {"reshape", "broadcast_in_dim", "squeeze", "expand_dims",
              "slice", "concatenate", "transpose"}


def element_ops(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        subs = [v for v in eqn.params.values()
                if isinstance(v, (jex_core.Jaxpr, jex_core.ClosedJaxpr))]
        if subs:
            n += sum(element_ops(getattr(s, "jaxpr", s)) for s in subs)
            continue
        if eqn.primitive.name in SHAPE_ONLY:
            continue
        n += sum(math.prod(v.aval.shape) for v in eqn.outvars)
    return n


def converter(x, xblk):
    x = x.astype(jnp.float32)
    se, se_el = block_exponents(x, *xblk)
    codes = encode_mxsf(scale_by_exp2(x, -se_el))
    return decode_mxsf(codes) * exp2i(se_el)


def weight_decode(codes, scales, wblk):
    wse = scales.astype(jnp.int32) - 127
    return decode_mxsf(codes) * exp2i(expand_scales(wse, *wblk))


def count(tm: int, tk: int, tn: int, xblk=(1, 64), wblk=(64, 1)):
    """(activation converter, weight decode) element-ops of one step."""
    x = jax.ShapeDtypeStruct((tm, tk), jnp.bfloat16)
    wc = jax.ShapeDtypeStruct((tk, tn), jnp.uint8)
    ws = jax.ShapeDtypeStruct((tk // wblk[0], tn // wblk[1]), jnp.uint8)
    act = element_ops(jax.make_jaxpr(lambda a: converter(a, xblk))(x).jaxpr)
    wgt = element_ops(jax.make_jaxpr(
        lambda c, s: weight_decode(c, s, wblk))(wc, ws).jaxpr)
    return act, wgt


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tm", type=int, nargs="+", default=[256, 8])
    ap.add_argument("--tk", type=int, default=512)
    ap.add_argument("--tn", type=int, default=256)
    a = ap.parse_args()
    for tm in a.tm:
        act, wgt = count(tm, a.tk, a.tn)
        print(f"tm={tm} tk={a.tk} tn={a.tn}: converter {act} "
              f"({act / (tm * a.tk):.1f} per element), weight decode {wgt} "
              f"({wgt / (a.tk * a.tn):.1f} per element), converter share "
              f"{act / (act + wgt):.1%}")


if __name__ == "__main__":
    main()
