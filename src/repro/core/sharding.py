"""Activation sharding hints, mesh-agnostic.

Model code never imports a mesh; it calls ``constrain(x, roles)`` with a
*role* per axis and the launcher installs a context that maps roles to mesh
axes (with divisibility guards).  Outside any context (CPU unit tests) the
hints are no-ops, so the model code runs anywhere.

Roles:
  'batch' -> DP axes        'heads'/'kv'/'experts'/'ff'/'hidden' -> TP axis
  'seq'   -> TP axis (context/sequence parallelism fallback when the head
             dim does not divide the TP axis)
  None    -> replicated
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P

_CTX: contextvars.ContextVar = contextvars.ContextVar("mx_mesh_ctx",
                                                      default=None)

TP_ROLES = ("heads", "kv", "experts", "ff", "hidden", "seq", "vocab")


@contextlib.contextmanager
def mesh_context(mesh, dp: Tuple[str, ...], tp: Optional[str]):
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    ctx = {
        "mesh": mesh,
        "dp": tuple(dp),
        "tp": tp,
        "dp_size": dp_size,
        "tp_size": mesh.shape.get(tp, 1) if tp else 1,
    }
    tok = _CTX.set(ctx)
    try:
        with mesh:
            yield ctx
    finally:
        _CTX.reset(tok)


def active() -> Optional[dict]:
    return _CTX.get()


def spec_for(shape: Sequence[int], roles: Sequence[Optional[str]],
             allow_uneven: Sequence[str] = ("experts",)) -> Optional[P]:
    """Build a PartitionSpec from per-dim roles; None when no context."""
    ctx = _CTX.get()
    if ctx is None:
        return None
    spec = []
    tp_used = False
    # first pass: batch -> dp
    for dim, role in zip(shape, roles):
        if role == "batch" and ctx["dp"] and dim % ctx["dp_size"] == 0:
            spec.append(ctx["dp"])
        else:
            spec.append(None)
    # second pass: first TP-eligible role that divides gets the TP axis
    if ctx["tp"]:
        for i, (dim, role) in enumerate(zip(shape, roles)):
            if spec[i] is not None or role not in TP_ROLES:
                continue
            if dim % ctx["tp_size"] == 0 or role in allow_uneven:
                spec[i] = ctx["tp"]
                tp_used = True
                break
    return P(*spec)


def split_axes(dim: int, role: str):
    """Mesh axes a dim of size ``dim`` splits over for ``role`` ('batch'
    -> the DP axes, any TP role -> the TP axis), or None when there is no
    context, no such axis, or it does not divide ``dim``."""
    ctx = _CTX.get()
    if ctx is None:
        return None
    if role == "batch":
        ok = ctx["dp"] and dim % ctx["dp_size"] == 0
        return ctx["dp"] if ok else None
    ok = ctx["tp"] and dim % ctx["tp_size"] == 0
    return ctx["tp"] if ok else None


def shard_local(fn, in_specs, out_specs):
    """``fn`` run once per shard under the active mesh (``jax.shard_map``).

    The TPU compiler cannot partition a Pallas kernel (a Mosaic custom
    call) the way GSPMD partitions XLA ops, so a kernel on a sharded step
    runs shard-local with explicit specs; operands laid out otherwise are
    resharded to them.  Without a context ``fn`` is returned as is."""
    ctx = _CTX.get()
    if ctx is None:
        return fn
    return jax.shard_map(fn, mesh=ctx["mesh"], in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def constrain(x: jax.Array, *roles: Optional[str]) -> jax.Array:
    """with_sharding_constraint by role; no-op without a mesh context."""
    spec = spec_for(x.shape, roles)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def per_device_nbytes(tree) -> dict:
    """Actual bytes resident per device for a pytree of live arrays.

    Sums ``addressable_shards`` sizes, so a sharded leaf counts each shard
    on its own device while a replicated leaf counts full-size everywhere —
    the number deployments eyeball to confirm a store/cache really split
    (``ServeEngine.stats()`` reports it).  Non-array leaves are skipped.
    """
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            continue
        for sh in shards:
            key = str(sh.device)
            out[key] = out.get(key, 0) + sh.data.nbytes
    return out
