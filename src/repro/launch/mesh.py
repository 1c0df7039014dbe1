"""Production meshes + parameter/activation sharding rules.

Mesh axes:
  * single pod : (16, 16)    -> ("data", "model")   = 256 chips (v5e pod)
  * multi pod  : (2, 16, 16) -> ("pod", "data", "model") = 512 chips

Parallelism mapping (DESIGN.md §4):
  * DP   : batch over ("pod",) "data"
  * FSDP : parameters + optimizer moments sharded over the DP axes on a
           designated dim, all-gathered at use by GSPMD
  * TP   : heads / ffn hidden / vocab over "model"
  * EP   : MoE expert dim over "model"
  * SP   : long-context KV/state sequence dim over "data" (batch=1 cells)

All rules guard divisibility — a dim that does not divide its mesh axes is
replicated rather than unevenly sharded.  Packed (pack-once store) leaves
get layout-aware rules: codes and shared-exponent scales shard together,
judged on the scale grid (``packed_leaf_spec``; docs/ARCHITECTURE.md §10).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import packed_store
from ..core.blocking import QuantizedTensor

__all__ = ["make_production_mesh", "make_test_mesh", "MeshRules",
           "state_shardings", "batch_shardings", "cache_shardings"]


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 (dryrun.py "
            "sets this automatically)")
    import numpy as np
    return Mesh(np.asarray(devices[:n]).reshape(shape), axes)


def make_test_mesh(data: int = 2, model: int = 2) -> Mesh:
    """Small mesh from whatever devices exist (tests/examples).

    BOTH axes clamp to the device count — the old version clamped only
    ``data``, so a 1-device box with the default ``model=2`` raised from
    ``jax.make_mesh`` — and the floor is a (1, 1) mesh."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = max(1, min(data, n // model))
    return jax.make_mesh((data, model), ("data", "model"))


class MeshRules:
    """Sharding rule oracle bound to one mesh."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.dp: Tuple[str, ...] = tuple(
            a for a in ("pod", "data") if a in mesh.axis_names)
        self.tp: Optional[str] = "model" if "model" in mesh.axis_names else None
        self.dp_size = 1
        for a in self.dp:
            self.dp_size *= mesh.shape[a]
        self.tp_size = mesh.shape.get("model", 1)

    # -- helpers ---------------------------------------------------------
    def _dp(self, dim: int):
        return self.dp if self.dp and dim % self.dp_size == 0 else None

    def _tp(self, dim: int):
        return self.tp if self.tp and dim % self.tp_size == 0 else None

    def named(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # -- parameter rules ---------------------------------------------------
    def param_spec(self, name: str, shape: Tuple[int, ...]) -> P:
        dims = len(shape)
        if dims <= 1:
            # norms/bias vectors: TP if they match a TP-sharded activation dim
            if dims == 1 and shape[0] >= 1024:
                return P(self._tp(shape[0]))
            return P()
        if name == "emb":          # (V, d)
            return P(self._tp(shape[0]), self._dp(shape[1]))
        if name == "head":         # (d, V)
            return P(self._dp(shape[0]), self._tp(shape[1]))
        if name in ("wq", "wk", "wv", "wg", "wu", "in_proj"):
            return P(self._dp(shape[0]), self._tp(shape[1]))
        if name in ("wo", "wd", "out_proj"):
            return P(self._tp(shape[0]), self._dp(shape[1]))
        if name == "router":       # (d, E): replicate E (small)
            return P(self._dp(shape[0]), None)
        if name in ("we_g", "we_u", "we_d"):  # (E, d|f, f|d): EP + FSDP
            # expert dim sharded even when uneven (GSPMD pads): a 60-expert
            # table replicated 16x would cost ~100 GB/device on qwen2-moe
            return P(self.tp, self._dp(shape[1]), None)
        if name == "conv_w":
            return P(None, self._tp(shape[1]))
        if name == "pos":
            return P(None, None)
        # stacked-layer leading dims are handled by caller stripping them
        return P(*([None] * dims))

    def packed_leaf_spec(self, name: str, qt: QuantizedTensor) -> P:
        """Spec for a pack-once store leaf (``core/packed_store.py``).

        Derived from the f32 rule on the LOGICAL weight shape, then
        filtered through the packed-layout consistency check: codes and
        shared-exponent scales shard together, so a dim splits only when
        its scale grid divides the mesh axes (uneven grids replicate —
        same contract as the f32 divisibility guards)."""
        base_rank = _base_rank(name)
        lead = len(qt.shape) - base_rank
        spec = self.param_spec(name, qt.shape[lead:])
        base = P(*([None] * lead + list(spec)))
        return packed_store.packed_spec(qt, base, dict(self.mesh.shape))

    def param_sharding_tree(self, params_shapes):
        """Param tree -> NamedSharding tree (layer-stack aware).

        Accepts ShapeDtypeStruct trees, live array trees, and PACKED trees:
        a ``QuantizedTensor`` leaf maps to a QuantizedTensor carrying one
        NamedSharding for its codes and one for its scales (the same
        pytree structure jit/device_put expect for the packed store)."""

        def rule(path, leaf):
            name = None
            for pk in reversed(path):
                k = str(getattr(pk, "key", getattr(pk, "idx", pk)))
                if not k.isdigit():
                    name = k
                    break
            if isinstance(leaf, QuantizedTensor):
                ns = self.named(self.packed_leaf_spec(name, leaf))
                return QuantizedTensor(ns, ns, leaf.fmt, leaf.block,
                                       leaf.shape, leaf.dtype)
            shape = leaf.shape
            # strip stacked-layer leading dims: rules match trailing dims
            base_rank = _base_rank(name)
            lead = len(shape) - base_rank
            spec = self.param_spec(name, shape[lead:])
            full = P(*([None] * lead + list(spec)))
            return self.named(full)

        return jax.tree_util.tree_map_with_path(
            rule, params_shapes,
            is_leaf=lambda x: isinstance(x, QuantizedTensor))

    # -- activation/batch rules -----------------------------------------
    def data_spec(self, shape: Tuple[int, ...], batch_axis: int = 0) -> P:
        spec = [None] * len(shape)
        if shape[batch_axis] % self.dp_size == 0 and self.dp:
            spec[batch_axis] = self.dp
        return P(*spec)


_BASE_RANK = {
    "emb": 2, "head": 2, "wq": 2, "wk": 2, "wv": 2, "wo": 2, "wg": 2,
    "wu": 2, "wd": 2, "in_proj": 2, "out_proj": 2, "router": 2,
    "we_g": 3, "we_u": 3, "we_d": 3, "conv_w": 2, "pos": 2, "cls": 3,
    "w": 1, "b": 1, "bq": 1, "bk": 1, "bv": 1, "A_log": 1, "D": 1,
    "dt_bias": 1, "norm_w": 1, "conv_b": 1,
}


def _base_rank(name: str) -> int:
    return _BASE_RANK.get(name, 0)


def state_shardings(rules: MeshRules, state_shapes):
    """Shardings for {'params','opt'} train state (moments follow params)."""
    params = rules.param_sharding_tree(state_shapes["params"])
    out = {"params": params}
    if "opt" in state_shapes:
        out["opt"] = {
            "m": rules.param_sharding_tree(state_shapes["opt"]["m"]),
            "v": rules.param_sharding_tree(state_shapes["opt"]["v"]),
            "step": rules.named(P()),
        }
        if "master" in state_shapes["opt"]:
            out["opt"]["master"] = rules.param_sharding_tree(
                state_shapes["opt"]["master"])
    return out


def batch_shardings(rules: MeshRules, batch_shapes):
    """Token/label/frontend batches: shard dim 0 (global batch) over DP."""
    return jax.tree.map(
        lambda s: rules.named(rules.data_spec(s.shape)), batch_shapes)


def cache_shardings(rules: MeshRules, cache_shapes, batch_size: int):
    """KV/state caches.

    Batch dim is sharded over DP when divisible; otherwise (long_500k,
    batch=1) the *sequence/window* dim of attention caches is sharded over
    DP (sequence parallelism) and SSM states shard their head dim over TP.
    """

    def rule(path, leaf):
        shape = leaf.shape
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        name = keys[-1] if keys else ""
        # packed cache codes shard like the kv tensor; the scales are the
        # same (..., B, kv, W) without the trailing dh
        lead = len(shape) - (3 if name in ("k_scales", "v_scales") else 4)
        if name in ("k_codes", "v_codes", "k_scales", "v_scales"):
            name = "k"
        spec = [None] * len(shape)
        if name in ("k", "v"):
            # (..., B, kv, W, dh) — mirrors the _attend TP rule:
            # kv heads over TP when divisible, else cache length over TP
            b_ax, kv_ax, w_ax = lead, lead + 1, lead + 2
            w_axes = []
            if batch_size % rules.dp_size == 0 and rules.dp:
                spec[b_ax] = rules.dp
            elif shape[w_ax] % rules.dp_size == 0 and rules.dp:
                w_axes += list(rules.dp)  # SP over the cache sequence
            if rules.tp and shape[kv_ax] % rules.tp_size == 0:
                spec[kv_ax] = rules.tp
            elif rules.tp and shape[w_ax] % (rules.tp_size or 1) == 0:
                w_axes.append(rules.tp)
            if w_axes:
                spec[w_ax] = tuple(w_axes)
        elif name in ("state", "conv"):
            # state: (..., B, G, Hg, P, N) / conv: (..., B, K-1, C)
            nb = 5 if name == "state" else 3
            b_ax = len(shape) - nb
            if batch_size % rules.dp_size == 0 and rules.dp:
                spec[b_ax] = rules.dp
            if name == "state" and rules.tp:
                hg_ax = len(shape) - 3
                if shape[hg_ax] % rules.tp_size == 0:
                    spec[hg_ax] = rules.tp
            if name == "conv" and rules.tp:
                c_ax = len(shape) - 1
                if shape[c_ax] % rules.tp_size == 0:
                    spec[c_ax] = rules.tp
        return rules.named(P(*spec))

    return jax.tree_util.tree_map_with_path(rule, cache_shapes)
