"""Public model API: init / forward / decode + ShapeDtypeStruct input specs.

``input_specs`` provides allocation-free stand-ins for every model input of
a given (arch x shape) cell — the dry-run lowers against these.  Modality
frontends ([audio]/[vlm]) are stubs per the assignment: precomputed
frame/patch embeddings appear directly in the specs.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeConfig
from ..core import packed_store
from ..core.policy import QuantPolicy
from . import decoding, transformer

init_params = transformer.init_params
forward = transformer.forward
init_cache = decoding.init_cache
decode_step = decoding.decode_step
prefill_step = decoding.prefill_step
prefill = decoding.prefill
pack_params = packed_store.pack_params          # generic pytree pass


def pack_model_params(cfg: ModelConfig, params, policy: QuantPolicy,
                      dtype=None):
    """Quantize the model's weight pytree ONCE into the serving format.

    On top of the generic ``core/packed_store.pack_params`` pass this
    handles the model-level concerns:

      * tied embeddings — injects a packed ``"head"`` (the transposed
        table quantized at pack time) so the LM head takes the
        zero-dispatch path while ``"emb"`` stays a gatherable value table;
      * encoder-decoder cross-attention — left in values (its prefill
        consumes raw ``wk``/``wv`` arrays when precomputing the cross KV);
      * cast-at-use — leaves are cast to ``cfg.compute_dtype`` before
        quantizing, matching ``blocks.dense``, so packed and per-call
        quantization are bit-identical.

    Idempotent: already-packed leaves pass through.
    """
    if not packed_store.packable_policy(policy):
        return params  # incl. bf16-passthrough fwd formats: no packed form
    dtype = jnp.dtype(cfg.compute_dtype) if dtype is None else jnp.dtype(dtype)
    params = dict(params)
    if cfg.tie_embeddings and "head" not in params and "emb" in params:
        params["head"] = packed_store.pack_leaf(params["emb"].T, policy,
                                                dtype)
    exclude = ("cross",) if cfg.family == "encdec" else ()
    return packed_store.pack_params(params, policy, dtype=dtype,
                                    exclude=exclude)


def packed_model_specs(cfg: ModelConfig, policy: QuantPolicy, dtype=None):
    """Abstract packed-param structure (ShapeDtypeStructs + static MX
    metadata) without materializing full-precision weights — the
    ``ckpt.restore`` target for a packed checkpoint."""
    return jax.eval_shape(lambda: pack_model_params(
        cfg, init_params(jax.random.PRNGKey(0), cfg), policy, dtype))


def decode_attn_backend(cfg: ModelConfig, policy: QuantPolicy,
                        cache_shardings=None) -> str:
    """Which datapath cached attention will take — decode steps AND prefill
    chunks share one gate (the kernel's q-side grid tiles over S, so the
    same predicate covers S=1 and S=C).

    * ``'pallas-packed'`` — the MXSF flash kernel consumes the packed cache
      codes directly (kernels/mxsf_attention.py; SAFE-MAC dataflow).
    * ``'jnp'`` — dequantize-the-cache + ``mx_einsum`` reference path
      (also the fallback for softcapped attention and SWA patterns, whose
      window masks need the jnp path's ring-aware slot->position math).

    ``cache_shardings`` (a NamedSharding tree for the cache pytree, from
    ``launch/mesh.cache_shardings``) adds the per-shard half of the gate:
    when the cache POSITION axis is sharded (sequence parallelism — the
    batch/kv dims could not absorb the mesh), each shard holds a slice of
    every sequence, and the flash kernel's per-row online softmax cannot
    run shard-local (it would need a cross-device m/l/acc combine).  Those
    layouts take the jnp path, whose einsums GSPMD partitions with the
    collectives in the right places.  Batch- and kv-head-sharded caches
    keep the kernel: per-shard rows are whole (batch x kv-head) sequences.

    Shares ``blocks.attn_kernel_eligible`` with the gate in
    ``blocks.attention`` (no drift); the serving engine records it so
    deployments can assert the fast path actually engaged.
    """
    from . import blocks
    if not blocks.attn_kernel_eligible(cfg, policy):
        return "jnp"
    if cache_shardings is not None and \
            cache_position_axis_sharded(cache_shardings):
        return "jnp"
    return "pallas-packed"


def cache_position_axis_sharded(cache_shardings) -> bool:
    """True when any KV-cache leaf shards its position/window axis (the
    ``W`` of ``(..., B, kv, W, dh)`` / ``(..., B, kv, W)``) — the one cache
    layout the packed flash-attention kernel cannot consume shard-local
    (see ``decode_attn_backend``)."""
    flat = jax.tree_util.tree_flatten_with_path(cache_shardings)[0]
    for path, ns in flat:
        name = str(getattr(path[-1], "key", path[-1])) if path else ""
        if name not in ("k", "v", "k_codes", "v_codes",
                        "k_scales", "v_scales"):
            continue
        spec = tuple(ns.spec)
        w_ax = -1 if name in ("k_scales", "v_scales") else -2
        if len(spec) >= -w_ax and spec[w_ax] is not None:
            return True
    return False


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def active_param_count(cfg: ModelConfig, params) -> int:
    """Active params per token (MoE: top_k + shared experts only)."""
    total = param_count(params)
    if not cfg.n_experts:
        return total
    expert_p = 3 * cfg.d_model * cfg.expert_ff  # gate/up/down per expert
    n_moe_layers = cfg.n_layers // cfg.moe_every
    inactive = n_moe_layers * (cfg.n_experts - cfg.top_k) * expert_p
    return total - inactive


def train_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict:
    """ShapeDtypeStructs for one train/prefill step's batch."""
    B, S = shape.global_batch, shape.seq_len
    specs = {}
    if cfg.family == "encoder":
        specs["embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
        specs["label"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        return specs
    specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if cfg.family == "encdec":
        specs["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
    if cfg.frontend == "vision" and cfg.frontend_tokens:
        specs["embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16)
    return specs


def decode_specs(cfg: ModelConfig, shape: ShapeConfig, ring: bool = True,
                 kv_fmt: str = "") -> Dict:
    """Specs for one serve_step: new token + KV/state cache at seq_len."""
    B = shape.global_batch
    cache = jax.eval_shape(
        lambda: decoding.init_cache(cfg, B, shape.seq_len, ring=ring,
                                    kv_fmt=kv_fmt))
    return {
        "tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "cache": cache,
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def cell_supported(cfg: ModelConfig, shape: ShapeConfig):
    """(supported, reason) for an (arch x shape) cell — DESIGN.md §5 rules."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("full-attention arch: 524k-token decode cache is the "
                       "quadratic regime the assignment skips")
    return True, ""
