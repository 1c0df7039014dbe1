"""GPipe-style pipeline parallelism over the ``pod`` axis (beyond-paper).

At 1000+-node scale the cross-pod links (DCN) are an order of magnitude
slower than in-pod ICI, so FSDP across pods is wasteful; the standard answer
is pipeline stages at pod granularity.  This module implements a GPipe
schedule with ``shard_map`` + ``ppermute``:

  * layers are split into S contiguous stages, one per pod-axis index
  * a microbatch stream flows stage->stage via collective_permute
  * the bubble is the classic (S-1)/(S-1+M) fraction

Works for any stack of homogeneous scanned layers (the ``decoder``/``ssm``
families).  Used by the multi-pod demo test and available to launch/train.py
via ``--pipeline``.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_apply"]


def pipeline_apply(mesh, stage_axis: str, layer_fn: Callable,
                   stage_params, x_microbatches):
    """Run ``layer_fn(params, x) -> x`` as a GPipe pipeline.

    stage_params : pytree stacked on a leading stage dim (S, ...) — sharded
                   over ``stage_axis`` so each pod holds only its stage.
    x_microbatches : (M, mb, ...) microbatch stream (replicated over the
                   stage axis; realistic ingestion feeds stage 0 only).
    Returns (M, mb, ...) outputs after all S stages.
    """
    S = mesh.shape[stage_axis]
    M = x_microbatches.shape[0]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(stage_axis), P()),
             out_specs=P(stage_axis))
    def run(params_stage, xs):
        # params_stage: (1, ...) local stage params; xs: (M, mb, ...)
        local = jax.tree.map(lambda p: p[0], params_stage)
        idx = jax.lax.axis_index(stage_axis)
        n_ticks = M + S - 1
        perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            buf, outs = carry           # buf: (mb, ...) current stage input
            # stage 0 ingests microbatch t (if in range), others take buf
            take = jnp.clip(t, 0, M - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, take, 0, keepdims=False)
            x_in = jnp.where(idx == 0, inject, buf)
            y = layer_fn(local, x_in)
            # last stage emits finished microbatch t-(S-1)
            out_t = t - (S - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(out_t, 0, M - 1), 0)
            outs = jnp.where((out_t >= 0) & (idx == S - 1), upd, outs)
            # hand off to the next stage
            buf_next = jax.lax.ppermute(y, stage_axis, perm)
            return (buf_next, outs), None

        # carries become device-varying after the first ppermute
        buf0 = jax.lax.pvary(jnp.zeros_like(xs[0]), stage_axis)
        outs0 = jax.lax.pvary(jnp.zeros_like(xs), stage_axis)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
        return outs

    stacked = run(stage_params, x_microbatches)  # (S*M, mb, ...)
    return stacked[(S - 1) * M:]  # only the last stage's buffer is real
