"""Continuous-batching serving engine (vLLM-style slot manager, CPU-scale).

A fixed pool of batch slots shares two jitted entry points compiled for
static shapes — ``decode_step`` (one token per slot) and ``prefill_step``
(one C-token prompt chunk per slot) — so each slot carries its OWN position
((B,) position vectors: per-sequence cache columns and rope phases) and its
own phase:

  * **prefill phase** — the slot still has queued prompt tokens.  Chunked
    prefill drains them C at a time: a P-token prompt costs ceil(P/C)
    prefill dispatches instead of P single-token ticks, with every linear
    running the fused MXSF quantize→matmul over C rows and all C cache
    columns written in one dispatch (one packed-KV attention kernel call
    per layer covers the whole chunk).
  * **decode phase** — the prompt is consumed; the slot feeds back its last
    sampled token one position per tick.

Mixed-phase scheduling: each tick issues (up to) one decode dispatch for
the decode-phase slots and one prefill dispatch for the prefill-phase
slots.  Both dispatches carry the full static batch; slots in the *other*
phase are masked — in the prefill dispatch by ``n_valid=0`` (cache writes
dropped, logits ignored), in the decode dispatch by discarding the sampled
token (the stale column a masked slot writes at its position is overwritten
by its own prefill chunk in the same tick, before anything can attend to
it).  Finished requests free their slot; idle/stale slots stay harmless: a
slot's cache rows are only ever read by its own attention, and its next
real step overwrites each column before reading it.

``prefill_chunk=1`` falls back to the original token-by-token schedule
(prompt tokens ride the decode dispatch — one dispatch per tick total).
MoE configs always take that fallback: expert capacity is sized per
dispatch, so a C-token chunk could drop tokens the one-token path routes,
breaking exact parity with sequential decode.

Generation stops at ``max_new`` tokens, a full cache, or the request's
``eos_id`` (the EOS token is kept in ``Request.out``).

**Sharded serving** (``mesh=...``): the engine places the pack-once store
(packed-layout ``MeshRules``: codes + shared-exponent scales split
together, uneven dims replicate), shards the packed KV cache slot-batch
over the DP axes and kv-heads over the TP axis, and jits both entry
points with explicit in/out shardings, traced under
``sharding.mesh_context`` so the role constraints in ``models/blocks.py``
resolve to mesh axes and the Pallas kernels run shard-local
(``sharding.shard_local``).  A sharded engine is token-for-token
identical to the single-device one (asserted across mesh shapes in
tests/test_sharded_serving.py).  Kernel gates are re-checked
per shard: a layout the flash-attention kernel cannot consume shard-local
falls back to the jnp path for this engine only, recorded in
``shard_fallback``.  ``stats()`` reports dispatch counts, occupancy and
per-device store/cache bytes; ``from_checkpoint`` restores a packed
checkpoint per-shard without ever materializing full-precision weights.

**Tracing**: each tick writes host spans into the JAX profiler's trace
(``engine.tick`` > ``engine.admit``, ``engine.decode`` / ``engine.prefill``
> ``.pack``, ``.dispatch``, ``.sync``, ``engine.emit``) with their counts
as span arguments, and the steps compile as ``jit_serve_decode_step`` and
``jit_serve_prefill_step``.  Outside a ``jax.profiler.trace`` a span costs
about a microsecond of host time; README "Tracing a serving engine".

Scope: attention-cache families (``decoder``).  SSM/hybrid recurrent state
advances unconditionally per step, so continuous batching for those needs
per-slot state checkpointing — a ROADMAP open item.

Tested against sequential generation in tests/test_serve_engine.py and
tests/test_chunked_prefill.py.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from collections import deque
from typing import Callable, Deque, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs.base import ModelConfig
from ..core import packed_store
from ..core import sharding as shd
from ..core.blocking import QuantizedTensor
from ..core.policy import QuantPolicy
from ..kernels import ops as kernel_ops
from ..launch import mesh as mesh_lib
from ..models import model as M

__all__ = ["Request", "ServeEngine", "auto_prefill_chunk"]


def auto_prefill_chunk(max_len: int, slots: int) -> int:
    """Resolve ``prefill_chunk="auto"``: pick C from the engine shape.

    C trades dispatch count (a P-token prompt costs ceil(P/C) prefill
    dispatches) against per-chunk latency and VMEM: a prefill dispatch
    runs ``slots * C`` rows through every linear, so the chunk that fills
    one fused-matmul M tile (256 rows, the kernels/ops.py default)
    across the slot batch saturates the kernel without growing the
    working set — and a full-length prompt should still drain in >= 4
    chunks so mixed-phase ticks keep interleaving decode work.  Integer
    ``prefill_chunk`` values bypass this and keep exact manual behavior.
    """
    c = max(1, min(max_len // 4, 256 // max(slots, 1)))
    c = 1 << (c.bit_length() - 1)  # round down to a tile-friendly pow2
    return max(1, min(c, max_len))


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new: int
    eos_id: Optional[int] = None
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # host clock (time.perf_counter) at submit: queue wait at admission
    submitted: float = dataclasses.field(default_factory=time.perf_counter)


class ServeEngine:
    """Fixed-slot continuous batching over prefill_step + decode_step."""

    def __init__(self, cfg: ModelConfig, params, policy: QuantPolicy,
                 slots: int = 4, max_len: int = 256,
                 sampler: Optional[Callable] = None,
                 backend: Optional[str] = None,
                 pack_weights: Optional[bool] = None,
                 prefill_chunk: Union[int, str] = 16,
                 eos_id: Optional[int] = None,
                 mesh=None):
        if cfg.family != "decoder":
            raise NotImplementedError(
                "continuous batching needs per-slot recurrent-state "
                "checkpointing for SSM/hybrid families")
        if backend is not None:
            # route the linear layers through the Pallas kernel datapath
            # (fused quantize->matmul, packed weights; see core/mx_dot.py);
            # validates eagerly so a bad combo fails at engine construction
            policy = policy.replace(backend=backend)
            _ = policy.use_pallas
        self.cfg = cfg
        # -- mesh placement (sharded serving) -----------------------------
        # mesh=None keeps the single-host engine bit-identical.  With a
        # mesh, the layout contract is: slot batch over the DP ("data")
        # axes, kv heads over the TP ("model") axis for the packed KV
        # cache, and the pack-once store sharded by the packed-layout
        # MeshRules (codes and shared-exponent scales split together;
        # uneven dims replicate) — docs/ARCHITECTURE.md §10.
        self.mesh = mesh
        self.rules = mesh_lib.MeshRules(mesh) if mesh is not None else None
        # cache precision follows the model's compute dtype — init_cache's
        # bf16 default silently downcast K/V under float32 configs and made
        # batched decode diverge from the sequential reference
        self.cache = M.init_cache(cfg, slots, max_len,
                                  dtype=jnp.dtype(cfg.compute_dtype),
                                  ring=False, kv_fmt=policy.kv_cache_fmt)
        self._cache_sh = None
        if self.rules is not None:
            self._cache_sh = mesh_lib.cache_shardings(self.rules, self.cache,
                                                      slots)
        # per-shard half of the attention-kernel gate: a cache layout the
        # flash kernel cannot consume shard-local (position axis sharded =
        # sequence parallelism) downgrades THIS engine to the jnp path —
        # recorded in shard_fallback like attn_backend records the static
        # gate, so deployments can see why the fast path disengaged
        self.shard_fallback: Optional[str] = None
        if (self.rules is not None
                and M.decode_attn_backend(cfg, policy) == "pallas-packed"
                and M.cache_position_axis_sharded(self._cache_sh)):
            policy = policy.replace(pallas_attention=False)
            self.shard_fallback = (
                "cache position axis sharded (sequence-parallel fallback "
                "layout): packed-attention kernel cannot run shard-local, "
                "using the jnp cached-attention path")
        # pack-once weight store (default for quantizing policies): the
        # whole weight pytree is cast to resident MXSF codes HERE, so decode
        # steps perform zero weight-quantize dispatches and the caller can
        # drop the full-precision params — the store is ~2x smaller than
        # bf16 weights, ~4x smaller than f32 (self.store_nbytes reports it)
        can_pack = packed_store.packable_policy(policy)
        if pack_weights and not can_pack:
            raise ValueError(
                "pack_weights=True needs a quantizing policy with a real "
                f"element format; got block_mode={policy.block_mode!r}, "
                f"fwd_fmt={policy.fwd_fmt!r}")
        self.packed = can_pack and (pack_weights is None or pack_weights)
        if self.packed:
            params = M.pack_model_params(cfg, params, policy)
        self._store_sh = None
        if self.rules is not None:
            self._store_sh = self.rules.param_sharding_tree(params)
            # per-shard half of the matmul-kernel gate: every sharded
            # packed leaf must keep whole MX blocks per shard.  Specs
            # derived by MeshRules satisfy this by construction (uneven
            # scale grids replicate), so this is a defensive check — but
            # if it ever fails, the engine falls back to the jnp matmul
            # path per-config rather than feeding the kernels torn blocks.
            if policy.use_pallas and not self._store_blocks_aligned(params):
                policy = policy.replace(backend="jnp")
                self.shard_fallback = (
                    (self.shard_fallback + "; ") if self.shard_fallback
                    else "") + (
                    "packed store sharding tears MX blocks per shard: "
                    "falling back to the jnp matmul path")
            params = jax.device_put(params, self._store_sh)
            self.cache = jax.device_put(self.cache, self._cache_sh)
        self.params = params
        self.store_nbytes = packed_store.store_nbytes(params)
        # which cached-attention datapath this engine's policy selects
        # (decode steps and prefill chunks share the gate):
        # 'pallas-packed' = flash kernel over the packed MXSF cache codes,
        # 'jnp' = dequantize + mx_einsum (see models/model.py)
        self.attn_backend = M.decode_attn_backend(cfg, policy,
                                                  self._cache_sh)
        self.policy = policy
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.sampler = sampler or (lambda logits: jnp.argmax(logits, -1))
        self.pos = np.zeros(slots, np.int32)
        self.live: List[Optional[Request]] = [None] * slots
        # deques: admission pops the queue head and prefill pops up to one
        # chunk of prompt tokens per tick — list.pop(0) made both O(n)
        self.pending_prompt: List[Deque[int]] = [deque() for _ in range(slots)]
        self.queue: Deque[Request] = deque()
        self.last_tok = np.zeros(slots, np.int32)
        # chunked prefill: C clamps to the cache width (a chunk is one
        # contiguous dynamic_update-sized write) and collapses to 1 for MoE
        # configs (see module docstring: per-dispatch expert capacity);
        # "auto" sizes C from the engine shape + measured bench rows
        if prefill_chunk == "auto":
            chunk = auto_prefill_chunk(max_len, slots)
        elif isinstance(prefill_chunk, str):
            raise ValueError(f"prefill_chunk={prefill_chunk!r}: expected an "
                             "int or 'auto'")
        else:
            chunk = max(1, min(int(prefill_chunk), max_len))
        if cfg.n_experts > 0:
            chunk = 1
        self.prefill_chunk = chunk
        # jitted entry points; under a mesh both carry explicit in/out
        # shardings (store + cache stay put, token/position/logit batches
        # split over DP) and are traced inside sharding.mesh_context so the
        # role constraints in models/blocks.py resolve to mesh axes.  Their
        # names name the compiled modules (jit_serve_decode_step,
        # jit_serve_prefill_step) in a profiler trace.
        def serve_decode_step(p, t, c, pos):
            return M.decode_step(p, t, c, pos, cfg, policy)

        def serve_prefill_step(p, t, c, pos, nv):
            return M.prefill_step(p, t, c, pos, nv, cfg, policy)

        # (activation tiles converted, grid steps) of each step's fused
        # matmuls, recorded when the step is traced
        self._converts = {}
        step = self._traced("decode", serve_decode_step)
        pre = self._traced("prefill", serve_prefill_step)
        if self.rules is None:
            self._decode = jax.jit(step)
            self._prefill = jax.jit(pre) if chunk > 1 else None
        else:
            r = self.rules
            tok = r.named(r.data_spec((slots, 1)))
            vec = r.named(r.data_spec((slots,)))
            logit = r.named(r.data_spec((slots, max(cfg.padded_vocab, 1))))
            self._decode = jax.jit(
                step,
                in_shardings=(self._store_sh, tok, self._cache_sh, vec),
                out_shardings=(logit, self._cache_sh))
            self._prefill = None
            if chunk > 1:
                ptok = r.named(r.data_spec((slots, chunk)))
                self._prefill = jax.jit(
                    pre,
                    in_shardings=(self._store_sh, ptok, self._cache_sh,
                                  vec, vec),
                    out_shardings=(logit, self._cache_sh))
        # dispatch accounting (asserted in tests: a P-token prompt costs
        # ceil(P/C) prefill dispatches, and neither entry point retraces
        # across prompt lengths)
        self.prefill_dispatches = 0
        self.decode_dispatches = 0
        self.tokens_generated = 0
        # rows handed to the steps (slots per decode dispatch, slots x C
        # per prefill dispatch) and rows that did work (decode-phase slots,
        # prompt tokens prefilled); also the engine.decode/engine.prefill
        # spans' counters
        self.rows_computed = 0
        self.rows_useful = 0
        self._live_slot_ticks = 0
        self._uid = 0
        self.ticks = 0

    def _store_blocks_aligned(self, params) -> bool:
        """Kernel-gate check: every sharded packed leaf keeps whole MX
        blocks per shard (see core/packed_store.shard_block_aligned)."""
        axis_sizes = dict(self.mesh.shape)
        is_qt = lambda x: isinstance(x, QuantizedTensor)
        leaves = jax.tree_util.tree_leaves(params, is_leaf=is_qt)
        shs = jax.tree_util.tree_leaves(self._store_sh, is_leaf=is_qt)
        for leaf, sh in zip(leaves, shs):
            if isinstance(leaf, QuantizedTensor) and \
                    not packed_store.shard_block_aligned(
                        leaf, sh.codes.spec, axis_sizes):
                return False
        return True

    def _traced(self, phase: str, fn):
        """``fn`` traced inside ``sharding.mesh_context`` under a mesh, so
        the role hints in models/blocks.py resolve to mesh axes and the
        Pallas kernels run shard-local however the step is lowered
        (dispatch or ahead of time).  Each trace also records the fused
        matmul's activation-tile conversions and grid steps in the step
        (``lhs_convert_share`` in ``stats()``)."""
        mesh = self.mesh
        scope = contextlib.nullcontext
        if self.rules is not None:
            dp, tp = self.rules.dp, self.rules.tp
            scope = lambda: shd.mesh_context(mesh, dp, tp)

        @functools.wraps(fn)
        def traced(*args):
            c0 = kernel_ops.fused_lhs_converts()
            with scope():
                out = fn(*args)
            self._converts[phase] = [
                b - a for a, b in zip(c0, kernel_ops.fused_lhs_converts())]
            return out

        return traced

    @classmethod
    def from_checkpoint(cls, cfg: ModelConfig, ckpt_dir: str,
                        policy: QuantPolicy, *, mesh=None,
                        step: Optional[int] = None,
                        backend: Optional[str] = None, **engine_kw):
        """Build a serving engine straight from a packed checkpoint.

        The restore target comes from ``models/model.packed_model_specs``
        (an eval_shape of init+pack: full-precision weights are never
        materialized, host or device) and, under a mesh, every leaf is
        restored per-shard onto its serving sharding from
        ``MeshRules.param_sharding_tree`` — each device receives only its
        own slice of the uint8 codes/scales.
        """
        from ..ckpt import ckpt as ckpt_lib
        pol = policy if backend is None else policy.replace(backend=backend)
        specs = M.packed_model_specs(cfg, pol)
        shardings = None
        if mesh is not None:
            shardings = mesh_lib.MeshRules(mesh).param_sharding_tree(specs)
        params, _ = ckpt_lib.restore(ckpt_dir, specs, step=step,
                                     shardings=shardings)
        return cls(cfg, params, policy, mesh=mesh, backend=backend,
                   **engine_kw)

    def stats(self) -> dict:
        """Engine observability: cumulative counters plus live memory
        placement — the dict deployments eyeball to compare sharded vs
        single-device runs (tests assert the accounting).

        * ``tokens_generated`` — tokens emitted into ``Request.out``.
        * ``prefill_dispatches`` / ``decode_dispatches`` / ``ticks`` — the
          dispatch accounting the chunked-prefill tests pin.
        * ``rows_computed`` / ``rows_useful`` — rows the steps computed
          (``slots`` per decode dispatch, ``slots * prefill_chunk`` per
          prefill dispatch) and rows that did work (decode-phase slots,
          prompt tokens prefilled): the sums of the counters on the
          ``engine.decode`` / ``engine.prefill`` trace spans.
        * ``occupancy`` — mean fraction of slots holding a live request
          over all ticks so far (1.0 = the pool never idled).
        * ``store_nbytes`` / ``*_nbytes_per_device`` — pack-once store
          footprint and the per-device split of store and KV cache
          (replicated leaves count full-size on every device).
        * ``attn_backend`` / ``shard_fallback`` / ``mesh`` — which
          datapath engaged and why a kernel gate may have disengaged.
        * ``lhs_convert_share`` — per compiled step (``decode`` /
          ``prefill``), the activation tiles the fused matmuls convert
          over their grid steps: 1/(N/TN) for a call with N/TN output
          tiles, which all read one converted row block.  Each call site
          traced in the step counts once (a layer scan's body once,
          whatever its length).  Static per compiled shape; a step with no
          fused matmul (jnp backend) is left out.
        """
        denom = self.ticks * self.slots
        return {
            "tokens_generated": self.tokens_generated,
            "prefill_dispatches": self.prefill_dispatches,
            "decode_dispatches": self.decode_dispatches,
            "ticks": self.ticks,
            "rows_computed": self.rows_computed,
            "rows_useful": self.rows_useful,
            "occupancy": (self._live_slot_ticks / denom) if denom else 0.0,
            "live": sum(1 for r in self.live if r is not None),
            "queued": len(self.queue),
            "prefill_chunk": self.prefill_chunk,
            "attn_backend": self.attn_backend,
            "shard_fallback": self.shard_fallback,
            "mesh": dict(self.mesh.shape) if self.mesh is not None else None,
            "store_nbytes": dict(self.store_nbytes),
            "store_nbytes_per_device": shd.per_device_nbytes(self.params),
            "cache_nbytes_per_device": shd.per_device_nbytes(self.cache),
            "lhs_convert_share": {phase: conv / steps for phase, (conv, steps)
                                  in self._converts.items() if steps},
        }

    def submit(self, prompt: List[int], max_new: int,
               truncate: bool = False,
               eos_id: Optional[int] = None) -> Request:
        """Queue a prompt.  A prompt longer than the cache rejects (or, with
        ``truncate=True``, keeps the first ``max_len`` tokens): prefill
        writes one cache column per prompt token, so anything longer would
        run past the cache width and previously spun until ``max_ticks``
        writing out-of-bounds columns.  ``eos_id`` (default: the engine's)
        ends generation early when sampled; the EOS token stays in ``out``.
        """
        prompt = list(prompt)
        if len(prompt) > self.max_len:
            if not truncate:
                raise ValueError(
                    f"prompt length {len(prompt)} exceeds the engine cache "
                    f"(max_len={self.max_len}); pass truncate=True or size "
                    "the engine for the workload")
            prompt = prompt[: self.max_len]
        self._uid += 1
        req = Request(self._uid, prompt, max_new,
                      eos_id=self.eos_id if eos_id is None else eos_id)
        self.queue.append(req)
        return req

    def run(self, max_ticks: int = 100_000) -> List[Request]:
        finished: List[Request] = []
        while self.queue or any(self.live):
            # a plain span: on the TPU a StepTraceAnnotation left every
            # later tick ~3 ms slower once a trace had been taken
            with TraceAnnotation(
                    "engine.tick", step_num=self.ticks,
                    live=sum(1 for r in self.live if r is not None),
                    queued=len(self.queue)):
                self._admit()
                finished.extend(self._tick())
            self.ticks += 1
            if self.ticks >= max_ticks:
                break
        return finished

    # -- internals --------------------------------------------------------
    def _admit(self):
        with TraceAnnotation("engine.admit") as span:
            now = time.perf_counter()
            admitted, wait = 0, 0.0
            for s in range(self.slots):
                if self.live[s] is None and self.queue:
                    req = self.queue.popleft()
                    self.live[s] = req
                    self.pos[s] = 0
                    self.pending_prompt[s] = deque(req.prompt)
                    admitted += 1
                    wait = max(wait, now - req.submitted)
            span.set_metadata(admitted=admitted, queue_wait_ms=wait * 1e3)

    def _phase(self, name: str, computed: int, useful: int):
        """The span over one dispatch's half of a tick, carrying the rows
        handed to the step and the rows that did work (``stats()`` sums
        them)."""
        self.rows_computed += computed
        self.rows_useful += useful
        return TraceAnnotation(name, rows_computed=computed,
                               rows_useful=useful)

    def _sync(self, name: str, logits) -> np.ndarray:
        """Sample on the device and wait for the tokens on the host."""
        with TraceAnnotation(name):
            return np.asarray(self.sampler(logits))

    @contextlib.contextmanager
    def _emitting(self, done: List[Request]):
        """The span over a loop of ``_emit`` calls, counting the tokens it
        emitted and the requests it finished."""
        t0, d0 = self.tokens_generated, len(done)
        with TraceAnnotation("engine.emit") as span:
            yield
            span.set_metadata(emitted=self.tokens_generated - t0,
                              finished=len(done) - d0)

    def _emit(self, s: int, tok: int, done: List[Request]):
        """Record a generated token for slot ``s`` and retire the request
        when it hits max_new, a full cache, or its EOS."""
        req = self.live[s]
        req.out.append(tok)
        self.tokens_generated += 1
        self.last_tok[s] = tok
        if (len(req.out) >= req.max_new
                or self.pos[s] >= self.max_len
                or (req.eos_id is not None and tok == req.eos_id)):
            req.done = True
            done.append(req)
            self.live[s] = None

    def _tick(self) -> List[Request]:
        self._live_slot_ticks += sum(
            1 for r in self.live if r is not None)
        if self.prefill_chunk == 1:
            return self._tick_merged()
        done: List[Request] = []
        prefill_slots = [s for s in range(self.slots)
                         if self.live[s] is not None
                         and self.pending_prompt[s]]
        decode_slots = [s for s in range(self.slots)
                        if self.live[s] is not None
                        and not self.pending_prompt[s]]

        # decode dispatch first: a prefill-phase slot rides along masked
        # (its sampled token is discarded) and writes one stale column at
        # its position — which the prefill dispatch below then overwrites
        # with the chunk's first real token before anything attends to it.
        if decode_slots:
            with self._phase("engine.decode", self.slots, len(decode_slots)):
                with TraceAnnotation("engine.decode.dispatch"):
                    logits, self.cache = self._decode(
                        self.params,
                        jnp.asarray(self.last_tok)[:, None].astype(jnp.int32),
                        self.cache, jnp.asarray(self.pos))
                self.decode_dispatches += 1
                nxt = self._sync("engine.decode.sync", logits)
                with self._emitting(done):
                    for s in decode_slots:
                        self.pos[s] = min(self.pos[s] + 1, self.max_len)
                        self._emit(s, int(nxt[s]), done)

        # prefill dispatch: up to C prompt tokens per prefilling slot;
        # decode/idle slots are masked by n_valid=0 (their cache writes are
        # dropped inside blocks.attention, so the column the decode
        # dispatch just wrote stays intact)
        if prefill_slots:
            C = self.prefill_chunk
            useful = sum(min(C, len(self.pending_prompt[s]))
                         for s in prefill_slots)
            with self._phase("engine.prefill", self.slots * C, useful):
                with TraceAnnotation("engine.prefill.pack"):
                    toks = np.zeros((self.slots, C), np.int32)
                    nv = np.zeros(self.slots, np.int32)
                    for s in prefill_slots:
                        q = self.pending_prompt[s]
                        n = min(C, len(q))
                        for j in range(n):
                            toks[s, j] = q.popleft()
                        nv[s] = n
                with TraceAnnotation("engine.prefill.dispatch"):
                    logits, self.cache = self._prefill(
                        self.params, jnp.asarray(toks), self.cache,
                        jnp.asarray(self.pos), jnp.asarray(nv))
                self.prefill_dispatches += 1
                nxt = self._sync("engine.prefill.sync", logits)
                with self._emitting(done):
                    for s in prefill_slots:
                        self.pos[s] = min(self.pos[s] + int(nv[s]),
                                          self.max_len)
                        if not self.pending_prompt[s]:
                            # prompt fully consumed; the chunk's
                            # last-valid-token logits yield the first
                            # generated token
                            self._emit(s, int(nxt[s]), done)
        return done

    def _tick_merged(self) -> List[Request]:
        """Token-by-token fallback (prefill_chunk=1): every slot consumes
        either its next prompt token (prefill phase) or its last sampled
        token (decode phase) in ONE batched decode dispatch."""
        live = [s for s in range(self.slots) if self.live[s] is not None]
        prefilling = np.zeros(self.slots, bool)
        for s in live:
            prefilling[s] = bool(self.pending_prompt[s])
        # a tick that consumes any prompt token is a prefill dispatch (the
        # token-by-token path merges both phases into one dispatch)
        phase = "engine.prefill" if prefilling.any() else "engine.decode"
        done: List[Request] = []
        with self._phase(phase, self.slots, len(live)):
            toks = np.array(self.last_tok)
            for s in np.flatnonzero(prefilling):
                toks[s] = self.pending_prompt[s].popleft()
            with TraceAnnotation(phase + ".dispatch"):
                logits, self.cache = self._decode(
                    self.params, jnp.asarray(toks)[:, None].astype(jnp.int32),
                    self.cache, jnp.asarray(self.pos))
            if prefilling.any():
                self.prefill_dispatches += 1
            else:
                self.decode_dispatches += 1
            nxt = self._sync(phase + ".sync", logits)
            with self._emitting(done):
                for s in live:
                    # idle slots keep pos (their column is rewritten later);
                    # cap at the cache width: position max_len has no
                    # column, and an uncapped pos would keep a full-length
                    # request alive forever
                    self.pos[s] = min(self.pos[s] + 1, self.max_len)
                    if prefilling[s] and self.pending_prompt[s]:
                        continue  # still mid-prompt: nothing sampled
                    self._emit(s, int(nxt[s]), done)
        return done
