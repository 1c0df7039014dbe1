"""Composable model blocks (pure JAX, param pytrees, no framework deps).

Every matmul that the paper's accelerator would execute goes through
``mx_dot`` / ``mx_einsum`` so the MXSF policy applies uniformly: QKV/O
projections, MLP, MoE experts, attention score/context matmuls.  Softmax,
norms, router and residual math stay in f32 (paper §I keeps these
dequantized).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core import sharding as shd
from ..core.blocking import QuantizedTensor
from ..core.mx_dot import mx_dot, mx_einsum, qdq_along
from ..core.policy import QuantPolicy


def _dense_init(key, d_in, d_out, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return jax.random.normal(key, (d_in, d_out), jnp.float32) * scale


def dense(x, w, policy, tp_in: bool = False):
    """mx_dot with cast-at-use: f32 master weights -> activation dtype.

    ``w`` may also be a resident packed weight (``QuantizedTensor``) from
    the pack-once store — those were cast to the compute dtype at pack time
    and mx_dot consumes the codes directly (zero weight-quantize
    dispatches).  ``tp_in`` marks the row-parallel weights (wo, wd,
    out_proj; see ``mx_dot``)."""
    if isinstance(w, QuantizedTensor):
        return mx_dot(x, w, policy, tp_in)
    return mx_dot(x, w.astype(x.dtype), policy)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d):
    return {"w": jnp.ones((d,), jnp.float32)}


def rmsnorm(p, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["w"]).astype(x.dtype)


def layernorm_init(d):
    return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["w"] + p["b"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D), positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, SWA, softcap) — shared by all transformer families
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig):
    d, dh, h, kv = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], d, h * dh),
        "wk": _dense_init(ks[1], d, kv * dh),
        "wv": _dense_init(ks[2], d, kv * dh),
        "wo": _dense_init(ks[3], h * dh, d),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), jnp.float32)
        p["bk"] = jnp.zeros((kv * dh,), jnp.float32)
        p["bv"] = jnp.zeros((kv * dh,), jnp.float32)
    return p


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _attn_mask_bias(qpos, kpos, *, causal: bool, window: Optional[int]):
    """Additive mask from broadcast position comparisons (no HBM mask)."""
    qp = qpos[:, :, None] if qpos is not None else None
    kp = kpos[:, None, :]
    allowed = kp >= 0  # negative kpos marks unwritten ring-cache slots
    if causal and qp is not None:
        allowed &= kp <= qp
    if window is not None and qp is not None:
        allowed &= kp > qp - window
    return jnp.where(allowed, 0.0, -1e30).astype(jnp.float32)


def attn_kernel_eligible(cfg: ModelConfig, policy: QuantPolicy) -> bool:
    """Static (cfg x policy) half of the packed-attention kernel gate.

    The dynamic half — cached causal self-attention (S=1 decode steps and
    S=C prefill chunks alike) — is checked at the call site in
    ``attention``.  Softcap and SWA patterns fall back: the kernel applies
    neither tanh capping nor the ring-aware slot->position window math
    (window-free causal decode stays correct under ring wrap because
    ``kv_len`` clamps to the cache width).
    ``models/model.py::decode_attn_backend`` reports this same predicate.
    """
    return (policy.use_pallas_attention and not cfg.attn_softcap
            and cfg.swa_pattern == "none")


def attention(p, x, cfg: ModelConfig, policy: QuantPolicy, *,
              positions=None, kv_positions=None, kv_x=None, kv_cached=None,
              causal=True, window=None, cache=None, cache_pos=None,
              cache_write_len=None):
    """Generalized attention.

    * self-attention train/prefill: ``kv_x=None, cache=None``
    * cross-attention: ``kv_x`` = encoder states (positions ignored for rope)
    * cross-attention decode: ``kv_cached`` = precomputed (k, v) dict
    * decode: ``cache`` = {k, v} ring/full buffers, ``cache_pos`` scalar step
    * chunked prefill: ``cache_pos`` a (B,) vector, ``cache_write_len`` a
      (B,) count of valid tokens in this S-token chunk — only cache columns
      ``pos..pos+len-1`` are written (rows past ``len`` are dropped, so a
      slot with ``len=0`` leaves its cache untouched; padded chunk tails and
      masked-out batch slots never corrupt neighbouring columns).  Queries
      past ``len`` produce garbage rows the caller must ignore.
    Returns (out, new_cache).
    """
    B, S, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv, cfg.head_dim
    g = h // kv

    q = dense(x, p["wq"], policy)
    if "bq" in p:
        q = (q + p["bq"]).astype(x.dtype)
    q = _split_heads(q, h, dh)
    if kv_cached is not None:  # (B, kv, L, dh) cache layout
        k = kv_cached["k"].astype(x.dtype).transpose(0, 2, 1, 3)
        v = kv_cached["v"].astype(x.dtype).transpose(0, 2, 1, 3)
        kpos = jnp.zeros((B, k.shape[1]), jnp.int32)
        with jax.named_scope("attention"):
            ctx = _attend(q, k, v, None, kpos, False, None, x, cfg, policy)
        return dense(ctx, p["wo"], policy, tp_in=True), None
    src = x if kv_x is None else kv_x
    k = dense(src, p["wk"], policy)
    v = dense(src, p["wv"], policy)
    if "bk" in p:
        k = (k + p["bk"]).astype(x.dtype)
        v = (v + p["bv"]).astype(x.dtype)
    k = _split_heads(k, kv, dh)
    v = _split_heads(v, kv, dh)

    use_rope = kv_x is None and cfg.rope_theta > 0 and cfg.family != "encdec"
    if use_rope:
        if cache is not None:
            pv = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (B,))
            positions = pv[:, None] + jnp.arange(S)[None, :]
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_positions if kv_positions is not None else positions,
                 cfg.rope_theta)
        # pin post-rope layout: without this GSPMD reshards the rope
        # elementwise chain ("involuntary full rematerialization" warnings)
        q = shd.constrain(q, "batch", None, "heads", None)
        k = shd.constrain(k, "batch", None, "kv", None)

    new_cache = None
    if cache is not None:
        # cache_pos may be a scalar (lockstep batch) or a (B,) vector of
        # per-sequence positions (continuous batching, serve/engine.py)
        pos_vec = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (B,))
        # per-slot buffers are (kv, W, dh) codes/values and (kv, W) scales:
        # positions on axis 1 of every leaf
        W = (cache["k_codes"] if "k_codes" in cache else cache["k"]).shape[2]
        slot = pos_vec % W

        if cache_write_len is None:
            def _write(buf, upd):
                return jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice(
                        c, u, (0, p) + (0,) * (c.ndim - 2))
                )(buf, upd, slot)
        else:
            # masked chunk write (prefill): scatter rows 0..len-1 onto
            # columns slot..slot+len-1; rows past len target column W and
            # are dropped, so padded chunk tails and len=0 slots leave the
            # cache bit-identical.  Non-wrapping like the slice path —
            # dynamic_update_slice would CLAMP an overhanging start and
            # silently shift the chunk onto live history columns, which is
            # exactly what a masked-out slot deep in its sequence would hit.
            wl = jnp.broadcast_to(jnp.asarray(cache_write_len, jnp.int32),
                                  (B,))
            cols = slot[:, None] + jnp.arange(S)[None, :]
            cols = jnp.where(jnp.arange(S)[None, :] < wl[:, None], cols, W)

            def _write(buf, upd):
                return jax.vmap(
                    lambda c, u, cc: c.at[:, cc].set(u, mode="drop")
                )(buf, upd, cols)

        # last absolute position actually WRITTEN this call: all S rows on
        # the slice path, only write_len on the masked-chunk path — counting
        # a partial chunk's padded tail here would push ``end`` past the
        # cache width and the ring math below would relabel the earliest
        # columns as future positions, causally masking real history away
        # from the chunk's valid queries
        if cache_write_len is None:
            end = pos_vec + S - 1                   # (B,)
        else:
            end = pos_vec + wl - 1                  # wl=0 -> pos-1: no-op
        idx = jnp.arange(W)
        # absolute position held by each ring slot (unwritten slots < 0)
        kpos = end[:, None] - ((end[:, None] - idx[None, :]) % W)
        qpos = pos_vec[:, None] + jnp.arange(S)[None, :]
    if cache is not None and "k_codes" in cache:
        # 8-bit MX-packed KV cache (policy.kv_cache_fmt): new k/v quantize
        # along dh; reads either feed the codes straight into the flash
        # kernel (pallas decode path below) or dequantize the whole cache.
        from ..core import blocking as mxblk
        fmt = policy.kv_cache_fmt or "mxsf"
        new_cache = dict(cache)
        for nm, val in (("k", k), ("v", v)):
            qt = mxblk.quantize(val.transpose(0, 2, 1, 3), fmt, (dh,))
            new_cache[f"{nm}_codes"] = _write(cache[f"{nm}_codes"], qt.codes)
            new_cache[f"{nm}_scales"] = _write(cache[f"{nm}_scales"],
                                               qt.scale_e8m0[..., 0])
        if attn_kernel_eligible(cfg, policy) and kv_x is None and causal:
            # cached causal self-attention through the flash kernel — S=1
            # decode steps AND S=C prefill chunks: it reads the 1-byte codes
            # directly, so no value-domain cache and no S x L score matrix
            # in HBM.  Chunk-internal causality rides the kernel's absolute
            # qpos/kpos comparison (q_offset = pos_vec), which also keeps
            # valid queries off any unwritten tail columns of a partial
            # chunk (kpos <= qpos < pos + write_len).
            with jax.named_scope("attention"):
                ctx = _attend_packed(q, new_cache, pos_vec, window, cfg,
                                     policy)
            return dense(ctx, p["wo"], policy, tp_in=True), new_cache
        with jax.named_scope("attention"):
            k, v = (mxblk.dequantize(mxblk.QuantizedTensor(
                new_cache[f"{nm}_codes"], new_cache[f"{nm}_scales"][..., None],
                fmt, (dh,), new_cache[f"{nm}_codes"].shape, str(x.dtype)
            )).transpose(0, 2, 1, 3) for nm in ("k", "v"))
    elif cache is not None:
        # ring buffer (B, kv, W, dh); contiguous non-wrapping writes only
        # (decode S=1 anywhere; prefill S>1 requires cache_pos=0, W >= S).
        ck = _write(cache["k"], k.transpose(0, 2, 1, 3).astype(
            cache["k"].dtype))
        cv = _write(cache["v"], v.transpose(0, 2, 1, 3).astype(
            cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        k, v = ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3)
    else:
        qpos = jnp.broadcast_to(positions if positions.ndim == 2
                                else positions[None, :], (B, S))
        if kv_x is None:
            kpos = qpos
        else:  # cross-attention: all encoder slots valid
            kpos = jnp.zeros((B, k.shape[1]), jnp.int32)
            qpos = None

    # the "attention" scope names what lies between the projections,
    # whichever path computes it: the cache read, scores, softmax and PV
    with jax.named_scope("attention"):
        ctx = _attend(q, k, v, qpos, kpos, causal and kv_x is None, window,
                      x, cfg, policy,
                      kv_prequant=bool(cache is not None
                                       and "k_codes" in cache))
    return dense(ctx, p["wo"], policy, tp_in=True), new_cache


def _attend_packed(q, cache, pos_vec, window, cfg: ModelConfig,
                   policy: QuantPolicy):
    """Cached attention consuming the packed MXSF cache directly — S=1
    decode steps and S=C prefill chunks (the q-side grid tiles over S).
    Returns the (B, S, h * dh) context that ``wo`` projects.

    Routes through ``kernels/ops.py::mxsf_attention`` (SAFE-MAC dataflow:
    E8M0-scaled codes decoded at the MAC array).  q is 1D-quantized along dh
    when ``policy.attn_matmuls`` — the same operand treatment ``mx_einsum``
    applies; softmax probabilities stay f32 inside the online softmax (the
    one documented divergence from the jnp emulation, which re-quantizes the
    normalized probs before the V matmul).  ``kv_len``/``q_offset``/
    ``window`` ride as dynamic per-row scalars, so a growing cache never
    recompiles the kernel — and neither does a prefill chunk whose valid
    length varies (the chunk is padded to a fixed C upstream).
    """
    from ..kernels import ops as kops
    B, S, h, dh = q.shape
    kv = cfg.n_kv
    # cache-layout operands go to the kernel as-is — the BlockSpec index
    # maps read (B, kv, W, dh) per (batch x head) row, so the packed cache
    # never makes a relaid HBM copy (see decoding.kv_cache_rows)
    caches = (cache["k_codes"], cache["k_scales"], cache["v_codes"],
              cache["v_scales"])
    q = shd.constrain(q, "batch", None, "heads", None)
    qh = q.transpose(0, 2, 1, 3)                            # (B, h, S, dh)
    if policy.attn_matmuls:
        qh = qdq_along(qh, policy.fwd_fmt, policy, -1)
    per_head = lambda v: jnp.broadcast_to(
        jnp.asarray(v, jnp.int32).reshape(-1, 1), (B, h))
    scalars = [per_head(pos_vec + S),   # slots 0..pos hold positions 0..pos
               per_head(pos_vec)]       # the query sits at absolute pos
    if window is not None:
        scalars.append(per_head(jnp.broadcast_to(window, (B,))))

    def local(qh, kc, ks, vc, vs, kvl, off, *win):
        b, hl = qh.shape[:2]
        y = kops.mxsf_attention(
            qh.reshape(b * hl, S, dh), kc, ks, vc, vs, causal=True,
            kv_len=kvl.reshape(-1), q_offset=off.reshape(-1),
            window=win[0].reshape(-1) if win else None)
        return y.reshape(b, hl, S, dh)

    # under a mesh the kernel runs shard-local: slots over DP with their
    # cache rows, heads over TP with their kv heads (whole GQA groups; the
    # engine routes a cache whose kv heads do not split to the jnp path)
    P = jax.sharding.PartitionSpec
    bs = shd.split_axes(B, "batch")
    ts = shd.split_axes(kv, "kv")
    y = shd.shard_local(
        local,
        (P(bs, ts, None, None),) + (P(bs, ts, None, None), P(bs, ts, None)) * 2
        + (P(bs, ts),) * len(scalars),
        P(bs, ts, None, None))(qh, *caches, *scalars)
    ctx = y.transpose(0, 2, 1, 3).reshape(B, S, h * dh)
    # 'hidden' puts the flattened head dim on TP, matching wo's row shard
    return shd.constrain(ctx, "batch", None, "hidden")


ATTN_CHUNK = 1024  # query-chunk target (flash-style; bounds score memory)


def _pick_chunk(S: int, target: Optional[int] = None) -> int:
    target = target if target is not None else ATTN_CHUNK  # late-bound
    for c in range(min(S, target), 0, -1):
        if S % c == 0:
            return c
    return S


def _scores_block(qg_c, kk, vv, qpos_c, kpos, causal, window, dh, cfg,
                  policy, out_dtype, kv_prequant=False):
    """One query block: (B,kv,g,C,dh) x (B,kv,L,dh) -> (B,kv,g,C,dh)."""
    scores = mx_einsum("bkgsd,bkld->bkgsl", qg_c, kk, policy,
                       axes=(-1, -1), g_axes=(-1, -2),
                       quant_ops=(True, not kv_prequant))
    scores = scores.astype(jnp.float32) / math.sqrt(dh)
    if cfg.attn_softcap:
        scores = cfg.attn_softcap * jnp.tanh(scores / cfg.attn_softcap)
    bias = _attn_mask_bias(qpos_c, kpos, causal=causal, window=window)
    scores = scores + bias[:, None, None, :, :]
    scores = shd.constrain(scores, "batch", "kv", None, None, "seq")
    probs = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    ctx = mx_einsum("bkgsl,bkld->bkgsd", probs, vv, policy,
                    axes=(-1, -2), g_axes=(-1, -2),
                    quant_ops=(True, not kv_prequant))
    return shd.constrain(ctx, "batch", "kv", None, None, None)


def _attend(q, k, v, qpos, kpos, causal, window, x, cfg: ModelConfig,
            policy: QuantPolicy, kv_prequant: bool = False):
    """Query-chunked attention: the full (S x L) score tensor never
    materializes (peak is one (C x L) block per device).  Returns the
    (B, S, h * dh) context that ``wo`` projects.

    TP assignment (core/sharding.py): the kv-head dim when it divides the
    TP axis, else the key/cache length (sequence parallelism) — the same
    rule covers train, prefill and decode.
    """
    B, S, h, dh = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(B, S, kv, g, dh).transpose(0, 2, 3, 1, 4)
    kk = k.transpose(0, 2, 1, 3)   # (B, kv, L, dh)
    vv = v.transpose(0, 2, 1, 3)
    qg = shd.constrain(qg, "batch", "kv", None, None, None)
    kk = shd.constrain(kk, "batch", "kv", "seq", None)
    vv = shd.constrain(vv, "batch", "kv", "seq", None)

    chunk = _pick_chunk(S)
    if S <= chunk:
        ctx = _scores_block(qg, kk, vv, qpos, kpos, causal, window, dh,
                            cfg, policy, x.dtype, kv_prequant)
    elif qpos is None:  # cross-attention: mask depends only on kpos
        ctx = _scores_block(qg, kk, vv, None, kpos, causal, window, dh,
                            cfg, policy, x.dtype, kv_prequant)
    else:
        n = S // chunk
        qg_c = qg.reshape(B, kv, g, n, chunk, dh).transpose(3, 0, 1, 2, 4, 5)
        qpos_c = qpos.reshape(B, n, chunk).transpose(1, 0, 2)

        @jax.checkpoint
        def body(_, xs):
            qc, pc = xs
            return None, _scores_block(qc, kk, vv, pc, kpos, causal, window,
                                       dh, cfg, policy, x.dtype, kv_prequant)

        _, ctx = jax.lax.scan(body, None, (qg_c, qpos_c))
        # (n, B, kv, g, chunk, dh) -> (B, kv, g, S, dh)
        ctx = ctx.transpose(1, 2, 3, 0, 4, 5).reshape(B, kv, g, S, dh)
    return ctx.transpose(0, 3, 1, 2, 4).reshape(B, S, h * dh)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp in ("swiglu", "geglu"):
        return {"wg": _dense_init(ks[0], d, f), "wu": _dense_init(ks[1], d, f),
                "wd": _dense_init(ks[2], f, d)}
    return {"wu": _dense_init(ks[0], d, f), "wd": _dense_init(ks[1], f, d)}


def mlp(p, x, cfg: ModelConfig, policy: QuantPolicy):
    if cfg.mlp in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp == "swiglu" else \
            (lambda v: jax.nn.gelu(v, approximate=True))
        gate = act(dense(x, p["wg"], policy))
        up = dense(x, p["wu"], policy)
        return dense(gate * up, p["wd"], policy, tp_in=True)
    h = jax.nn.gelu(dense(x, p["wu"], policy), approximate=True)
    return dense(h, p["wd"], policy, tp_in=True)


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity-based per-row dispatch, sort-free combine)
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.expert_ff, cfg.padded_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], d, E, scale=0.02),
        "we_g": jax.random.normal(ks[1], (E, d, f), jnp.float32) / math.sqrt(d),
        "we_u": jax.random.normal(ks[2], (E, d, f), jnp.float32) / math.sqrt(d),
        "we_d": jax.random.normal(ks[3], (E, f, d), jnp.float32) / math.sqrt(f),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg,
                               d_ff=cfg.expert_ff * cfg.n_shared_experts)
    return p


def _row_dispatch(x_row, topi, topv, E, C):
    """Dispatch one row of tokens into (E, C, d) expert buffers.

    x_row: (S, d); topi/topv: (S, k).  Returns (xe, slot, valid, st, sw).
    """
    S, k = topi.shape
    flat_e = topi.reshape(-1)
    st = jnp.repeat(jnp.arange(S), k)
    sw = topv.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], st[order], sw[order]
    pos_in_e = jnp.arange(S * k) - jnp.searchsorted(se, se, side="left")
    valid = pos_in_e < C
    slot = jnp.where(valid, se * C + pos_in_e, E * C)
    d = x_row.shape[-1]
    buf = jnp.zeros((E * C + 1, d), x_row.dtype).at[slot].set(x_row[st])
    return buf[: E * C].reshape(E, C, d), slot, valid, st, sw


def moe(p, x, cfg: ModelConfig, policy: QuantPolicy):
    """x: (B, S, d) -> (B, S, d).  Row = sequence (decode regroups upstream)."""
    B, S, d = x.shape
    E, k = cfg.padded_experts, cfg.top_k
    C = max(1, int(math.ceil(S * k * cfg.capacity_factor / cfg.n_experts)))
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    if E != cfg.n_experts:  # mask padded (dead) experts out of routing
        dead = jnp.arange(E) >= cfg.n_experts
        logits = logits + jnp.where(dead, -1e30, 0.0)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)

    xe, slot, valid, st, sw = jax.vmap(
        lambda xr, ti, tv: _row_dispatch(xr, ti, tv, E, C))(x, topi, topv)
    xe = shd.constrain(xe, "batch", "experts", None, None)
    # expert FFN on (B, E, C, d)
    act = jax.nn.silu if cfg.mlp != "gelu" else jax.nn.gelu
    gate = act(mx_einsum("becd,edf->becf", xe, p["we_g"].astype(xe.dtype), policy,
                         axes=(-1, -2), g_axes=(-1, -2)))
    up = mx_einsum("becd,edf->becf", xe, p["we_u"].astype(xe.dtype), policy,
                   axes=(-1, -2), g_axes=(-1, -2))
    ye = mx_einsum("becf,efd->becd", gate * up, p["we_d"].astype(xe.dtype), policy,
                   axes=(-1, -2), g_axes=(-1, -2))
    ye = shd.constrain(ye, "batch", "experts", None, None)
    # combine back to tokens
    ye_flat = ye.reshape(B, E * C, d)
    ye_flat = jnp.concatenate([ye_flat, jnp.zeros((B, 1, d), ye.dtype)], axis=1)

    def _combine(yf, slot_r, valid_r, st_r, sw_r):
        contrib = yf[slot_r] * jnp.where(valid_r, sw_r, 0.0)[:, None]
        return jnp.zeros((S, d), yf.dtype).at[st_r].add(contrib)

    y = jax.vmap(_combine)(ye_flat, slot, valid, st, sw.astype(x.dtype))
    if "shared" in p:
        y = y + mlp(p["shared"], x, cfg, policy)
    return y


def moe_aux_loss(x, p, cfg: ModelConfig):
    """Switch-style load-balancing loss (fraction * probability per expert)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, cfg.padded_experts), axis=(0, 1))
    pmean = jnp.mean(probs, axis=(0, 1))
    return cfg.n_experts * jnp.sum(frac * pmean)
