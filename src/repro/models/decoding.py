"""KV/SSM-state caches, prefill (whole-prompt and chunked) and decode.

Three cached entry points share one decoder forward (``_decoder_forward``):
``prefill`` runs the whole prompt from position 0 (lockstep batches),
``prefill_step`` runs one C-token chunk at dynamic per-slot positions with
masked cache writes (continuous batching, serve/engine.py), and
``decode_step`` runs one token.

Cache layouts (stacked over layers for ``lax.scan``):
  * decoder : k/v ring buffers (n_super, moe_every, B, kv, W, dh); W is the
    SWA window when the arch is all-SWA (danube long-context: W=4096 ring)
    else the full max_len.
  * ssm     : recurrent state + conv tail, (L, ...).
  * hybrid  : ssm caches grouped (G, per, ...) (+tail) + one attention cache
    per shared-block application (G, B, kv, W, dh).
  * encdec  : decoder self-attn cache + precomputed cross-attn k/v.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core import sharding as shd
from ..core.policy import QuantPolicy
from . import blocks as blk
from . import ssd
from .transformer import (NO_WINDOW, _apply_ffn, _hybrid_split, _layer_windows,
                          _lm_head, _sinusoid_pos, encode)

__all__ = ["init_cache", "decode_step", "prefill_step", "prefill",
           "kv_cache_rows"]


def kv_cache_rows(cache):
    """One layer's packed KV cache in the flash-kernel *row* layout.

    The cache pytree stores per-layer codes as ``(B, kv, W, dh)`` uint8 with
    ``(B, kv, W)`` E8M0 scales: positions on the sublanes of the codes and
    on the lanes of the scales, the (8, 128)-tiled blocks the TPU kernel
    reads as-is.  The row layout merges (B, kv) into one ``B*kv`` axis —
    rows batch-major, so q row ``b*h + head`` reads kv row
    ``b*kv + head // (h // kv)``.  ``tests/test_attention_backend.py``
    asserts both layouts produce identical kernel output.
    Returns ``(k_codes, k_scales, v_codes, v_scales)``.
    """
    B, kv, W, dh = cache["k_codes"].shape
    return (cache["k_codes"].reshape(B * kv, W, dh),
            cache["k_scales"].reshape(B * kv, W),
            cache["v_codes"].reshape(B * kv, W, dh),
            cache["v_scales"].reshape(B * kv, W))


def _attn_cache(cfg: ModelConfig, lead, batch, W, dtype, kv_fmt: str = ""):
    shape = (*lead, batch, cfg.n_kv, W, cfg.head_dim)
    if kv_fmt:  # 8-bit MX-packed cache: 1B codes + 1B E8M0 scale per head row
        sshape = (*lead, batch, cfg.n_kv, W)
        return {"k_codes": jnp.zeros(shape, jnp.uint8),
                "k_scales": jnp.zeros(sshape, jnp.uint8),
                "v_codes": jnp.zeros(shape, jnp.uint8),
                "v_scales": jnp.zeros(sshape, jnp.uint8)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cache_window(cfg: ModelConfig, max_len: int) -> int:
    if cfg.swa_pattern == "all" and cfg.swa_window:
        return min(cfg.swa_window, max_len)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               ring: bool = True, kv_fmt: str = ""):
    """``ring=True`` shrinks all-SWA caches to the window (decode);
    prefill needs ``ring=False`` (one contiguous write of the prompt).
    ``kv_fmt='mxsf'`` stores the cache packed in 8-bit MX codes."""
    if cfg.family == "decoder":
        n_super = cfg.n_layers // cfg.moe_every
        W = (_cache_window(cfg, max_len + cfg.frontend_tokens) if ring
             else max_len + cfg.frontend_tokens)
        return _attn_cache(cfg, (n_super, cfg.moe_every), batch, W, dtype,
                           kv_fmt)
    if cfg.family == "ssm":
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)),
            ssd.ssd_init_cache(cfg, batch))
    if cfg.family == "hybrid":
        G, per, tail = _hybrid_split(cfg)
        base = ssd.ssd_init_cache(cfg, batch)
        cache = {
            "groups": jax.tree.map(
                lambda x: jnp.broadcast_to(x, (G, per, *x.shape)), base),
            "attn": _attn_cache(cfg, (G,), batch, max_len, dtype, kv_fmt),
        }
        if tail:
            cache["tail"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (tail, *x.shape)), base)
        return cache
    if cfg.family == "encdec":
        return {
            "self": _attn_cache(cfg, (cfg.n_layers,), batch, max_len, dtype,
                                kv_fmt),
            "cross": _attn_cache(cfg, (cfg.n_layers,), batch, cfg.enc_seq,
                                 dtype),
            "cross_ready": jnp.zeros((), jnp.int32),
        }
    raise ValueError(f"family {cfg.family} has no decode step")


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

def _decoder_forward(params, tokens, cache, pos, cfg: ModelConfig,
                     policy: QuantPolicy, write_len=None):
    """Shared decoder-family cached forward over an S-token slice.

    tokens: (B, S) int32; pos: scalar or (B,) start positions.
    ``write_len`` (None or (B,)): per-slot count of valid tokens — only
    cache columns ``pos..pos+write_len-1`` are written (see
    ``blocks.attention``); None writes all S.  Returns the FULL per-position
    logits (B, S, vocab) plus the new cache — ``decode_step`` (S=1) and
    ``prefill_step`` (S=C) pick their position out of it.
    """
    x = params["emb"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.name.startswith("gemma2"):
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    # slot batch over the DP axes from the first layer on (no-op without a
    # mesh context; the sharded serving engine installs one)
    x = shd.constrain(x, "batch", None, None)
    pos_eff = pos + cfg.frontend_tokens  # VLM prefix occupies slots 0..T-1
    n_super = cfg.n_layers // cfg.moe_every
    windows = _layer_windows(cfg, cfg.n_layers).reshape(n_super,
                                                        cfg.moe_every)

    def body(x, inp):
        lp, c, win = inp
        outs = {k: [] for k in c}
        for j in range(cfg.moe_every):
            is_moe = cfg.n_experts > 0 and j == cfg.moe_every - 1
            sub_c = {k: v[j] for k, v in c.items()}
            h = blk.rmsnorm(lp[f"sub{j}"]["ln1"], x)
            a, sub_c = blk.attention(lp[f"sub{j}"]["attn"], h, cfg, policy,
                                     positions=None, window=win[j],
                                     cache=sub_c, cache_pos=pos_eff,
                                     cache_write_len=write_len)
            if cfg.post_norms:
                a = blk.rmsnorm(lp[f"sub{j}"]["pn1"], a)
            x = x + a
            h = blk.rmsnorm(lp[f"sub{j}"]["ln2"], x)
            f = _apply_ffn(lp[f"sub{j}"]["ffn"], h, cfg, policy, is_moe)
            if cfg.post_norms:
                f = blk.rmsnorm(lp[f"sub{j}"]["pn2"], f)
            x = x + f
            for k in outs:
                outs[k].append(sub_c[k])
        return x, {k: jnp.stack(v) for k, v in outs.items()}

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache, windows))
    return _mask_pad(_lm_head(params, x, cfg, policy), cfg), new_cache


def decode_step(params, tokens, cache, pos, cfg: ModelConfig,
                policy: QuantPolicy):
    """One token step.  tokens: (B, 1) int32; pos: scalar int32 step index.

    Returns (logits (B, vocab), new_cache).
    """
    if cfg.family == "encdec":
        return _decode_encdec(params, tokens, cache, pos, cfg, policy)

    if cfg.family == "decoder":
        logits, new_cache = _decoder_forward(params, tokens, cache, pos,
                                             cfg, policy)
        return logits[:, 0], new_cache

    x = params["emb"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    if cfg.family == "ssm":
        def body(x, inp):
            lp, c = inp
            y, c = ssd.ssd_decode_step(lp["ssd"], blk.rmsnorm(lp["ln"], x),
                                       c, cfg, policy)
            return x + y, c
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    elif cfg.family == "hybrid":
        x, new_cache = _decode_hybrid(params, x, cache, pos, cfg, policy)
    else:
        raise ValueError(cfg.family)

    logits = _mask_pad(_lm_head(params, x, cfg, policy), cfg)
    return logits[:, 0], new_cache


def prefill_step(params, tokens, cache, pos, n_valid, cfg: ModelConfig,
                 policy: QuantPolicy):
    """One C-token prompt chunk in ONE dispatch (chunked prefill).

    tokens : (B, C) int32 — per-slot prompt chunks, padded to a fixed C
             (pad value is irrelevant: padded rows are neither written to
             the cache nor attended by valid queries).
    pos    : scalar or (B,) int32 — each slot's start position; the chunk
             occupies cache columns ``pos..pos+n_valid-1``.
    n_valid: (B,) int32 in [0, C] — valid tokens per slot.  0 masks the
             slot out entirely: its cache is left bit-identical and its
             logits row is garbage the caller must ignore (this is how the
             serving engine keeps decode-phase slots out of a mixed-phase
             prefill dispatch).

    Returns (logits (B, vocab) at each slot's LAST valid token, new_cache).
    Because C is static and ``pos``/``n_valid`` are dynamic, every chunk of
    every prompt length reuses a single compilation — a P-token prompt
    costs ceil(P/C) dispatches, not P.

    Chunk-internal causality and the partial-tail contract ride the same
    absolute-position mask math as decode (see ``blocks.attention``): a
    valid query at position p attends exactly columns 0..p, never the
    unwritten tail of its own chunk.  Decoder (attention-cache) family
    only: SSM/hybrid recurrent state advances per token, so their prompt
    phase stays token-by-token until per-slot state checkpointing lands
    (ROADMAP open item).
    """
    if cfg.family != "decoder":
        raise NotImplementedError(
            "chunked prefill needs attention caches; SSM/hybrid recurrent "
            "state advances per token (see ROADMAP: per-slot state "
            "checkpointing)")
    B, C = tokens.shape
    nv = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32), (B,))
    logits, new_cache = _decoder_forward(params, tokens, cache, pos, cfg,
                                         policy, write_len=nv)
    last = jnp.clip(nv - 1, 0, C - 1)
    return jnp.take_along_axis(
        logits, last[:, None, None], axis=1)[:, 0], new_cache


def _mask_pad(logits, cfg):
    if cfg.padded_vocab == cfg.vocab:
        return logits
    dead = jnp.arange(cfg.padded_vocab) >= cfg.vocab
    return logits + jnp.where(dead, -1e30, 0.0)


def _decode_hybrid(params, x, cache, pos, cfg, policy):
    def ssm_body(x, inp):
        lp, c = inp
        y, c = ssd.ssd_decode_step(lp["ssd"], blk.rmsnorm(lp["ln"], x),
                                   c, cfg, policy)
        return x + y, c

    def group_body(x, inp):
        glp, gc, ac = inp
        x, gc = jax.lax.scan(ssm_body, x, (glp, gc))
        h = blk.rmsnorm(params["shared"]["ln1"], x)
        a, ac = blk.attention(params["shared"]["attn"], h, cfg, policy,
                              positions=None, window=NO_WINDOW,
                              cache=ac, cache_pos=pos)
        x = x + a
        h = blk.rmsnorm(params["shared"]["ln2"], x)
        x = x + blk.mlp(params["shared"]["ffn"], h, cfg, policy)
        return x, (gc, ac)

    x, (g_new, a_new) = jax.lax.scan(
        group_body, x, (params["layers"], cache["groups"], cache["attn"]))
    new_cache = {"groups": g_new, "attn": a_new}
    if "tail" in cache:
        x, t_new = jax.lax.scan(ssm_body, x, (params["tail"], cache["tail"]))
        new_cache["tail"] = t_new
    return x, new_cache


def _decode_encdec(params, tokens, cache, pos, cfg, policy):
    x = params["emb"][tokens].astype(jnp.dtype(cfg.compute_dtype))
    pv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (tokens.shape[0],))
    pe = jax.vmap(lambda p_: _dynamic_sinusoid(p_, cfg.d_model))(pv)  # (B,1,d)
    x = x + pe.astype(x.dtype)

    def body(x, inp):
        lp, sc, cc = inp
        h = blk.rmsnorm(lp["ln1"], x)
        a, sc = blk.attention(lp["self"], h, cfg, policy, positions=None,
                              cache=sc, cache_pos=pos)
        x = x + a
        h = blk.rmsnorm(lp["ln2"], x)
        c, _ = blk.attention(lp["cross"], h, cfg, policy, positions=None,
                             kv_cached=cc, causal=False)
        x = x + c
        x = x + blk.mlp(lp["mlp"], blk.rmsnorm(lp["ln3"], x), cfg, policy)
        return x, sc

    x, self_new = jax.lax.scan(
        body, x, (params["dec_layers"], cache["self"], cache["cross"]))
    logits = _mask_pad(_lm_head(params, x, cfg, policy), cfg)
    new_cache = dict(cache, self=self_new)
    return logits[:, 0], new_cache


def _dynamic_sinusoid(pos, d):
    i = jnp.arange(d // 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / (10_000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, :]


# ---------------------------------------------------------------------------
# prefill (fills caches; used by serving examples/tests)
# ---------------------------------------------------------------------------

def prefill(params, batch, cache, cfg: ModelConfig, policy: QuantPolicy):
    """Run the prompt through the model, filling caches from position 0.

    Requires prompt_len <= cache window (ring wrap during prefill is not
    supported; long-context flows decode token-by-token after this).
    Returns (last_logits (B, vocab), cache).
    """
    if cfg.family == "ssm":
        def body(x, inp):
            lp, _ = inp
            y, c = ssd.ssd_forward(lp["ssd"], blk.rmsnorm(lp["ln"], x),
                                   cfg, policy, return_state=True)
            return x + y, c
        x = params["emb"][batch["tokens"]].astype(jnp.dtype(cfg.compute_dtype))
        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        logits = _mask_pad(_lm_head(params, x, cfg, policy), cfg)
        return logits[:, -1], new_cache

    if cfg.family == "decoder":
        x = params["emb"][batch["tokens"]].astype(jnp.dtype(cfg.compute_dtype))
        if cfg.name.startswith("gemma2"):
            x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
        if "embeds" in batch and cfg.frontend_tokens:
            x = jnp.concatenate([batch["embeds"].astype(x.dtype), x], axis=1)
        x = shd.constrain(x, "batch", None, None)
        B, S, _ = x.shape
        n_super = cfg.n_layers // cfg.moe_every
        windows = _layer_windows(cfg, cfg.n_layers).reshape(n_super,
                                                            cfg.moe_every)

        def body(x, inp):
            lp, c, win = inp
            outs = {k: [] for k in c}
            for j in range(cfg.moe_every):
                is_moe = cfg.n_experts > 0 and j == cfg.moe_every - 1
                sub_c = {k: v[j] for k, v in c.items()}
                h = blk.rmsnorm(lp[f"sub{j}"]["ln1"], x)
                a, sub_c = blk.attention(lp[f"sub{j}"]["attn"], h, cfg, policy,
                                         positions=None, window=win[j],
                                         cache=sub_c, cache_pos=0)
                if cfg.post_norms:
                    a = blk.rmsnorm(lp[f"sub{j}"]["pn1"], a)
                x = x + a
                h = blk.rmsnorm(lp[f"sub{j}"]["ln2"], x)
                f = _apply_ffn(lp[f"sub{j}"]["ffn"], h, cfg, policy, is_moe)
                if cfg.post_norms:
                    f = blk.rmsnorm(lp[f"sub{j}"]["pn2"], f)
                x = x + f
                for k in outs:
                    outs[k].append(sub_c[k])
            return x, {k: jnp.stack(v) for k, v in outs.items()}

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache, windows))
        logits = _mask_pad(_lm_head(params, x, cfg, policy), cfg)
        return logits[:, -1], new_cache

    if cfg.family == "hybrid":
        x = params["emb"][batch["tokens"]].astype(jnp.dtype(cfg.compute_dtype))
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

        def ssm_body(x, inp):
            lp, _ = inp
            y, c = ssd.ssd_forward(lp["ssd"], blk.rmsnorm(lp["ln"], x),
                                   cfg, policy, return_state=True)
            return x + y, c

        def group_body(x, inp):
            glp, gc, ac = inp
            x, gc_new = jax.lax.scan(ssm_body, x, (glp, gc))
            h = blk.rmsnorm(params["shared"]["ln1"], x)
            a, ac_new = blk.attention(params["shared"]["attn"], h, cfg, policy,
                                      positions=positions, window=NO_WINDOW,
                                      cache=ac, cache_pos=0)
            x = x + a
            h = blk.rmsnorm(params["shared"]["ln2"], x)
            x = x + blk.mlp(params["shared"]["ffn"], h, cfg, policy)
            return x, (gc_new, ac_new)

        x, (g_new, a_new) = jax.lax.scan(
            group_body, x, (params["layers"], cache["groups"], cache["attn"]))
        new_cache = {"groups": g_new, "attn": a_new}
        if "tail" in cache:
            x, t_new = jax.lax.scan(ssm_body, x,
                                    (params["tail"], cache["tail"]))
            new_cache["tail"] = t_new
        logits = _mask_pad(_lm_head(params, x, cfg, policy), cfg)
        return logits[:, -1], new_cache

    if cfg.family == "encdec":
        enc = encode(params, batch["frames"], cfg, policy)

        def kv_body(_, lp):
            k = enc @ lp["cross"]["wk"].astype(enc.dtype)
            v = enc @ lp["cross"]["wv"].astype(enc.dtype)
            B, S, _ = k.shape
            k = k.reshape(B, S, cfg.n_kv, cfg.head_dim).transpose(0, 2, 1, 3)
            v = v.reshape(B, S, cfg.n_kv, cfg.head_dim).transpose(0, 2, 1, 3)
            return None, {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}

        _, cross = jax.lax.scan(kv_body, None, params["dec_layers"])
        new_cache = dict(cache, cross=cross,
                         cross_ready=jnp.ones((), jnp.int32))
        return None, new_cache

    raise ValueError(cfg.family)
