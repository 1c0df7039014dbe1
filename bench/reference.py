"""Plain float32 reference of a dense GQA decoder, and its int4 control.

Written from the published architecture (Qwen2 / Mistral family: RMSNorm,
rotary position embedding on the first and second halves of each head,
grouped-query attention, optional q/k/v biases, optional sliding window,
SwiGLU MLP, untied output head).  It imports nothing of the system under
test and takes nothing that it made: the weights come again from
``weights.py`` and the seed.

Requests are packed into one flat stream of ``T`` tokens with a segment id
each, so one compiled layer serves every sample; attention is masked to the
same segment, causally, and to the sliding window.  Matmuls run at
``Precision.HIGHEST``.  The layers are made and applied one at a time, so
the reference fits beside nothing else on one chip.

``quant="int4"`` is the control: every matmul operand (activations and
weights along the contraction, queries and keys along the head dim, and
the cached values) is rounded to symmetric int4 in blocks of 64 with an
absmax scale, the step below the 8-bit formats the program serves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HI = jax.lax.Precision.HIGHEST
Q_CHUNK = 512        # queries per attention block
HEAD_ROWS = 256      # rows per block of the output head


def int4(x, axis: int, block: int = 64):
    """Symmetric int4 (levels -7..7) in blocks of ``block`` along ``axis``,
    returned dequantized."""
    x = jnp.moveaxis(x, axis, -1)
    n = x.shape[-1]
    pad = (-n) % block
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xp.reshape(*xp.shape[:-1], -1, block)
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 7.0
    q = jnp.where(scale > 0, jnp.round(xb / jnp.where(scale > 0, scale, 1.0)),
                  0.0)
    y = (jnp.clip(q, -7, 7) * scale).reshape(xp.shape)[..., :n]
    return jnp.moveaxis(y, -1, axis)


def _mm(x, w, quant):
    if quant:
        x, w = int4(x, -1), int4(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (T, n, dh); rotate the first half against the second."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _layer(w, x, seg, pos, s, quant):
    T, d = x.shape
    h, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    g = h // kv
    eps, win = s["rms_norm_eps"], s["sliding_window"]
    a = _rmsnorm(x, w["ln1"], eps)
    q, k, v = (_mm(a, w["wq"], quant), _mm(a, w["wk"], quant),
               _mm(a, w["wv"], quant))
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    q = _rope(q.reshape(T, h, dh), pos, s["rope_theta"])
    k = _rope(k.reshape(T, kv, dh), pos, s["rope_theta"])
    v = v.reshape(T, kv, dh)
    if quant:
        q, k, v = int4(q, -1), int4(k, -1), int4(v, -1)
    qg = q.reshape(T, kv, g, dh)

    def block(i):
        sl = jax.lax.dynamic_slice_in_dim
        qb = sl(qg, i * Q_CHUNK, Q_CHUNK)                 # (C, kv, g, dh)
        qp, qs = sl(pos, i * Q_CHUNK, Q_CHUNK), sl(seg, i * Q_CHUNK, Q_CHUNK)
        sc = jnp.einsum("ckgd,tkd->kgct", qb, k, precision=HI) / np.sqrt(dh)
        ok = (qs[:, None] == seg[None, :]) & (pos[None, :] <= qp[:, None])
        if win:
            ok &= pos[None, :] > qp[:, None] - win
        sc = jnp.where(ok[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgct,tkd->ckgd", p, v, precision=HI)

    ctx = jax.lax.map(block, jnp.arange(T // Q_CHUNK))
    x = x + _mm(ctx.reshape(T, h * dh), w["wo"], quant)
    m = _rmsnorm(x, w["ln2"], eps)
    up = jax.nn.silu(_mm(m, w["wg"], quant)) * _mm(m, w["wu"], quant)
    return x + _mm(up, w["wd"], quant)


def pack(samples, T: int):
    """Pack ``samples`` (token lists) into one stream of ``T`` tokens.
    Returns (tokens, segment ids, positions, start offset of each sample);
    the padded tail has its own segment."""
    toks = np.zeros(T, np.int32)
    seg = np.full(T, -1, np.int32)
    pos = np.zeros(T, np.int32)
    starts, at = [], 0
    for i, t in enumerate(samples):
        n = len(t)
        if at + n > T:
            raise ValueError(f"samples need {at + n} tokens, stream has {T}")
        toks[at:at + n], seg[at:at + n], pos[at:at + n] = t, i, np.arange(n)
        starts.append(at)
        at += n
    pos[at:] = np.arange(T - at)
    return toks, seg, pos, starts


def hidden(s: dict, seed: int, toks, seg, pos, rows, quant=None):
    """Final-normed hidden states at stream positions ``rows``."""
    key = W.root_key(seed)
    x = jnp.take(W.embedding(key, s), jnp.asarray(toks), axis=0)
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)
    make = jax.jit(W.layer, static_argnums=(2,))
    fs = _freeze(s)
    for i in range(s["num_hidden_layers"]):
        x = _layer(make(key, i, fs), x, seg, pos, fs, quant)
    x = jnp.take(x, jnp.asarray(rows), axis=0)
    return _rmsnorm(x, W.final_norm(key, s), s["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("quant",))
def _head_block(hb, head, tok, quant):
    """Per row: the best logit, the logits' standard deviation, the logit
    of ``tok`` and the argmax.  With ``quant`` the rows are rounded here and
    ``head`` comes rounded already."""
    z = jnp.dot(int4(hb, -1) if quant else hb, head, precision=HI)
    return (jnp.max(z, -1), jnp.std(z, -1),
            jnp.take_along_axis(z, tok[:, None], -1)[:, 0],
            jnp.argmax(z, -1).astype(jnp.int32))


def head_stats(s: dict, seed: int, h, tok, quant=None):
    """Head statistics for every row of ``h`` (see ``_head_block``)."""
    head = W.head(W.root_key(seed), s)
    if quant:
        head = jax.jit(int4, static_argnums=1)(head, 0)
    n = h.shape[0]
    pad = (-n) % HEAD_ROWS
    hp = jnp.pad(h, ((0, pad), (0, 0)))
    tp = jnp.pad(jnp.asarray(tok, jnp.int32), (0, pad))
    outs = [_head_block(hp[i:i + HEAD_ROWS], head, tp[i:i + HEAD_ROWS],
                        quant) for i in range(0, n + pad, HEAD_ROWS)]
    return [np.concatenate([np.asarray(o[j]) for o in outs])[:n]
            for j in range(4)]


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _freeze(s: dict):
    return _Frozen(s)


def served_gaps(s: dict, seed: int, samples, T: int, control: bool = False):
    """Teacher-forced comparison of served tokens with the reference.

    ``samples``: list of (prompt, served tokens).  For each served token,
    the gap by which its reference logit lies below the reference's best,
    over the standard deviation of the reference logits there.  With
    ``control``, also the same gap of the token the int4 control puts
    first at each of those positions.  Returns (gaps, control gaps or
    None)."""
    streams = [list(p) + list(o[:-1]) for p, o in samples]
    toks, seg, pos, starts = pack(streams, T)
    rows = np.concatenate([st + len(p) - 1 + np.arange(len(o))
                           for st, (p, o) in zip(starts, samples)])
    served = np.concatenate([np.asarray(o, np.int32) for _, o in samples])
    fs = _freeze(s)
    h = hidden(fs, seed, toks, seg, pos, rows)
    best, std, got, _ = head_stats(fs, seed, h, served)
    gaps = (best - got) / std
    if not control:
        return gaps, None
    hq = hidden(fs, seed, toks, seg, pos, rows, quant="int4")
    _, _, _, pick = head_stats(fs, seed, hq, served, quant="int4")
    del hq
    _, _, got_c, _ = head_stats(fs, seed, h, pick)
    return gaps, (best - got_c) / std
