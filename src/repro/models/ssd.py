"""Mamba2 / SSD (state-space duality) block in JAX.

Chunked SSD algorithm (Dao & Gu 2024): intra-chunk quadratic term +
inter-chunk recurrent state carried by ``lax.scan``.  All recurrence math is
f32 (decays are exp of negative numbers, bounded by 1).  The paper's MX
technique applies to ``in_proj``/``out_proj`` only (DESIGN.md §5) — the
recurrence is not a MAC-array matmul.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core import sharding as shd
from .blocks import dense, rmsnorm
from ..core.policy import QuantPolicy


def _dims(cfg: ModelConfig):
    dI = cfg.d_inner
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    H = cfg.ssm_heads
    P = cfg.ssm_headdim
    return dI, G, N, H, P


def ssd_init(key, cfg: ModelConfig):
    d = cfg.d_model
    dI, G, N, H, P = _dims(cfg)
    d_in = 2 * dI + 2 * G * N + H  # z, x, B, C, dt
    conv_ch = dI + 2 * G * N
    ks = jax.random.split(key, 4)
    return {
        "in_proj": jax.random.normal(ks[0], (d, d_in), jnp.float32) / math.sqrt(d),
        "conv_w": jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch), jnp.float32) * 0.2,
        "conv_b": jnp.zeros((conv_ch,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((H,), 0.01, jnp.float32))),
        "norm_w": jnp.ones((dI,), jnp.float32),
        "out_proj": jax.random.normal(ks[3], (dI, d), jnp.float32) / math.sqrt(dI),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv over time.  x: (B, L, C), w: (K, C)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(K))
    return y + b


def _split_proj(zxbcdt, cfg: ModelConfig):
    dI, G, N, H, P = _dims(cfg)
    z = zxbcdt[..., :dI]
    xBC = zxbcdt[..., dI : 2 * dI + 2 * G * N]
    dt = zxbcdt[..., 2 * dI + 2 * G * N :]
    return z, xBC, dt


def _gate_out(p, y, z, x_resid, cfg, policy):
    y = y + x_resid * p["D"].astype(y.dtype)[None, None, :, None]  # D skip
    B, L = y.shape[:2]
    y = y.reshape(B, L, cfg.d_inner)
    y = rmsnorm({"w": p["norm_w"]}, y * jax.nn.silu(z))
    return dense(y, p["out_proj"], policy, tp_in=True)


def ssd_forward(p, u, cfg: ModelConfig, policy: QuantPolicy, *,
                return_state: bool = False):
    """u: (B, L, d_model) -> (B, L, d_model) [+ (state, conv_tail) cache]."""
    Bsz, L, _ = u.shape
    dI, G, N, H, P = _dims(cfg)
    Hg = H // G
    Q = min(cfg.ssm_chunk, L)
    assert L % Q == 0, (L, Q)
    nc = L // Q

    zxbcdt = dense(u, p["in_proj"], policy)
    zxbcdt = shd.constrain(zxbcdt, "batch", None, "hidden")
    z, xBC, dt = _split_proj(zxbcdt, cfg)
    xBC_conv = jax.nn.silu(_causal_conv(xBC, p["conv_w"], p["conv_b"]))
    x = xBC_conv[..., :dI].reshape(Bsz, L, G, Hg, P).astype(jnp.float32)
    x = shd.constrain(x, "batch", None, None, "heads", None)
    Bm = xBC_conv[..., dI : dI + G * N].reshape(Bsz, L, G, N).astype(jnp.float32)
    Cm = xBC_conv[..., dI + G * N :].reshape(Bsz, L, G, N).astype(jnp.float32)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B, L, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                 # (H,)
    dA = (dt * A).reshape(Bsz, nc, Q, G, Hg)
    dt_c = dt.reshape(Bsz, nc, Q, G, Hg)
    x_c = x.reshape(Bsz, nc, Q, G, Hg, P)
    B_c = Bm.reshape(Bsz, nc, Q, G, N)
    C_c = Cm.reshape(Bsz, nc, Q, G, N)

    cs = jnp.cumsum(dA, axis=2)                                   # (B,c,Q,g,h)
    # ---- intra-chunk quadratic term -------------------------------------
    CB = jnp.einsum("bcigm,bcjgm->bcgij", C_c, B_c)               # (B,c,g,Q,Q)
    seg = cs[:, :, :, None] - cs[:, :, None, :]                   # i-axis, j-axis
    seg = seg.transpose(0, 1, 4, 5, 2, 3)                         # (B,c,g,h,i,j)
    ii = jnp.arange(Q)
    causal = (ii[:, None] >= ii[None, :])
    decay = jnp.where(causal, jnp.exp(jnp.minimum(seg, 0.0)), 0.0)
    M = CB[:, :, :, None] * decay * dt_c.transpose(0, 1, 3, 4, 2)[:, :, :, :, None, :]
    y_intra = jnp.einsum("bcghij,bcjghp->bcighp", M, x_c)

    # ---- chunk states + inter-chunk scan ---------------------------------
    w_state = jnp.exp(cs[:, :, -1:, :, :] - cs) * dt_c            # (B,c,Q,g,h)
    states = jnp.einsum("bcjgh,bcjgm,bcjghp->bcghpm", w_state, B_c, x_c)
    states = shd.constrain(states, "batch", None, None, "heads", None, None)
    chunk_decay = jnp.exp(jnp.sum(dA, axis=2))                    # (B,c,g,h)

    def step(S, inp):
        st, cd, Cc, csc = inp
        y_int = jnp.einsum("bigm,bghpm->bighp", Cc, S)
        y_int = y_int * jnp.exp(csc)[..., None]  # csc: (B,Q,g,h)
        S_next = cd[..., None, None] * S + st
        return S_next, y_int

    xs = (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0),
          jnp.moveaxis(C_c, 1, 0), jnp.moveaxis(cs, 1, 0))
    S0 = jnp.zeros((Bsz, G, Hg, P, N), jnp.float32)
    S_last, y_inter = jax.lax.scan(step, S0, xs)
    y_inter = jnp.moveaxis(y_inter, 0, 1)                         # (B,c,i,g,h,p)

    y = (y_intra + y_inter).reshape(Bsz, L, H, P)
    x_resid = x.reshape(Bsz, L, H, P)
    out = _gate_out(p, y.astype(u.dtype), z, x_resid.astype(u.dtype), cfg, policy)
    if return_state:
        conv_tail = xBC[:, -(cfg.ssm_conv - 1):, :]
        return out, {"state": S_last, "conv": conv_tail}
    return out


def ssd_init_cache(cfg: ModelConfig, batch: int):
    dI, G, N, H, P = _dims(cfg)
    return {
        "state": jnp.zeros((batch, G, H // G, P, N), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1, dI + 2 * G * N), jnp.float32),
    }


def ssd_decode_step(p, u, cache, cfg: ModelConfig, policy: QuantPolicy):
    """Single-token recurrent update.  u: (B, 1, d_model)."""
    Bsz = u.shape[0]
    dI, G, N, H, P = _dims(cfg)
    Hg = H // G

    zxbcdt = dense(u, p["in_proj"], policy)
    z, xBC, dt = _split_proj(zxbcdt, cfg)
    # conv over (tail ++ current)
    hist = jnp.concatenate([cache["conv"], xBC.astype(cache["conv"].dtype)], axis=1)
    w = p["conv_w"]
    xc = (hist * w[None, :, :]).sum(axis=1) + p["conv_b"]
    xc = jax.nn.silu(xc)                                           # (B, C)
    x = xc[:, :dI].reshape(Bsz, G, Hg, P).astype(jnp.float32)
    Bm = xc[:, dI : dI + G * N].reshape(Bsz, G, N).astype(jnp.float32)
    Cm = xc[:, dI + G * N :].reshape(Bsz, G, N).astype(jnp.float32)

    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt * A).reshape(Bsz, G, Hg)

    S = cache["state"]
    S_new = dA[..., None, None] * S + jnp.einsum(
        "bgh,bgm,bghp->bghpm", dt.reshape(Bsz, G, Hg), Bm, x)
    S_new = shd.constrain(S_new, "batch", None, "heads", None, None)
    y = jnp.einsum("bgm,bghpm->bghp", Cm, S_new)                   # (B,g,h,p)
    y = y.reshape(Bsz, 1, H, P)
    x_resid = x.reshape(Bsz, 1, H, P)
    out = _gate_out(p, y.astype(u.dtype), z, x_resid.astype(u.dtype), cfg, policy)
    new_cache = {"state": S_new, "conv": hist[:, 1:, :]}
    return out, new_cache
