"""Seeded random weights of a dense decoder, made by the benchmark itself.

One generator serves both sides: the harness stacks its layers into the
program's parameter layout and packs them on the device in one jitted
call, and the reference makes the same float32 values again, one layer at
a time.  Each leaf has a key of its own (``fold_in`` of the layer index and
the leaf's number), so a layer's values do not depend on how many layers
are made or in which order.

Scales keep every activation of order one: unit-variance embeddings,
weights of variance 1/fan_in, norm weights near 1 and non-zero biases, so
that each part of the layer moves the logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "ln1", "ln2",
                "wg", "wu", "wd")
EMB, HEAD, FINAL_NORM = 0, 1, 2


def root_key(seed: int):
    """A PRNG key from any whole number, also one above 2**32."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    key = jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF)
    return jax.random.fold_in(key, int(words[1]) & 0x7FFFFFFF)


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def layer(key, i, s: dict) -> dict:
    """Layer ``i``'s weights as a flat dict of float32 arrays."""
    d, h, kv = s["hidden_size"], s["num_attention_heads"], \
        s["num_key_value_heads"]
    dh, f = s["head_dim"], s["intermediate_size"]
    lk = jax.random.fold_in(jax.random.fold_in(key, 1000), i)
    k = {n: jax.random.fold_in(lk, j) for j, n in enumerate(LAYER_LEAVES)}
    w = {
        "wq": _normal(k["wq"], (d, h * dh), d ** -0.5),
        "wk": _normal(k["wk"], (d, kv * dh), d ** -0.5),
        "wv": _normal(k["wv"], (d, kv * dh), d ** -0.5),
        "wo": _normal(k["wo"], (h * dh, d), (h * dh) ** -0.5),
        "ln1": 1.0 + _normal(k["ln1"], (d,), 0.1),
        "ln2": 1.0 + _normal(k["ln2"], (d,), 0.1),
        "wg": _normal(k["wg"], (d, f), d ** -0.5),
        "wu": _normal(k["wu"], (d, f), d ** -0.5),
        "wd": _normal(k["wd"], (f, d), f ** -0.5),
    }
    if s["attention_bias"]:
        w["bq"] = _normal(k["bq"], (h * dh,), 0.5)
        w["bk"] = _normal(k["bk"], (kv * dh,), 0.5)
        w["bv"] = _normal(k["bv"], (kv * dh,), 0.5)
    return w


def embedding(key, s: dict):
    """(vocab, d) input embedding rows."""
    return _normal(jax.random.fold_in(key, EMB),
                   (s["vocab_size"], s["hidden_size"]), 1.0)


def head(key, s: dict):
    """(d, vocab) output projection."""
    return _normal(jax.random.fold_in(key, HEAD),
                   (s["hidden_size"], s["vocab_size"]),
                   s["hidden_size"] ** -0.5)


def final_norm(key, s: dict):
    return 1.0 + _normal(jax.random.fold_in(key, FINAL_NORM),
                         (s["hidden_size"],), 0.1)


def program_params(key, s: dict, padded_vocab: int) -> dict:
    """All weights in the program's parameter layout: layers stacked on a
    leading axis (``layers/sub0/...``), the vocabulary padded with zero rows
    and columns to ``padded_vocab``.  Call inside a jit."""
    pad = padded_vocab - s["vocab_size"]
    stacked = jax.vmap(lambda i: layer(key, i, s))(
        jnp.arange(s["num_hidden_layers"]))
    attn = {n: stacked[n] for n in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if n in stacked}
    return {
        "emb": jnp.pad(embedding(key, s), ((0, pad), (0, 0))),
        "head": jnp.pad(head(key, s), ((0, 0), (0, pad))),
        "final_norm": {"w": final_norm(key, s)},
        "layers": {"sub0": {
            "ln1": {"w": stacked["ln1"]},
            "attn": attn,
            "ln2": {"w": stacked["ln2"]},
            "ffn": {n: stacked[n] for n in ("wg", "wu", "wd")},
        }},
    }
