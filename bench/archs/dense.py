"""Dense GQA decoder (Qwen2 / Mistral family): RMSNorm, rotary positions,
grouped-query attention with optional q/k/v biases and sliding window,
SwiGLU MLP, untied head.

An architecture module gives the harness everything that depends on the
layer's equations; a configuration file names its module with ``"arch"``
(``spec.arch``, ``"dense"`` when the key is absent):

  * ``sizes(config)``: the sizes that the reference and the counts read
  * ``program_config(config)``: the program's ``ModelConfig``
  * ``program_params(key, s, padded_vocab)``: seeded weights in the
    program's parameter layout (``weights.py``)
  * ``served_gaps(s, seed, samples, T, control=False)``: the plain
    reference's comparison and its control (``reference.py``)
  * ``layer_flops(s)``, ``head_shape(s)``, ``attn_rows(s, starts, rows)``:
    the work counts that the metric readers take (``work.py``)
  * ``PROGRAM_KEYS``: program config field <- published key, for each
    size that ``program_config`` copies (the tests check the cut by it)
"""
from __future__ import annotations

from reference import served_gaps  # noqa: F401
from weights import program_params  # noqa: F401
from work import attn_rows, head_shape, layer_flops  # noqa: F401

# program config field <- published (Hugging Face config.json) key
PROGRAM_KEYS = {
    "n_layers": "num_hidden_layers",
    "d_model": "hidden_size",
    "n_heads": "num_attention_heads",
    "n_kv": "num_key_value_heads",
    "d_head": "head_dim",
    "d_ff": "intermediate_size",
    "vocab": "vocab_size",
    "rope_theta": "rope_theta",
}


def sizes(config: dict) -> dict:
    """The model sizes the reference and the work counts read."""
    return {k: config[k] for k in (
        "num_hidden_layers", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
        "rope_theta", "rms_norm_eps", "attention_bias", "sliding_window")}


def program_config(config: dict):
    """The program's ModelConfig for a configuration file: the registry
    entry named by ``model`` with the file's sizes applied."""
    from repro.configs.base import get_config
    base = get_config(config["model"])
    kw = {field: config[key] for field, key in PROGRAM_KEYS.items()}
    kw["qkv_bias"] = bool(config["attention_bias"])
    if config["sliding_window"]:
        kw.update(swa_window=config["sliding_window"], swa_pattern="all")
    else:
        kw.update(swa_window=None, swa_pattern="none")
    return base.replace(name=config["name"], **kw)
