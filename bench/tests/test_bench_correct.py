"""``correct`` against the plain reference, at a size the CPU holds.

The harness runs end to end past its look for a chip, on the qwen2.5
layer (q/k/v biases, GQA, the fused matmul and packed-KV kernels in
interpret mode) at tiny widths.  Sound runs are correct; the int4 control
reads far above the limit; and each fault a serving cell can have, planted
in the timed path after warm-up, makes ``correct`` false.
"""
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import jax.numpy as jnp
import pytest

import run as R

HERE = os.path.dirname(os.path.abspath(__file__))
# at this size sound runs read at most 0.07 and the control about 0.9
LIMIT = 0.3
MIX = {"loop": "open",
       "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
                  "max": 60},
       "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
                  "max": 20},
       "engine": {"slots": 4, "max_len": 96, "prefill_chunk": 16},
       "check": {"ref_tokens": 1024}}
# the batch mix's shape: a staggered closed backlog
BATCH = dict(MIX, loop="closed", block=8, pool=32, outstanding_per_slot=2,
             prompt={"dist": "uniform", "min": 8, "max": 24},
             output={"dist": "uniform", "min": 12, "max": 36})
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _cell(mix=MIX):
    with open(os.path.join(HERE, "tiny.json")) as f:
        cfg = json.load(f)
    return {"name": "tiny", "chips": 1, "config": cfg, "mix": mix,
            "engine": mix["engine"],
            "params": {"rate_per_s": 16.0,
                       "limits": {"max_logit_gap": LIMIT,
                                  "min_served_tokens": 20}},
            "end_to_end": [{"name": "ttft_p90_ms"}, {"name": "setup_s"}],
            "per_layer": []}


def _run(fault=None, control=False, seed=2**33 + 5, mix=MIX):
    return R.run(_cell(mix), seed, 1.5, False, require_tpu=False,
                 peaks=PEAKS, fault=fault, control=control)


@pytest.mark.parametrize("mix", [MIX, BATCH], ids=["chat", "batch"])
def test_sound_run_is_correct_and_the_control_is_not(mix):
    res = _run(control=True, mix=mix)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_tokens_compared"]["value"] >= 20
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ttft_p90_ms", "setup_s"}
    assert not res["control"]["correct"], res["control"]
    assert res["control"]["checks"]["max_logit_gap"]["value"] > 2 * LIMIT


def _stale_cache(eng):
    step = eng._decode
    eng._decode = lambda p, t, c, pos: (step(p, t, c, pos)[0], c)


def _half_batch(eng):
    step = eng._prefill
    keep = jnp.arange(eng.slots) % 2 == 0
    eng._prefill = lambda p, t, c, pos, nv: step(p, t, c, pos,
                                                 jnp.where(keep, nv, 0))


def _altered_token(eng):
    sample = eng.sampler
    calls = []

    def altered(logits):
        calls.append(1)
        tok = sample(logits)
        if len(calls) % 3 == 0:
            tok = (tok + 1) % eng.cfg.vocab
        return tok

    eng.sampler = altered


@pytest.mark.parametrize("fault", [_stale_cache, _half_batch,
                                   _altered_token],
                         ids=["state-unchanged", "half-batch",
                              "token-altered"])
def test_a_broken_timed_path_is_not_correct(fault):
    res = _run(fault=fault)
    assert not res["correct"], res["checks"]
