"""Architecture modules: a configuration names its own (``"arch"``), the
dense module is the harness as it was before modules, and a new
architecture needs new files only."""
import json
import os
import re
import shutil
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.dirname(os.path.abspath(__file__)),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import jax
import numpy as np
import pytest

import reference
import run as R
import spec
import weights as W
import work
from test_bench_correct import MIX
from test_bench_trace import _traced

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIGS = ["tiny", "qwen2.5-32b-l4", "h2o-danube-1.8b"]
# the keys the harness read before architecture modules
DENSE_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "rope_theta", "rms_norm_eps", "attention_bias",
              "sliding_window")


def _config(name):
    if name == "tiny":
        with open(os.path.join(HERE, "tiny.json")) as f:
            return json.load(f)
    return spec.load_config(name)


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_module_is_the_harness_as_it_was(name):
    cfg = _config(name)
    assert "arch" not in cfg
    A = spec.arch(cfg)
    assert A is spec.arch(dict(cfg, arch="dense"))
    assert A.__file__ == os.path.join(spec.HERE, "archs", "dense.py")
    s = A.sizes(cfg)
    assert s == {k: cfg[k] for k in DENSE_KEYS} == spec.sizes(cfg)
    prog = A.program_config(cfg)
    assert prog == spec.program_config(cfg)
    key = W.root_key(2**40 + 3)
    ours = lambda k: A.program_params(k, s, prog.padded_vocab)
    before = lambda k: W.program_params(k, s, prog.padded_vocab)
    if name == "tiny":
        jax.tree.map(np.testing.assert_array_equal, ours(key), before(key))
    else:
        shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
        assert shape(jax.eval_shape(ours, key)) == \
            shape(jax.eval_shape(before, key))
    assert A.layer_flops(s) == work.layer_flops(s) == sum(
        2.0 * k * n for k, n in work.linear_shapes(s))
    assert A.head_shape(s) == work.head_shape(s) == (cfg["hidden_size"],
                                                     cfg["vocab_size"])
    starts, rows = [0, 100, 4000, 7], [256, 1, 1, 0]
    assert A.attn_rows(s, starts, rows) == work.attn_rows(s, starts, rows)


def test_dense_served_gaps_are_the_reference():
    cfg = _config("tiny")
    A = spec.arch(cfg)
    s = A.sizes(cfg)
    rng = np.random.default_rng(5)
    samples = [(rng.integers(0, 512, n).tolist(),
                rng.integers(0, 512, m).tolist()) for n, m in ((9, 6),
                                                              (20, 3))]
    got = A.served_gaps(s, 2**35 + 1, samples, 512, control=True)
    want = reference.served_gaps(s, 2**35 + 1, samples, 512, control=True)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


# a new architecture: the dense decoder, recording which of its functions
# the harness calls
TOY = '''
import spec

CALLS = []
_dense = spec.arch({})
PROGRAM_KEYS = _dense.PROGRAM_KEYS


def _recorded(name):
    f = getattr(_dense, name)

    def call(*args, **kw):
        CALLS.append(name)
        return f(*args, **kw)
    return call


for _name in ("sizes", "program_config", "program_params", "served_gaps",
              "layer_flops", "head_shape", "attn_rows"):
    globals()[_name] = _recorded(_name)
'''


def test_a_new_architecture_needs_new_files_only(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(spec.HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", ".scratch", "tests"))
    (bench / "archs" / "toy.py").write_text(TOY)
    cfg = dict(_config("tiny"), name="tiny-toy", arch="toy")
    (bench / "configs" / "tiny-toy.json").write_text(json.dumps(cfg))
    (bench / "mixes" / "tiny-chat.json").write_text(json.dumps(MIX))
    bench_json = {
        "workloads": [{"name": "toy-chat", "config": "tiny-toy",
                       "traffic": "tiny-chat", "chips": 1}],
        "end_to_end": [{"name": "setup_s"}],
        "per_layer": [{"name": "step_mfu.chat"}]}
    monkeypatch.setattr(spec, "HERE", str(bench))

    cell = spec.load_cell("toy-chat", bench_json)
    A = spec.arch(cell["config"])
    assert A.__file__ == str(bench / "archs" / "toy.py")
    prog, eng = R.build(cell, 11)
    assert prog.n_layers == cfg["num_hidden_layers"]
    assert {"sizes", "program_config", "program_params"} <= set(A.CALLS)

    cell["params"] = {"limits": {"max_logit_gap": 1.0,
                                 "min_served_tokens": 1}}
    checks, _ = R.check(cell, 11, [([1, 2, 3, 4], [5, 6])])
    assert "served_gaps" in A.CALLS
    assert checks["served_tokens_compared"]["value"] == 2
    assert np.isfinite(checks["max_logit_gap"]["value"])

    run = _traced("closed")
    run.cell, run.arch = cell, A
    run.sizes = A.sizes(cell["config"])
    assert spec.reader("step_mfu.chat")(run)["value"] > 0
    assert {"layer_flops", "head_shape", "attn_rows"} <= set(A.CALLS)


def test_an_unknown_architecture_names_the_directory():
    where = re.escape(os.path.join(spec.HERE, "archs"))
    with pytest.raises(FileNotFoundError, match=where):
        spec.arch({"arch": "no-such-arch"})
