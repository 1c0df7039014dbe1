"""The benchmark's own weights: the program's layout, and the same values
whether stacked for the program or made one layer at a time for the
reference."""
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import jax
import numpy as np
import pytest

import spec
import weights as W

HERE = os.path.dirname(os.path.abspath(__file__))


def _sizes():
    with open(os.path.join(HERE, "tiny.json")) as f:
        cfg = json.load(f)
    return cfg, spec.sizes(cfg)


@pytest.mark.parametrize("name", ["qwen2.5-32b-l4", "h2o-danube-1.8b"])
def test_layout_is_the_programs(name):
    from repro.models import model as M
    cfg = spec.load_config(name)
    prog = spec.program_config(cfg)
    ours = jax.eval_shape(lambda k: W.program_params(
        k, spec.sizes(cfg), prog.padded_vocab), W.root_key(0))
    theirs = jax.eval_shape(lambda k: M.init_params(k, prog),
                            jax.random.PRNGKey(0))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    assert shape(ours) == shape(theirs)


def test_stacked_layers_equal_layers_made_alone():
    _, s = _sizes()
    key = W.root_key(2**40 + 3)
    p = W.program_params(key, s, 640)
    for i in range(s["num_hidden_layers"]):
        alone = W.layer(key, i, s)
        np.testing.assert_array_equal(p["layers"]["sub0"]["attn"]["wq"][i],
                                      alone["wq"])
        np.testing.assert_array_equal(p["layers"]["sub0"]["ffn"]["wd"][i],
                                      alone["wd"])
        np.testing.assert_array_equal(p["layers"]["sub0"]["attn"]["bk"][i],
                                      alone["bk"])
    np.testing.assert_array_equal(p["emb"][:512], W.embedding(key, s))
    assert not np.any(p["emb"][512:]) and not np.any(p["head"][:, 512:])


def test_seeds_give_different_weights():
    _, s = _sizes()
    a = W.layer(W.root_key(1), 0, s)["wq"]
    b = W.layer(W.root_key(2), 0, s)["wq"]
    assert not np.allclose(a, b)
