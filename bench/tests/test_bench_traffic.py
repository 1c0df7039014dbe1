"""The traffic generator: deterministic per seed, and the same work for
every seed."""
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import numpy as np
import pytest

import spec
import traffic


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_same_seed_same_requests(mix):
    m = spec.load_mix(mix)
    a = traffic.generate(m, {"rate_per_s": 3.0}, 2**33 + 7, 30, 1000, 8)
    b = traffic.generate(m, {"rate_per_s": 3.0}, 2**33 + 7, 30, 1000, 8)
    assert [(r.due, r.prompt, r.max_new) for r in a] == \
        [(r.due, r.prompt, r.max_new) for r in b]
    c = traffic.generate(m, {"rate_per_s": 3.0}, 2**33 + 8, 30, 1000, 8)
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_every_seed_gets_the_same_sizes(mix):
    """One schedule for every seed: the same sizes, gaps and order; only
    the token ids differ."""
    m = spec.load_mix(mix)
    sched = lambda seed: [(r.due, len(r.prompt), r.max_new, r.started)
                          for r in traffic.generate(m, {"rate_per_s": 3.2},
                                                    seed, 40, 1000, 8)]
    assert sched(1) == sched(2**31 + 99)
    assert len(sched(1)) == (128 if mix == "chat" else m["pool"])


def test_open_loop_window_is_one_block():
    """Each quantile of each distribution once in the window."""
    m = spec.load_mix("chat")
    reqs = traffic.generate(m, {"rate_per_s": 0.28}, 9, 51, 1000, 8)
    assert len(reqs) == 14
    assert sorted(len(r.prompt) for r in reqs) == sorted(
        traffic.quantiles(m["prompt"], 14))
    assert sorted(r.max_new for r in reqs) == sorted(
        traffic.quantiles(m["output"], 14))


def test_staggered_backlog():
    """The first ``slots`` requests are under way at staggered progress:
    what each has produced joins its prompt, and what remains of its
    answer is its ``max_new``; the rest are whole requests."""
    m = spec.load_mix("batch")
    slots = m["engine"]["slots"]
    reqs = traffic.generate(m, {}, 2**31 + 5, 51, 1000, slots)
    plain = traffic.generate(m, {}, 2**31 + 5, 51, 1000, 0)
    assert [r.started for r in reqs] == [i < slots for i in range(len(reqs))]
    made = [len(r.prompt) - len(p.prompt) for r, p in zip(reqs, plain)]
    assert made[slots:] == [0] * (len(reqs) - slots)
    for r, p, k in zip(reqs[:slots], plain, made):
        assert 0 <= k < p.max_new and r.max_new == p.max_new - k
        assert len(r.prompt) + r.max_new <= m["engine"]["max_len"]
    share = sorted(k / p.max_new for k, p in zip(made[:slots], plain))
    assert share == pytest.approx([(i + 0.5) / slots for i in range(slots)],
                                  abs=2e-3)


def test_open_loop_rate_and_window():
    m = spec.load_mix("chat")
    reqs = traffic.generate(m, {"rate_per_s": 4.0}, 5, 32, 1000, 8)
    assert len(reqs) == 128
    due = np.array([r.due for r in reqs])
    assert due[0] == 0 and np.all(np.diff(due) > 0)
    assert 28 < due[-1] < 32
    for r in reqs:
        assert m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
        assert m["output"]["min"] <= r.max_new <= m["output"]["max"]
        assert max(r.prompt) < 1000


def test_quantiles_follow_the_distribution():
    q = traffic.quantiles({"dist": "lognormal", "median": 384, "sigma": 0.9,
                           "min": 64, "max": 2048}, 16)
    assert q.min() >= 64 and q.max() <= 2048
    assert abs(np.median(q) - 384) < 60
    u = traffic.quantiles({"dist": "uniform", "min": 512, "max": 1024}, 32)
    assert u.min() >= 512 and u.max() <= 1024
    assert abs(u.mean() - 768) < 2
