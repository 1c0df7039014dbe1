"""Tails and rates are taken over every request due in the window."""
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import types

import numpy as np
import pytest

import run as R


def _rec(due, times, done):
    return R.Rec(due, types.SimpleNamespace(done=done, out=[0] * len(times)),
                 list(times))


def _window():
    recs = [_rec(0.0, [0.5, 0.6, 0.7], True),      # finished
            _rec(1.0, [1.2, 1.4], False),          # still decoding at 2.0
            _rec(1.5, [], False)]                  # never got a token
    return R.Window(2.0, recs, [], tokens=5)


def test_unfinished_requests_count_at_their_elapsed_time():
    ttft, itl = R.latencies(_window())
    assert ttft == pytest.approx([0.5, 0.2, 0.5])
    assert sorted(itl) == pytest.approx(sorted([0.1, 0.1, 0.2, 0.6]))


def test_end_to_end_metrics():
    m = R.end_to_end(_window(), 12.5)
    assert m["setup_s"] == {"value": 12.5, "unit": "s"}
    assert m["tokens_per_s"]["value"] == pytest.approx(2.5)
    assert m["ttft_p90_ms"]["value"] == pytest.approx(
        np.percentile([500, 200, 500], 90))
    assert m["itl_p50_ms"]["value"] == pytest.approx(
        np.percentile([100, 100, 200, 600], 50))


def test_window_report_names_collector_pauses_and_slow_ticks():
    import gc
    pauses: list = []
    watch = R._gc_watch(pauses)
    gc.callbacks.append(watch)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(watch)
    assert pauses and pauses[-1][0] == 2 and pauses[-1][1] >= 0.0
    ticks = [R.Tick(t0, t1, None, None, None, 1, int(pre), 1)
             for t0, t1, pre in [(0.0, 0.1, 0), (0.1, 0.2, 0), (0.2, 0.9, 0),
                                 (0.9, 3.0, 1)]]
    win = R.Window(3.0, [], ticks, gc_pauses=[(0, 0.001), (2, 0.25)])
    line = R.window_report(win)
    assert "2 collections (1 full), 0.2510 s, longest 0.2500 s" in line
    assert ("decode ticks: 3, median 100.00 ms, over it 0.6000 s, "
            "longest 700.0 ms") in line
    assert "prefill ticks: 1, median 2100.00 ms, over it 0.0000 s" in line
