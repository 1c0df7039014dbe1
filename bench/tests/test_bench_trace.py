"""The reduction from a trace to busy time, idle share, kernel time and
the metric readers, on a small synthetic trace."""
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import numpy as np
import pytest

import run as R
import spec
import trace_reduce as tr
import work

MS = 1e6  # ns per millisecond

# two ticks on a 0..100 ms window: tick 1 at 0..40 ms, tick 2 at 60..100 ms
PRE = ("%mxsf_fused_matmul_pallas.7 = f32[8,256]{1,0:T(8,128)} custom-call("
       "bf16[8,128]{1,0:T(8,128)(2,1)} %fusion.2, u8[128,256]{1,0} %p)")
DEC = ("%mxsf_fused_matmul_pallas.7 = f32[2,256]{1,0:T(2,128)} custom-call("
       "bf16[2,128]{1,0:T(2,128)(2,1)} %fusion.4, u8[128,256]{1,0} %p)")
ATT = "%_flash_attention_jit.3 = bf16[8,1,32]{2,1,0} custom-call(s32[8] %f)"
OPS = [("%fusion.1 = f32[8]", 0 * MS, 10 * MS),
       (PRE, 5 * MS, 20 * MS),                   # overlaps fusion.1
       (ATT, 25 * MS, 30 * MS),
       (DEC, 65 * MS, 80 * MS),
       ("%while.2 = (s32[])", 60 * MS, 81 * MS),  # a loop around the body
       # an op that reads the kernel's output is not the kernel
       ("%slice.9 = bf16[2,64]{1,0} slice(f32[2,256]{1,0} "
        "%mxsf_fused_matmul_pallas.7)", 81 * MS, 81 * MS),
       ("%copy.3 = u8[4]", 90 * MS, 120 * MS)]   # runs past the window
SPANS = [("bench.tick", 0 * MS, 40 * MS), ("bench.wait", 40 * MS, 60 * MS),
         ("bench.tick", 60 * MS, 100 * MS)]


def test_busy_is_the_union_inside_the_window():
    assert tr.union([(0, 10), (5, 20), (25, 30)]) == [(0, 20), (25, 30)]
    # 0..20, 25..30, 60..81, 90..100 (cut at the window's end)
    assert tr.busy(OPS, 0, 100 * MS) == pytest.approx(0.056)


def test_kernel_sums_and_top_ops():
    secs, n = tr.kernel_time(OPS, "mxsf_fused_matmul_pallas", 0, 100 * MS)
    assert (secs, n) == (pytest.approx(0.030), 2)
    top = tr.top_ops(OPS, 0, 100 * MS)
    assert top[0] == [PRE, pytest.approx(0.015)]
    assert len(top) == 6 and not any(n.startswith("%while") for n, _ in top)


def test_idle_gaps_are_named_by_the_host_span():
    gaps = tr.idle_gaps(OPS, SPANS, 0, 100 * MS)
    assert gaps[0] == ["bench.wait", pytest.approx(0.030)]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        sorted([0.005, 0.030, 0.009]))


def _traced(loop):
    cell = {"mix": {"loop": loop}, "config": {}}
    s = {"num_hidden_layers": 1, "hidden_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
         "intermediate_size": 256, "vocab_size": 1000,
         "sliding_window": None}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e10}
    # tick 1: a prefill dispatch (slot 0 prefills 3 rows at 0); tick 2: a
    # decode dispatch (slot 0 decodes at position 3)
    t1 = R.Tick(0.0, 0.04, np.array([0, 0]), np.array([3, 0]),
                np.array([True, False]), 0, 1, 1)
    t2 = R.Tick(0.06, 0.1, np.array([3, 0]), np.array([1, 0]),
                np.array([False, False]), 1, 0, 1)
    return R.Traced(cell, s, spec.arch(cell["config"]), peaks, 2, 4,
                    [t1, t2], OPS, SPANS, 0, 100 * MS)


def test_useful_row_share():
    got = spec.reader("useful_row_share.chat")(_traced("open"))
    # 4 useful rows over 2 * 4 (prefill) + 2 (decode) computed
    assert got["value"] == pytest.approx(100 * 4 / 10)


def test_idle_share_chat_counts_tick_spans_only():
    run = _traced("open")
    # ticks cover 80 ms; busy inside them: 0..20, 25..30, 60..81, 90..100
    assert spec.reader("device_idle_share.chat")(run)["value"] == \
        pytest.approx(100 * (1 - 56 / 80))
    assert spec.reader("device_idle_share.batch")(_traced("closed"))[
        "value"] == pytest.approx(100 * (1 - 56 / 100))


def test_fused_matmul_roofline_reads_shapes_from_the_trace():
    run = _traced("open")
    got = spec.reader("fused_matmul_roofline.chat")(run)
    floor = sum(work.least_time(*work.matmul(m, 128, 256), run.peaks)[0]
                for m in (8, 2))
    assert got["value"] == pytest.approx(100 * floor / 0.030)
    assert got["bound"] == "memory"


def test_attention_roofline_and_mfu():
    run = _traced("closed")
    got = spec.reader("attn_kernel_roofline.batch")(run)
    s, pk = run.sizes, run.peaks
    floor = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                for f, b in (work.attn_rows(s, [0], [3]),
                             work.attn_rows(s, [3], [1])))
    assert got["bound"] == "memory"
    assert got["value"] == pytest.approx(100 * floor / 0.005)
    mfu = spec.reader("step_mfu.batch")(run)
    flops = (4 * work.layer_flops(s) + 2 * 2 * 128 * 1000
             + work.attn_rows(s, [0], [3])[0] + work.attn_rows(s, [3], [1])[0])
    assert mfu["value"] == pytest.approx(100 * flops / (0.056 * 1e12))


def test_a_reader_with_nothing_to_read_returns_nothing():
    run = _traced("closed")
    run.ops = [("%fusion.1 = f32[8]", 0, 10 * MS)]
    assert spec.reader("fused_matmul_roofline.batch")(run) is None
    assert spec.reader("attn_kernel_roofline.batch")(run) is None
