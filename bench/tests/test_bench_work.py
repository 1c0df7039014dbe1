"""Operation and byte counts against hand computations."""
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import pytest

import work

S = {"num_hidden_layers": 2, "hidden_size": 128, "num_attention_heads": 4,
     "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
     "vocab_size": 1000, "sliding_window": None}


def test_one_matmul_call():
    f, b = work.matmul(8, 128, 256)
    assert f == 2 * 8 * 128 * 256
    # codes + one scale per 64 weights, bf16 in, f32 out
    assert b == 128 * 256 + 128 * 256 // 64 + 8 * 128 * 2 + 8 * 256 * 4


def test_layer_linears():
    assert work.linear_shapes(S) == [(128, 128), (128, 64), (128, 64),
                                     (128, 128), (128, 256), (128, 256),
                                     (256, 128)]
    assert work.layer_flops(S) == 2 * (128 * 128 * 2 + 128 * 64 * 2
                                       + 128 * 256 * 3)


def test_attention_reads_live_positions_once_per_kv_head():
    # one decode row at position 99: sees 100 keys, 4 q heads
    f, b = work.attn_rows(S, [99], [1])
    assert f == 4 * 32 * 4 * 100
    # K and V: 2 kv heads x 100 live positions x (32 codes + 1 scale),
    # plus the query and output rows in bf16 — not 4 q heads' worth
    assert b == 2 * 2 * 100 * 33 + 1 * 4 * 32 * 2 * 2


def test_attention_chunk_is_causal_and_windowed():
    # a 4-row chunk at positions 10..13 sees 11+12+13+14 keys
    f, _ = work.attn_rows(S, [10], [4])
    assert f == 4 * 32 * 4 * (11 + 12 + 13 + 14)
    win = dict(S, sliding_window=12)
    f, b = work.attn_rows(win, [10], [4])
    assert f == 4 * 32 * 4 * (11 + 12 + 12 + 12)
    assert b == 2 * 2 * 12 * 33 + 4 * 4 * 32 * 2 * 2
    assert work.attn_rows(S, [5, 0], [0, 0]) == (0.0, 0.0)


def test_least_time_names_its_bound():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f, b = work.matmul(8, 5120, 27648)
    assert work.least_time(f, b, peaks) == (b / 819e9, "memory")
    f, b = work.matmul(2048, 5120, 27648)
    assert work.least_time(f, b, peaks) == (f / 197e12, "compute")


def test_matmul_shape_from_the_trace():
    op = ("%mxsf_fused_matmul_pallas.78 = f32[2048,153600]{1,0:T(8,128)} "
          "custom-call(bf16[2048,5120]{1,0:T(8,128)(2,1)S(1)} %bitcast.1, "
          "u8[5120,153600]{1,0} %p)")
    assert work.traced_matmul(op) == (2048, 5120, 153600)
    assert work.traced_matmul("%fusion.2 = f32[8,8]{1,0} fusion()") is None
