"""Operations and bytes that the work of a serving step needs.

These count the work, not what today's kernels do: a matmul's rows are
the rows the step hands it, never tile padding; attention reads each
slot's live positions once per kv head, never the whole cache width.  So
a kernel that stops doing needless work raises its roofline share, and a
share above 100% means a count or a time is wrong.

Byte sizes follow the served precision: weights and the KV cache as
1-byte MXSF codes plus one 1-byte E8M0 scale per 64 (weights) or per
head row (cache); activations into a linear in bfloat16, its output in
float32; attention queries and outputs in bfloat16.
"""
from __future__ import annotations

import re
from typing import Iterable, List, Optional, Tuple

W_BLOCK = 64      # MXSF weight block along the contraction
ACT_IN, ACT_OUT = 2, 4
ATTN_IO = 2


def linear_shapes(s: dict) -> List[Tuple[int, int]]:
    """(K, N) of each linear of one layer."""
    d, h, kv = s["hidden_size"], s["num_attention_heads"], \
        s["num_key_value_heads"]
    dh, f = s["head_dim"], s["intermediate_size"]
    return [(d, h * dh), (d, kv * dh), (d, kv * dh), (h * dh, d),
            (d, f), (d, f), (f, d)]


def head_shape(s: dict) -> Tuple[int, int]:
    return s["hidden_size"], s["vocab_size"]


def matmul(m: int, k: int, n: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one fused quantize->matmul call: an (m, k)
    activation against a packed (k, n) weight, weight read once."""
    flops = 2.0 * m * k * n
    byts = k * n * (1 + 1 / W_BLOCK) + m * k * ACT_IN + m * n * ACT_OUT
    return flops, byts


_CALL = re.compile(r"= \w+\[(\d+),(\d+)\][^=]*?custom-call\(\w+\[(\d+),(\d+)\]")


def traced_matmul(op_name: str) -> Optional[Tuple[int, int, int]]:
    """(m, k, n) of a matmul kernel call from its op's HLO text in the
    trace, ``%name = f32[m,n]{...} custom-call(bf16[m,k]{...} ...``."""
    hit = _CALL.search(op_name)
    if hit is None:
        return None
    m, n, m2, k = map(int, hit.groups())
    return (m, k, n) if m == m2 else None


def least_time(flops: float, byts: float, peaks: dict) -> Tuple[float, str]:
    """Seconds the work needs at the chip's peaks, and its bound."""
    tf, tm = flops / peaks["bf16_flops_per_s"], byts / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tm else (tm, "memory")


def attn_rows(s: dict, starts: Iterable[int],
              rows: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one layer's cached attention over a batch of
    slots: slot ``i`` computes ``rows[i]`` query rows at positions
    ``starts[i]..starts[i]+rows[i]-1``, each attending causally to the
    positions before it (within the sliding window, if any).

    FLOPs: 4 * dh per (query head, visible key) pair (QK^T and PV).
    Bytes: codes and scales of each slot's live positions (those the last
    row sees) once per kv head, for K and V, plus queries and outputs."""
    h, kv, dh = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    win = s["sliding_window"] or None
    flops = byts = 0.0
    for p, r in zip(starts, rows):
        if r <= 0:
            continue
        vis = _visible(p, r, win)
        live = p + r if win is None else min(p + r, win)
        flops += 4.0 * h * dh * vis
        byts += 2.0 * kv * live * (dh + 1) + r * h * dh * 2 * ATTN_IO
    return flops, byts


def _visible(p: int, r: int, win) -> int:
    """Sum over q in [p, p+r) of the keys q sees: min(q+1, win)."""
    a, b = p + 1, p + r          # sum of min(x, win) for x in [a, b]
    if win is None or b <= win:
        return (a + b) * (b - a + 1) // 2
    if a > win:
        return win * (b - a + 1)
    return (a + win) * (win - a + 1) // 2 + win * (b - win)


def layer_flops(s: dict) -> float:
    """Linear FLOPs of one row through one layer."""
    return sum(2.0 * k * n for k, n in linear_shapes(s))
