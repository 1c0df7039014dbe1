"""step_mfu: useful model FLOPs of the serving steps over the device's
busy time at the chip's bf16 peak.

Useful FLOPs: every linear on the useful rows (prompt rows prefilled and
decode rows), the output head only on the rows whose logits give a token,
and attention over each row's visible positions, as the configuration's
architecture module counts them (``layer_flops``, ``head_shape``,
``attn_rows``).  Busy time is the union of device operations over the traced
ticks.  Layer: the serving steps (``models/decoding.py``).
"""
import numpy as np

import trace_reduce as tr


def read(run):
    s, A = run.sizes, run.arch
    layers = s["num_hidden_layers"]
    k, n = A.head_shape(s)
    flops = 0.0
    for t in run.ticks:
        flops += float(t.rows.sum()) * A.layer_flops(s) * layers
        flops += t.emitted * 2.0 * k * n
        flops += layers * A.attn_rows(s, t.starts, t.rows)[0]
    busy = tr.busy(run.ops, run.t0_ns, run.t1_ns)
    if busy <= 0 or flops <= 0:
        return None
    return {"value": 100.0 * flops / (busy * run.peaks["bf16_flops_per_s"]),
            "bound": "compute"}
