"""decode_step_ms: median device time of one decode step, in ms.

Each execution of the compiled module ``jit_serve_decode_step`` in the
traced ticks, as the device's module line times it.  Layer: the serving
steps (``models/decoding.py``).
"""
import numpy as np

import program_trace as pt

MODULE = "jit_serve_decode_step"


def read(run):
    trace = pt.load()
    if trace is None:
        return None
    ms = pt.step_ms(trace.steps, MODULE, run.t0_ns, run.t1_ns)
    if not ms:
        return None
    return {"value": float(np.median(ms))}
