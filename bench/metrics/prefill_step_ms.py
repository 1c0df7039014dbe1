"""prefill_step_ms: median device time of one prefill step (one chunk of
up to C prompt tokens per slot), in ms.

Each execution of the compiled module ``jit_serve_prefill_step`` in the
traced ticks.  Layer: the serving steps (``models/decoding.py``).
"""
import numpy as np

import program_trace as pt

MODULE = "jit_serve_prefill_step"


def read(run):
    trace = pt.load()
    if trace is None:
        return None
    ms = pt.step_ms(trace.steps, MODULE, run.t0_ns, run.t1_ns)
    if not ms:
        return None
    return {"value": float(np.median(ms))}
