"""useful_row_share: rows that did work over rows the steps computed.

Useful rows are the prompt tokens prefilled plus the decode rows of slots
in their decode phase, summed over the traced ticks from the positions the
slots advanced: the span the other per-layer metrics read (over the whole
window, a traced run would skew, since writing the trace stalls the
window's later ticks).  Computed rows are what the engine hands its two
steps: ``slots`` rows per decode dispatch and ``slots * prefill_chunk`` per
prefill dispatch.  Layer: the engine tick (``serve/engine.py``).
"""


def read(run):
    useful = sum(int(t.rows.sum()) for t in run.ticks)
    computed = sum(t.decode_dispatches * run.slots
                   + t.prefill_dispatches * run.slots * run.chunk
                   for t in run.ticks)
    if computed == 0:
        return None
    return {"value": 100.0 * useful / computed}
