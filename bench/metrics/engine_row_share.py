"""engine_row_share: rows that did work over rows the steps computed, as
the engine counts them.

Sums the ``rows_useful`` and ``rows_computed`` arguments of the engine's
``engine.decode`` and ``engine.prefill`` spans in the traced ticks: the
engine counts what it hands each step, so a ragged dispatch is followed
without an edit here.  Layer: the engine tick (``serve/engine.py``).
"""
import program_trace as pt

PHASES = ("engine.decode", "engine.prefill")


def read(run):
    trace = pt.load()
    if trace is None:
        return None
    useful = computed = 0
    for name, _, _, args in pt.inside(trace.spans, run.t0_ns, run.t1_ns):
        if name in PHASES:
            useful += args.get("rows_useful", 0)
            computed += args.get("rows_computed", 0)
    if computed == 0:
        return None
    return {"value": 100.0 * useful / computed}
