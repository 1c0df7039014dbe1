"""Split a cell's device-idle time by what the host was doing.

    python bench/idle_split.py --workload danube-batch --seed 7

One traced window of the cell as a ``--trace 1`` run makes it (the first
``run.TRACE_SECONDS`` of the window, after the same set-up), read with
``program_trace``.  Prints one JSON line: the traced ticks' span and busy
time, the device's idle seconds split by the innermost ``engine.*`` span
over each gap (``harness`` where only the harness's tick span is open,
``between ticks`` outside every tick), each span's count and median, and
the two steps' device times.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
from collections import defaultdict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import program_trace as pt  # noqa: E402
import run as R  # noqa: E402
import spec  # noqa: E402
import trace_reduce as tr  # noqa: E402
import traffic  # noqa: E402

STEPS = ("jit_serve_decode_step", "jit_serve_prefill_step")


def summary(trace: pt.ProgramTrace, ticks) -> dict:
    """The split of the window the harness's ``ticks`` spans, from
    ``trace``."""
    t0, t1 = min(t[1] for t in ticks), max(t[2] for t in ticks)
    spans = pt.inside(trace.spans, t0, t1)
    busy = tr.busy([o[:3] for o in trace.ops], t0, t1)
    per = defaultdict(list)
    for name, a, b, _ in spans:
        per[name].append((b - a) * 1e-6)
    out = {"window_s": (t1 - t0) * 1e-9, "busy_s": busy,
           "idle_s": pt.idle_by_span(trace.ops, spans, ticks, t0, t1),
           "spans": {n: {"count": len(v), "median_ms": float(np.median(v))}
                     for n, v in sorted(per.items())}}
    for module in STEPS:
        ms = pt.step_ms(trace.steps, module, t0, t1)
        if ms:
            out[module] = {"count": len(ms),
                           "median_ms": float(np.median(ms))}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    R.devices(cell["chips"], True)
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    _, eng = R.build(cell, a.seed)
    vocab = cell["config"]["vocab_size"]
    R.warm(eng, a.seed, vocab)
    # the schedule of a whole window, of which the trace takes the start
    reqs = traffic.generate(cell["mix"], cell["params"], a.seed,
                            spec.benchmark()["run_seconds"], vocab,
                            eng.slots)
    started = R.start(eng, reqs)
    gc.freeze()
    shutil.rmtree(R.TRACE_DIR, ignore_errors=True)
    R.drive(eng, cell["mix"], reqs, R.TRACE_SECONDS, R.TRACE_SECONDS,
            started)
    _, spans = tr.load(R.TRACE_DIR)
    ticks = [s for s in spans if s[0] == "bench.tick"]
    print(json.dumps(summary(pt.load(R.TRACE_DIR), ticks)), flush=True)
    shutil.rmtree(R.TRACE_DIR, ignore_errors=True)


if __name__ == "__main__":
    main()
