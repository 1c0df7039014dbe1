"""Find an open-loop cell's knee: one engine, several fixed rates.

    python bench/sweep.py --workload qwen32b-chat --seed 7 --seconds 20 \
        --rates 2,3,4,5

For each rate, one window of the cell's traffic at that rate, then the
engine is drained.  Prints one JSON line per rate: requests due and
finished, the backlog left at the window's end, TTFT and inter-token
percentiles and tokens/s.  The knee is the highest rate whose backlog
does not grow.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    R.devices(cell["chips"], True)
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    _, eng = R.build(cell, a.seed)
    vocab = cell["config"]["vocab_size"]
    R.warm(eng, a.seed, vocab)
    for rate in (float(r) for r in a.rates.split(",")):
        params = dict(cell["params"], rate_per_s=rate)
        reqs = traffic.generate(cell["mix"], params, a.seed, a.seconds,
                                vocab, eng.slots)
        win = R.drive(eng, cell["mix"], reqs, a.seconds)
        ttft, itl = R.latencies(win)
        row = {"rate_per_s": rate,
               "due": sum(r.due <= a.seconds for r in win.recs),
               "finished": sum(r.req.done for r in win.recs),
               "backlog_at_end": len(eng.queue) + sum(
                   r is not None for r in eng.live),
               "queued_at_end": len(eng.queue),
               "ttft_p50_ms": R.pct(ttft, 50) * 1e3,
               "ttft_p90_ms": R.pct(ttft, 90) * 1e3,
               "itl_p50_ms": R.pct(itl, 50) * 1e3 if itl else None,
               "itl_p95_ms": R.pct(itl, 95) * 1e3 if itl else None,
               "tokens_per_s": win.tokens / win.elapsed,
               "ticks": len(win.ticks)}
        print(json.dumps(row), flush=True)
        eng.queue.clear()
        eng.run()


if __name__ == "__main__":
    main()
