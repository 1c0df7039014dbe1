"""device_idle_share: share of the window in which no operation ran on
the device.

Open-loop (chat) cells count only the harness's own tick spans, so the
gaps while no request is waiting do not count; closed-loop cells count
the whole traced span.  Layer: the device.
"""
import trace_reduce as tr


def read(run):
    ops = [(a, b) for _, a, b in tr.clip(run.ops, run.t0_ns, run.t1_ns)]
    busy = tr.union(ops)
    if run.cell["mix"]["loop"] == "open":
        window = tr.union((a, b) for n, a, b in run.spans
                          if n == "bench.tick" and a >= run.t0_ns
                          and b <= run.t1_ns)
    else:
        window = [(run.t0_ns, run.t1_ns)]
    length = sum(b - a for a, b in window)
    if length <= 0:
        return None
    return {"value": 100.0 * (1.0 - tr.overlap(busy, window) / length)}
