"""Reduce a profiler trace to device busy time, idle gaps and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
tuples; every other function works on those tuples, so the reduction is
tested on small synthetic traces.

  * op: ``(name, start_ns, end_ns)`` of one operation on a device, from
    the device planes' ``XLA Ops`` line
  * span: ``(name, start_ns, end_ns)`` of one host annotation the harness
    wrote (names starting with ``bench.``)

Host and device events share the profiler's clock.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Iterable, List, Tuple

Event = Tuple[str, float, float]


def load(trace_dir: str):
    """(ops per device plane, host spans) from the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            ops_line = lines.get("XLA Ops")
            if ops_line is None:
                continue
            devices[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                   for e in ops_line.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in ln.events
                             if e.name.startswith("bench."))
    return devices, spans


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(merged_a, merged_b) -> float:
    """Total length of the intersection of two disjoint sorted lists."""
    i = j = 0
    total = 0.0
    while i < len(merged_a) and j < len(merged_b):
        a0, a1 = merged_a[i]
        b0, b1 = merged_b[j]
        total += max(0.0, min(a1, b1) - max(a0, b0))
        if a1 < b1:
            i += 1
        else:
            j += 1
    return total


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to the window [t0, t1]; those outside are dropped."""
    return [(n, max(a, t0), min(b, t1)) for n, a, b in events
            if b > t0 and a < t1]


def busy(ops: Iterable[Event], t0: float, t1: float) -> float:
    """Seconds of [t0, t1] in which some operation ran (the union)."""
    return sum(b - a for a, b in union((a, b) for _, a, b in
                                       clip(ops, t0, t1))) * 1e-9


def op_name(event: str) -> str:
    """An op's own name: its HLO instruction name, the text before ``=``
    (the rest names its operands, which may be other kernels' ops)."""
    return event.split("=", 1)[0]


def kernel_ops(ops: Iterable[Event], kernel: str, t0: float,
               t1: float) -> List[Event]:
    """The events in [t0, t1] of the ops named after ``kernel``."""
    return [e for e in clip(ops, t0, t1) if kernel in op_name(e[0])]


def kernel_time(ops: Iterable[Event], kernel: str, t0: float,
                t1: float) -> Tuple[float, int]:
    """(seconds, count) of ``kernel``'s events in [t0, t1]."""
    hits = kernel_ops(ops, kernel, t0, t1)
    return sum(b - a for _, a, b in hits) * 1e-9, len(hits)


def top_ops(ops: Iterable[Event], t0: float, t1: float, n: int = 10):
    """The ``n`` operation names with the most device time, in seconds.
    A ``while`` loop holds the ops of its body, which count on their own."""
    tot = defaultdict(float)
    for name, a, b in clip(ops, t0, t1):
        if not name.startswith("%while"):
            tot[name] += (b - a) * 1e-9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def idle_gaps(ops: Iterable[Event], spans: Iterable[Event], t0: float,
              t1: float, n: int = 10):
    """The ``n`` longest intervals of [t0, t1] with no device operation,
    each named by the host span that covers its middle (``host`` when none
    does), in seconds."""
    merged = union((a, b) for _, a, b in clip(ops, t0, t1))
    gaps, at = [], t0
    for a, b in merged + [(t1, t1)]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    spans = sorted(spans, key=lambda s: s[1])
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        cover = [s for s in spans if s[1] <= mid <= s[2]]
        label = min(cover, key=lambda s: s[2] - s[1])[0] if cover else "host"
        named.append([label, (b - a) * 1e-9])
    return named
