"""Read the program's own spans, compiled steps and named scopes from a
profiler trace.

``load`` reads the newest ``.xplane.pb`` under a directory once per file
(memoised by path and modification time), while the trace is still on
disk, into plain tuples; every other function works on those tuples, so
the readers are tested on small synthetic traces.

  * span: ``(name, start_ns, end_ns, args)`` of one host annotation the
    program wrote (names starting with ``engine.``); ``args`` holds the
    counts it carries (``rows_useful``, ``queue_wait_ms``, ...)
  * step: ``(module, start_ns, end_ns)`` of one execution of a compiled
    module on the device, ``module`` as XLA names it
    (``jit_serve_decode_step``)
  * op: ``(name, start_ns, end_ns, module, scope)`` of one operation on
    the device; ``name`` is its HLO instruction's (``fusion.443``), and
    ``scope`` that instruction's ``op_name`` metadata, the
    ``jax.named_scope`` path
    (``jit(serve_decode_step)/.../attention/dot_general``)

Where each lives in the xplane (jax 0.9):

  * TPU: ops are the events of the first TPU plane's ``XLA Ops`` line,
    named by their HLO text (``%fusion.443 = bf16[...] fusion(...)``);
    steps are the events of its ``XLA Modules`` line, named
    ``<module>(<program id>)``, and an op belongs to the step whose
    execution holds it.
  * CPU: ops are host events with an ``hlo_op`` stat, on the XLA client's
    threads, with ``hlo_module``, ``program_id`` and ``run_id`` stats; a
    step is the span of one ``run_id``'s ops.
  * Both: the scope is not on the op's event.  The ``/host:metadata``
    plane holds each loaded program's optimised HLO (event metadata named
    ``<module>(<program id>)`` with a ``Hlo Proto`` stat), and each
    instruction's metadata there names its scope; a fusion carries its
    root's.  ``_hlo_scopes`` reads it from the file's bytes, since
    ``jax.profiler.ProfileData`` does not expose event metadata.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict, namedtuple
from typing import Dict, Iterable, List, Optional, Tuple

import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(HERE, ".trace")

Span = Tuple[str, float, float, dict]
Op = Tuple[str, float, float, str, str]
ProgramTrace = namedtuple("ProgramTrace", "spans steps ops")

_LOADED: Dict[tuple, ProgramTrace] = {}
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


def load(trace_dir: str = TRACE_DIR) -> Optional[ProgramTrace]:
    """The newest trace under ``trace_dir`` as tuples, or None where there
    is none.  Loaded once per file."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _LOADED:
        _LOADED.clear()
        _LOADED[key] = _read(path)
    return _LOADED[key]


def _read(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    scopes = _hlo_scopes(raw)
    spans: List[Span] = []
    tpus = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            tpus.append(plane)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("engine."):
                        spans.append((e.name, e.start_ns, e.end_ns,
                                      dict(e.stats)))
    if tpus:
        steps, ops = _tpu_ops(min(tpus, key=lambda p: p.name), scopes)
    else:
        steps, ops = _cpu_ops(pd, scopes)
    return ProgramTrace(sorted(spans, key=lambda s: s[1]), steps, ops)


def _tpu_ops(plane, scopes):
    lines = {ln.name: ln for ln in plane.lines}
    runs = []
    if "XLA Modules" in lines:
        for e in lines["XLA Modules"].events:
            hit = _MODULE.match(e.name)
            name, pid = (hit.group(1), int(hit.group(2))) if hit else (
                e.name, None)
            runs.append((e.start_ns, e.end_ns, name, pid))
    runs.sort()
    ops: List[Op] = []
    i = 0
    events = (sorted(((e.name, e.start_ns, e.end_ns)
                      for e in lines["XLA Ops"].events),
                     key=lambda e: e[1]) if "XLA Ops" in lines else [])
    for name, a, b in events:
        while i < len(runs) and runs[i][1] < a:
            i += 1
        module, pid = ("", None)
        if i < len(runs) and runs[i][0] <= a:
            module, pid = runs[i][2], runs[i][3]
        instr = tr.op_name(name).strip().lstrip("%")
        ops.append((instr, a, b, module, scopes.get(pid, {}).get(instr, "")))
    return [(m, a, b) for a, b, m, _ in runs], ops


def _cpu_ops(pd, scopes):
    ops: List[Op] = []
    runs: Dict[tuple, list] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                st = dict(e.stats)
                if "hlo_op" not in st:
                    continue
                module, pid = st.get("hlo_module", ""), st.get("program_id")
                ops.append((st["hlo_op"], e.start_ns, e.end_ns, module,
                            scopes.get(pid, {}).get(st["hlo_op"], "")))
                run = runs.setdefault((module, pid, st.get("run_id")),
                                      [e.start_ns, e.end_ns])
                run[0], run[1] = min(run[0], e.start_ns), max(run[1],
                                                               e.end_ns)
    steps = sorted(((m, a, b) for (m, _, _), (a, b) in runs.items()),
                   key=lambda s: s[1])
    return steps, sorted(ops, key=lambda o: o[1])


# ---------------------------------------------------------------------------
# the HLO each program ran, from the xplane's bytes
# ---------------------------------------------------------------------------

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b: bytes, lo: int = 0, hi: Optional[int] = None):
    """(field number, value) of one protobuf message in ``b[lo:hi]``; a
    length-delimited value is its ``(start, end)`` in ``b``."""
    i, hi = lo, len(b) if hi is None else hi
    while i < hi:
        tag, i = _varint(b, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield tag >> 3, v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _hlo_scopes(raw: bytes) -> Dict[int, Dict[str, str]]:
    """program id -> {HLO instruction name: op_name metadata} for every
    program whose HLO the trace holds (XSpace.planes = 1; XPlane.name = 2,
    event_metadata = 4 (map entry: key 1, value 2), stat_metadata = 5;
    XEventMetadata.id = 1, stats = 5; XStat.metadata_id = 1, bytes = 6;
    HloProto.hlo_module = 1; HloModuleProto.computations = 3;
    HloComputationProto.instructions = 2; HloInstructionProto.name = 1,
    metadata = 7; OpMetadata.op_name = 2)."""
    out: Dict[int, Dict[str, str]] = {}
    for f, plane in _fields(raw):
        if f != 1:
            continue
        parts = defaultdict(list)
        for g, v in _fields(raw, *plane):
            parts[g].append(v)
        if not parts[2] or _text(raw, parts[2][0]) != "/host:metadata":
            continue
        stat_names = {}
        for entry in parts[5]:
            for g, v in _fields(raw, *entry):
                if g == 2:
                    md = dict(_fields(raw, *v))
                    stat_names[md.get(1, 0)] = _text(raw, md[2]) \
                        if 2 in md else ""
        for entry in parts[4]:
            for g, v in _fields(raw, *entry):
                if g != 2:
                    continue
                pid, protos = 0, []
                for h, w in _fields(raw, *v):
                    if h == 1:
                        pid = w
                    elif h == 5:
                        st = dict(_fields(raw, *w))
                        if stat_names.get(st.get(1)) == "Hlo Proto" \
                                and 6 in st:
                            protos.append(st[6])
                for proto in protos:
                    out.setdefault(pid, {}).update(_instr_scopes(raw, proto))
    return out


def _instr_scopes(raw: bytes, proto) -> Dict[str, str]:
    out = {}
    for f, module in _fields(raw, *proto):
        if f != 1:
            continue
        for g, comp in _fields(raw, *module):
            if g != 3:
                continue
            for h, instr in _fields(raw, *comp):
                if h != 2:
                    continue
                name = scope = ""
                for k, v in _fields(raw, *instr):
                    if k == 1:
                        name = _text(raw, v)
                    elif k == 7:
                        for m, w in _fields(raw, *v):
                            if m == 2:
                                scope = _text(raw, w)
                out[name] = scope
    return out


# ---------------------------------------------------------------------------
# reductions on the tuples
# ---------------------------------------------------------------------------

def inside(events: Iterable[tuple], t0: float, t1: float) -> list:
    """The events (name, start, end, ...) that lie wholly in [t0, t1]."""
    return [e for e in events if e[1] >= t0 and e[2] <= t1]


def in_scope(scope: str, name: str) -> bool:
    """Whether an op's scope path runs through ``jax.named_scope(name)``."""
    return name in scope.split("/")


def step_ms(steps: Iterable[tuple], module: str, t0: float,
            t1: float) -> List[float]:
    """Device durations in ms of the executions of ``module`` in [t0, t1]."""
    return [(b - a) * 1e-6 for m, a, b in inside(steps, t0, t1)
            if m == module]


def scope_time(ops: Iterable[Op], name: str, t0: float, t1: float) -> float:
    """Seconds of [t0, t1] in which an op under scope ``name`` ran (the
    union, so an op that holds others counts once)."""
    scoped = [(n, a, b) for n, a, b, _, s in ops if in_scope(s, name)]
    return tr.busy(scoped, t0, t1)


def idle_by_span(ops: Iterable[tuple], spans: Iterable[tuple],
                 ticks: Iterable[tuple], t0: float,
                 t1: float) -> Dict[str, float]:
    """Seconds of [t0, t1] with no device operation, split by what the
    host was doing: the innermost ``engine.*`` span at each instant, else
    ``harness`` inside one of ``ticks`` (the harness's own tick spans),
    else ``between ticks``."""
    busy = tr.union((a, b) for _, a, b in tr.clip((o[:3] for o in ops),
                                                  t0, t1))
    gaps, at = [], t0
    for a, b in busy + [(t1, t1)]:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    # the innermost label of each piece between consecutive span edges
    labelled = sorted([(a, b, n) for n, a, b, *_ in spans]
                      + [(a, b, "harness") for _, a, b, *_ in ticks])
    edges = sorted({t for a, b, _ in labelled for t in (a, b)} | {t0, t1})
    pieces, active, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(labelled) and labelled[k][0] <= a:
            active.append(labelled[k])
            k += 1
        active = [s for s in active if s[1] >= b]
        label = (min(active, key=lambda s: s[1] - s[0])[2] if active
                 else "between ticks")
        pieces.append((a, b, label))
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for g0, g1 in gaps:
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        m = j
        while m < len(pieces) and pieces[m][0] < g1:
            a, b, label = pieces[m]
            out[label] += (min(b, g1) - max(a, g0)) * 1e-9
            m += 1
    return dict(out)
