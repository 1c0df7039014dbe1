"""Serving benchmark: one cell, one seed, one measured window.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in BENCHMARK.json; its configuration,
traffic mix, parameters and per-layer metric readers are files under
``bench/`` found by name (``spec.py``).  A run:

1. places JAX's compile cache where ``repro.runtime.compile_cache`` says,
   and fails, printing no result, unless JAX finds a TPU with as many chips
   as the cell asks for;
2. makes the weights on the device from ``--seed`` and packs them into the
   program's MXSF store, in one jitted call (the weights, the program
   config, the plain reference and the work counts come from the
   configuration's architecture module, ``spec.arch``);
3. builds ``ServeEngine`` with the mix's engine shape and warms its two
   step shapes with one prefill and one decode dispatch (set-up ends here);
4. drives the window through ``ServeEngine.submit`` and one-tick
   ``ServeEngine.run`` calls only, submitting arrivals between ticks; a
   token's time is the end of the tick whose return shows it;
5. with ``--trace 1``, traces the first ``TRACE_SECONDS`` of the window
   with the profiler and reads the per-layer metrics from it; otherwise
   reports the end-to-end metrics;
6. frees the program's state and checks the served tokens against the
   architecture module's plain float32 reference on a sample of requests
   drawn from the seed.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
(``calibrate.py`` also asks for the int4 control, judged by the same
``passed``.)
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from typing import List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import spec  # noqa: E402
import traffic  # noqa: E402

TRACE_DIR = os.path.join(HERE, ".trace")
# traced span from the window's start: long enough to hold prefill and
# decode ticks in their share of the window (the batch cell's first refill
# prefills at ~5 s), short enough that writing and reading the trace keeps
# a run well inside its time limit
TRACE_SECONDS = 20.0


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Rec:
    """One request as the client sees it (times in seconds from the
    window's start)."""
    due: float
    req: object
    times: List[float] = dataclasses.field(default_factory=list)
    started: bool = False       # under way before the window (stagger)


@dataclasses.dataclass
class Tick:
    """One engine tick: host span, and per slot the first position it
    computed and how many rows (0 for a slot with no work)."""
    t0: float
    t1: float
    starts: np.ndarray
    rows: np.ndarray
    prefill: np.ndarray        # bool per slot: a prompt row, not a decode row
    decode_dispatches: int
    prefill_dispatches: int
    emitted: int
    traced: bool = False


@dataclasses.dataclass
class Window:
    """A measured window: it closes at the end of the first tick that ends
    at or after ``seconds``, which is ``elapsed``."""
    elapsed: float
    recs: List[Rec]
    ticks: List[Tick]
    tokens: int = 0
    compiles: int = 0
    gc_pauses: List[tuple] = dataclasses.field(default_factory=list)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def build(cell: dict, seed: int):
    """(program config, engine) with weights made and packed from ``seed``
    in one jitted call."""
    import jax

    from repro.models import model as M
    from repro.serve.engine import ServeEngine
    from weights import root_key

    A = spec.arch(cell["config"])
    cfg = A.program_config(cell["config"])
    s = A.sizes(cell["config"])
    policy = spec.policy()
    make = lambda key: A.program_params(key, s, cfg.padded_vocab)
    ours = jax.eval_shape(make, root_key(0))
    theirs = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)
    if shape(ours) != shape(theirs):
        raise ValueError(f"{A.__file__} no longer makes the program's "
                         "parameter layout")
    params = jax.jit(lambda key: M.pack_model_params(cfg, make(key), policy))(
        root_key(seed))
    jax.block_until_ready(params)
    e = cell["engine"]
    eng = ServeEngine(cfg, params, policy, backend="pallas",
                      slots=e["slots"], max_len=e["max_len"],
                      prefill_chunk=e["prefill_chunk"])
    return cfg, eng


def warm(eng, seed: int, vocab: int):
    """One prefill and one decode dispatch: the cell's two step shapes."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    eng.submit(rng.integers(0, vocab, 2).tolist(), 2)
    eng.run()
    import jax
    jax.block_until_ready(eng.cache)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def _compile_counter():
    """A list whose length counts backend compiles from now on."""
    import jax
    seen: list = []

    def listen(event, *args, **kw):
        if "backend_compile" in event:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    return seen


def _gc_watch(pauses: list):
    """A ``gc.callbacks`` entry that appends (generation, seconds) to
    ``pauses`` for each collection of the interpreter's cyclic collector."""
    began = [0.0]

    def watch(phase, info):
        if phase == "start":
            began[0] = time.perf_counter()
        else:
            pauses.append((info["generation"],
                           time.perf_counter() - began[0]))
    return watch


def start(eng, reqs: List[traffic.Req]) -> List[Rec]:
    """Set-up of a staggered backlog: submit the requests already under way
    when the window opens and tick until every one is past its prompt."""
    recs = [Rec(r.due, eng.submit(r.prompt, r.max_new), started=True)
            for r in reqs if r.started]
    while any(eng.pending_prompt) or eng.queue:
        eng.run(max_ticks=eng.ticks + 1)
    import jax
    jax.block_until_ready(eng.cache)
    return recs


def drive(eng, mix: dict, reqs: List[traffic.Req], seconds: float,
          trace_seconds: float = 0.0, started: List[Rec] = ()) -> Window:
    """Run the window.  Open loop: submit each request when due.  Closed
    loop: keep ``outstanding_per_slot * slots`` submitted, one more at each
    completion; ``started`` are those already under way (``start``).
    With ``trace_seconds``, the profiler runs over the window's first
    ``trace_seconds``."""
    import jax
    open_loop = mix["loop"] == "open"
    outstanding = (0 if open_loop
                   else mix["outstanding_per_slot"] * eng.slots)
    compiles = _compile_counter()
    n0 = len(compiles)
    pauses: list = []
    watch = _gc_watch(pauses)
    gc.callbacks.append(watch)
    recs: List[Rec] = list(started)
    ticks: List[Tick] = []
    inflight: List[Rec] = [r for r in started if not r.req.done]
    nxt = len(started)
    tok0 = eng.tokens_generated
    tracing = trace_seconds > 0
    if tracing:
        jax.profiler.start_trace(TRACE_DIR)
    t_start = time.perf_counter()
    now = 0.0

    def submit(r: traffic.Req):
        rec = Rec(r.due, eng.submit(r.prompt, r.max_new))
        recs.append(rec)
        inflight.append(rec)

    if not open_loop:
        while nxt < len(reqs) and len(inflight) < outstanding:
            submit(reqs[nxt])
            nxt += 1
    while now < seconds:
        if tracing and now >= trace_seconds:
            jax.profiler.stop_trace()
            tracing = False
        if open_loop:
            while nxt < len(reqs) and reqs[nxt].due <= now:
                submit(reqs[nxt])
                nxt += 1
        if not (eng.queue or any(r is not None for r in eng.live)):
            wait = (reqs[nxt].due if open_loop and nxt < len(reqs)
                    else seconds) - now
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(wait, seconds - now)))
            now = time.perf_counter() - t_start
            continue
        ticks.append(_tick(eng, t_start, tracing))
        now = ticks[-1].t1
        done = 0
        for rec in list(inflight):
            n = len(rec.req.out)
            if n > len(rec.times):
                rec.times.extend([now] * (n - len(rec.times)))
            if rec.req.done:
                inflight.remove(rec)
                done += 1
        if not open_loop:
            for _ in range(done):
                if nxt < len(reqs):
                    submit(reqs[nxt])
                    nxt += 1
    if tracing:
        jax.profiler.stop_trace()
    gc.callbacks.remove(watch)
    return Window(max(now, seconds), recs, ticks,
                  eng.tokens_generated - tok0, len(compiles) - n0, pauses)


def _tick(eng, t_start: float, traced: bool) -> Tick:
    """One ``ServeEngine.run`` tick, with what each slot computed in it:
    the engine admits queued requests into free slots in slot order, then
    advances each busy slot's position by the rows it computed."""
    import jax
    free = [s for s, r in enumerate(eng.live) if r is None]
    admitted = free[:len(eng.queue)]
    starts = np.array(eng.pos, np.int64)
    starts[admitted] = 0
    prefill = np.array([len(q) > 0 for q in eng.pending_prompt])
    prefill[admitted] = True
    busy = np.array([r is not None for r in eng.live])
    busy[admitted] = True
    d0, p0, e0 = (eng.decode_dispatches, eng.prefill_dispatches,
                  eng.tokens_generated)
    t0 = time.perf_counter() - t_start
    with jax.profiler.TraceAnnotation("bench.tick"):
        eng.run(max_ticks=eng.ticks + 1)
    t1 = time.perf_counter() - t_start
    rows = np.where(busy, np.array(eng.pos, np.int64) - starts, 0)
    return Tick(t0, t1, starts, rows, prefill & busy,
                eng.decode_dispatches - d0, eng.prefill_dispatches - p0,
                eng.tokens_generated - e0, traced)


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def latencies(win: Window):
    """(TTFTs, inter-token gaps) in seconds over every request due in the
    window.  A request with no token yet counts at its elapsed time, and
    one still decoding adds its open gap up to the window's end.  Requests
    under way before the window opened are not of it."""
    ttft, itl = [], []
    for r in win.recs:
        if r.started:
            continue
        t = r.times
        ttft.append((t[0] if t else win.elapsed) - r.due)
        itl.extend(np.diff(t).tolist())
        if t and not r.req.done:
            itl.append(win.elapsed - t[-1])
    return ttft, itl


def end_to_end(win: Window, setup_s: float) -> dict:
    ttft, itl = latencies(win)
    out = {"setup_s": (setup_s, "s"),
           "tokens_per_s": (win.tokens / win.elapsed, "tokens/s")}
    if ttft:
        out["ttft_p90_ms"] = (pct(ttft, 90) * 1e3, "ms")
    if itl:
        out["itl_p50_ms"] = (pct(itl, 50) * 1e3, "ms")
    return {k: {"value": v, "unit": u} for k, (v, u) in out.items()}


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Traced:
    """What the metric readers get: the traced ticks, the device ops and
    host spans of the trace, its window, and the cell's sizes, architecture
    module (whose work counts the readers take) and peaks."""
    cell: dict
    sizes: dict
    arch: object
    peaks: dict
    slots: int
    chunk: int
    ticks: List[Tick]
    ops: list
    spans: list
    t0_ns: float
    t1_ns: float

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9


def read_trace(win: Window, cell: dict, peaks: dict, eng) -> Traced:
    import trace_reduce as tr
    devs, spans = tr.load(TRACE_DIR)
    tick_spans = sorted(s for s in spans if s[0] == "bench.tick")
    if not devs or not tick_spans:
        raise RuntimeError("the trace holds no device operations or no "
                           "ticks")
    ops = devs[sorted(devs)[0]]
    A = spec.arch(cell["config"])
    return Traced(cell, A.sizes(cell["config"]), A, peaks, eng.slots,
                  eng.prefill_chunk, [t for t in win.ticks if t.traced],
                  ops, spans,
                  tick_spans[0][1], tick_spans[-1][2])


def per_layer(tr_run: Traced) -> dict:
    out = {}
    for m in tr_run.cell["per_layer"]:
        got = spec.reader(m["name"])(tr_run)
        if got is not None:
            out[m["name"]] = dict(got, unit=m["unit"])
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample(win: Window, seed: int, ref_tokens: int):
    """Requests to compare, drawn from the seed: the longest that finished
    (or, where none did, the longest served), then the others in a seeded
    order, finished ones first, while the stream fits ``ref_tokens``."""
    served = [r for r in win.recs if r.req.out]
    if not served:
        return []
    size = lambda r: len(r.req.prompt) + len(r.req.out) - 1
    done = [r for r in served if r.req.done]
    first = max(done or served, key=size)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    rest = [served[i] for i in rng.permutation(len(served))
            if served[i] is not first]
    rest.sort(key=lambda r: not r.req.done)
    picked, used = [], 0
    for r in [first] + rest:
        if used + size(r) <= ref_tokens:
            picked.append(r)
            used += size(r)
    return [(list(r.req.prompt), list(r.req.out)) for r in picked]


def check(cell: dict, seed: int, samples, control: bool = False):
    """Each number compared with its limit; with ``control``, the same
    numbers for the int4 control, read on the same positions (else
    None)."""
    A = spec.arch(cell["config"])
    lim = cell["params"]["limits"]
    n = sum(len(o) for _, o in samples)
    gap = ctl = None
    if samples:
        gaps, cgaps = A.served_gaps(
            A.sizes(cell["config"]), seed, samples,
            cell["mix"]["check"]["ref_tokens"], control=control)
        gap = float(np.max(gaps))
        ctl = None if cgaps is None else float(np.max(cgaps))
    checks = lambda g: {
        "served_tokens_compared": {"value": n,
                                   "limit": lim["min_served_tokens"]},
        "max_logit_gap": {"value": g, "limit": lim["max_logit_gap"]}}
    return checks(gap), (checks(ctl) if control else None)


def passed(checks: dict) -> bool:
    n, g = checks["served_tokens_compared"], checks["max_logit_gap"]
    return (n["value"] >= n["limit"] and g["value"] is not None
            and g["value"] <= g["limit"])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(cell: dict, seed: int, seconds: float, traced: bool, *,
        require_tpu: bool = True, peaks: Optional[dict] = None,
        fault=None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict.

    ``fault`` (tests only): a function ``fault(engine)`` that breaks the
    timed path underneath after warm-up."""
    t0 = time.perf_counter()
    import jax
    devs = devices(cell["chips"], require_tpu)
    if require_tpu:
        from repro.runtime.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    kind = devs[0].device_kind
    peaks = peaks or spec.peaks(kind)
    cfg, eng = build(cell, seed)
    vocab = cell["config"]["vocab_size"]
    warm(eng, seed, vocab)
    reqs = traffic.generate(cell["mix"], cell["params"], seed, seconds,
                            vocab, eng.slots)
    if fault is not None:
        fault(eng)
    started = start(eng, reqs)
    # set-up's objects (compiled steps, traced functions, the backlog) are
    # set aside from the cyclic collector, as a server freezes its heap
    # once warm: a full collection in the window then scans only what the
    # window allocates
    gc.freeze()
    setup_s = time.perf_counter() - t0
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    win = drive(eng, cell["mix"], reqs, seconds,
                TRACE_SECONDS if traced else 0.0, started)
    stats = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    attempted = sum(1 for r in win.recs if r.due <= seconds)
    result = {"correct": False, "attempted": attempted, "failed": 0}
    if traced:
        import trace_reduce as tr
        t_run = read_trace(win, cell, peaks, eng)
        busy = tr.busy(t_run.ops, t_run.t0_ns, t_run.t1_ns)
        result["metrics"] = per_layer(t_run)
        device.update(busy_s=busy, window_s=t_run.window_s)
        result["breakdown"] = {
            "device_ops": tr.top_ops(t_run.ops, t_run.t0_ns, t_run.t1_ns),
            "idle_gaps": tr.idle_gaps(t_run.ops, t_run.spans, t_run.t0_ns,
                                      t_run.t1_ns)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        names = {m["name"] for m in cell["end_to_end"]}
        result["metrics"] = {k: v for k, v in
                             end_to_end(win, setup_s).items() if k in names}
    result["device"] = device
    samples = sample(win, seed, cell["mix"]["check"]["ref_tokens"])
    log(f"window: {len(win.ticks)} ticks, {win.tokens} tokens, "
        f"{attempted} requests due, {sum(r.req.done for r in win.recs)} "
        f"finished, compiles in window {win.compiles}, setup_s "
        f"{setup_s:.3f}")
    log(window_report(win))
    del eng, win
    gc.unfreeze()
    gc.collect()
    checks, ctl = check(cell, seed, samples, control)
    if control:
        result["control"] = {"correct": passed(ctl), "checks": ctl}
    result["correct"] = passed(checks)
    result["checks"] = checks
    return result


def window_report(win: Window) -> str:
    """Where host time went in the window: the garbage collector's pauses,
    and per kind of tick (decode only, or with a prefill dispatch) the
    median and the time the ticks spent over it, which is where a stalled
    host shows."""
    g = [s for _, s in win.gc_pauses]
    full = sum(1 for gen, _ in win.gc_pauses if gen == 2)
    parts = [f"gc in window: {len(g)} collections ({full} full), "
             f"{sum(g):.4f} s, longest {max(g, default=0.0):.4f} s"]
    for kind, pre in (("decode", False), ("prefill", True)):
        dur = np.array([t.t1 - t.t0 for t in win.ticks
                        if (t.prefill_dispatches > 0) == pre])
        if dur.size:
            med = float(np.median(dur))
            parts.append(f"{kind} ticks: {dur.size}, median "
                         f"{med * 1e3:.2f} ms, over it "
                         f"{np.sum(dur - med, where=dur > med):.4f} s, "
                         f"longest {dur.max() * 1e3:.1f} ms")
    return "; ".join(parts)


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    try:
        result = run(cell, a.seed, a.seconds, bool(a.trace))
    except NoChip as e:
        log(f"error: {e}")
        return 2
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
