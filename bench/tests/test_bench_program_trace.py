"""The program's own spans, steps and scopes: ``program_trace.py`` and the
four readers on them, on small synthetic traces."""
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.dirname(os.path.abspath(__file__)),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import pytest

import program_trace as pt
import spec
import work
from test_bench_trace import MS, _traced

DEC, PRE = "jit_serve_decode_step", "jit_serve_prefill_step"
ATTN = "jit(serve_decode_step)/while/body/closed_call/attention"
# the fixture's window is 0..100 ms: a prefill tick at 0..40 ms (slot 0
# prefills 3 rows of a 4-row chunk on 2 slots) and a decode tick at
# 60..100 ms (slot 0 decodes); what lies past 100 ms is outside it
SPANS = [("engine.tick", 1 * MS, 39 * MS, {"step_num": 7, "live": 1,
                                          "queued": 0}),
         ("engine.prefill", 2 * MS, 38 * MS, {"rows_computed": 8,
                                              "rows_useful": 3}),
         ("engine.prefill.sync", 31 * MS, 37 * MS, {}),
         ("engine.tick", 61 * MS, 99 * MS, {"step_num": 8, "live": 1,
                                           "queued": 0}),
         ("engine.decode", 62 * MS, 98 * MS, {"rows_computed": 2,
                                             "rows_useful": 1}),
         ("engine.decode", 101 * MS, 120 * MS, {"rows_computed": 2,
                                               "rows_useful": 2})]
STEPS = [(PRE, 5 * MS, 30 * MS), (DEC, 65 * MS, 80 * MS),
         (DEC, 85 * MS, 95 * MS), (DEC, 110 * MS, 140 * MS),
         ("jit__argmax", 96 * MS, 97 * MS)]
OPS = [("%_flash_attention_jit.3 = bf16[8,4,32]", 25 * MS, 30 * MS, PRE,
        "jit(serve_prefill_step)/attention/jit(_flash_attention_jit)/"
        "pallas_call"),
       ("%while.1 = (s32[])", 65 * MS, 81 * MS, DEC,
        "jit(serve_decode_step)/while"),
       ("%fusion.9 = bf16[2,4]", 66 * MS, 70 * MS, DEC, ATTN + "/mul"),
       # an op that holds another counts once
       ("%while.7 = (s32[])", 66 * MS, 69 * MS, DEC, ATTN + "/while"),
       # a name that only contains the word is not the scope
       ("%fusion.5 = f32[2]", 40 * MS, 45 * MS, DEC,
        "jit(f)/jit(_flash_attention_jit)/mul"),
       ("%fusion.2 = f32[2,4]", 71 * MS, 79 * MS, DEC,
        "jit(serve_decode_step)/while/body/closed_call/dot_general"),
       ("%fusion.8 = bf16[2,4]", 110 * MS, 120 * MS, DEC, ATTN + "/mul")]


@pytest.fixture
def trace(monkeypatch):
    got = pt.ProgramTrace(SPANS, STEPS, OPS)
    monkeypatch.setattr(pt, "load", lambda *a: got)
    return got


def test_engine_row_share_equals_useful_row_share_on_the_same_ticks(trace):
    run = _traced("open")
    got = spec.reader("engine_row_share.chat")(run)
    assert got["value"] == pytest.approx(100 * 4 / 10)
    assert got["value"] == pytest.approx(
        spec.reader("useful_row_share.chat")(run)["value"])


def test_step_times_are_medians_in_the_window(trace):
    run = _traced("closed")
    assert spec.reader("decode_step_ms.batch")(run)["value"] == \
        pytest.approx(12.5)
    assert spec.reader("prefill_step_ms.batch")(run)["value"] == \
        pytest.approx(25.0)


def test_attention_roofline_reads_the_scope(trace):
    run = _traced("closed")
    got = spec.reader("attention_roofline.batch")(run)
    s, pk = run.sizes, run.peaks
    floor = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                for f, b in (work.attn_rows(s, [0], [3]),
                             work.attn_rows(s, [3], [1])))
    # the kernel's 5 ms and the decode ops' union 66..70 ms
    assert pt.scope_time(OPS, "attention", 0, 100 * MS) == \
        pytest.approx(0.009)
    assert got["bound"] == "memory"
    assert got["value"] == pytest.approx(100 * floor / 0.009)


READERS = ["engine_row_share.chat", "decode_step_ms.chat",
           "prefill_step_ms.batch", "attention_roofline.batch"]


@pytest.mark.parametrize("metric", READERS)
def test_nothing_to_read_returns_nothing(monkeypatch, metric):
    run = _traced("closed")
    # the parent program: a trace with none of the spans, steps or scope
    bare = pt.ProgramTrace([], [("jit_step", 5 * MS, 30 * MS)],
                           [("%fusion.1 = f32[8]", 0, 10 * MS, "jit_step",
                             "jit(step)/mul")])
    monkeypatch.setattr(pt, "load", lambda *a: bare)
    assert spec.reader(metric)(run) is None
    monkeypatch.setattr(pt, "load", lambda *a: None)
    assert spec.reader(metric)(run) is None


def test_idle_is_split_by_the_innermost_span():
    ops = [("a", 0, 10, "", ""), ("b", 20, 30, "", "")]
    spans = [("engine.tick", 0, 40, {}), ("engine.decode", 2, 38, {}),
             ("engine.decode.sync", 12, 19, {}), ("engine.emit", 30, 35, {})]
    ticks = [("bench.tick", 0, 45)]
    got = pt.idle_by_span(ops, spans, ticks, 0, 50)
    assert got == pytest.approx({"engine.decode": 6e-9,
                                 "engine.decode.sync": 7e-9,
                                 "engine.emit": 5e-9, "engine.tick": 2e-9,
                                 "harness": 5e-9, "between ticks": 5e-9})


def _varint(v):
    out = b""
    while True:
        out += bytes([(v & 0x7F) | (0x80 if v > 0x7F else 0)])
        v >>= 7
        if not v:
            return out


def _msg(*fields):
    """A protobuf message of (field, value): ints as varints, the rest
    length-delimited."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_scopes_come_from_the_hlo_the_trace_holds():
    instr = _msg((1, "fusion.3"), (2, "fusion"),
                 (7, _msg((1, "mul"), (2, ATTN + "/mul"))))
    hlo = _msg((1, _msg((1, DEC), (3, _msg((1, "main"), (2, instr))))))
    meta = _msg(
        (2, "/host:metadata"),
        (5, _msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto"))))),
        (4, _msg((1, 5), (2, _msg((1, 5), (2, DEC + "(5)"),
                                  (5, _msg((1, 7), (6, hlo))))))))
    raw = _msg((1, _msg((2, "/host:CPU"), (3, b""))), (1, meta))
    assert pt._hlo_scopes(raw) == {5: {"fusion.3": ATTN + "/mul"}}


def test_load_reads_each_file_once(tmp_path):
    import jax
    import jax.numpy as jnp
    assert pt.load(str(tmp_path)) is None
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("engine.tick", live=2):
            f(jnp.ones(4)).block_until_ready()
    got = pt.load(str(tmp_path))
    assert got is pt.load(str(tmp_path))
    assert [(s[0], s[3]["live"]) for s in got.spans] == [("engine.tick", 2)]
    assert any(m == "jit__lambda" for m, _, _ in got.steps)


def test_idle_split_summary():
    import idle_split
    ticks = [("bench.tick", 0, 40 * MS), ("bench.tick", 60 * MS, 100 * MS)]
    got = idle_split.summary(pt.ProgramTrace(SPANS, STEPS, OPS), ticks)
    assert got["window_s"] == pytest.approx(0.1)
    assert got["spans"]["engine.decode"] == {"count": 1,
                                             "median_ms": 36.0}
    assert got["jit_serve_decode_step"] == {"count": 2, "median_ms": 12.5}
    assert sum(got["idle_s"].values()) == pytest.approx(0.1 - got["busy_s"])
    assert got["idle_s"]["between ticks"] == pytest.approx(0.015)
