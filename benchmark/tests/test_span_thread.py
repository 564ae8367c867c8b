"""`readers/span_thread.py` on hand-made span lists: self time and self CPU
on the span's own thread (a prefetch-thread child does not take it), the
CPU time of the outermost spans, the off-CPU share, and nothing from spans
without `cpu_us` (a program that does not record it)."""

import pytest

from readers import span_thread


def span(sid, psid, name, ts_us, dur_us, cpu_us, thread="main", tid="t1"):
    return {"tid": tid, "sid": sid, "psid": psid, "name": name,
            "proc": "cn", "thread": thread, "ts_us": ts_us,
            "dur_us": dur_us, "cpu_us": cpu_us}


def statement():
    """One statement of 1000 us, 600 of them on the CPU.  Under `run` an
    aggregate's step 100..900 (CPU 300) pulls a fused join's step
    200..500 (CPU 100) on its own thread; the prefetch thread decodes a
    chunk 150..850 (CPU 700) under the aggregate's step.  A nested
    `statement` (a re-entrant execute) runs inside `run`."""
    return [
        span("st", "", "statement", 0, 1000, 600),
        span("run", "st", "run", 10, 980, 560),
        span("agg", "run", "op.aggregate", 100, 800, 300),
        span("fj", "agg", "op.fused_join", 200, 300, 100),
        span("dec", "agg", "object.decode", 150, 700, 700, "prefetch"),
        span("in", "run", "statement", 920, 50, 40),
    ]


def ctx_of(spans):
    return {"spans": spans}


def test_self_time_takes_off_only_children_on_the_own_thread():
    ctx = ctx_of(statement())
    # the aggregate: 800 less the fused join's 300; the decode beside it
    # on the prefetch thread takes nothing
    assert span_thread.read(ctx, ["op.aggregate"], "self_ms") == 0.5
    assert span_thread.read(ctx, ["op.fused_join"], "self_ms") == 0.3
    # nested spans of the list each keep their own self time
    assert span_thread.read(ctx, ["op.aggregate", "op.fused_join"],
                            "self_ms") == 0.8


def test_self_cpu_takes_off_the_own_threads_children():
    ctx = ctx_of(statement())
    assert span_thread.read(ctx, ["op.aggregate"], "self_cpu_ms") == 0.2
    assert span_thread.read(ctx, ["op.fused_join"], "self_cpu_ms") == 0.1


def test_cpu_of_the_outermost_spans_per_statement():
    ctx = ctx_of(statement())
    # the nested statement is inside the root's CPU time: counted once
    assert span_thread.read(ctx, ["statement"], "cpu_ms") == 0.6
    two = statement() + [span("s2", "", "statement", 2000, 500, 200,
                              tid="t2")]
    assert span_thread.read(ctx_of(two), ["statement"], "cpu_ms") == 0.4


def test_off_cpu_share():
    spans = statement() + [
        span("d1", "run", "vector.search.dispatch", 20, 100, 25),
        span("d2", "run", "vector.search.dispatch", 200, 300, 75)]
    got = span_thread.read(ctx_of(spans), ["vector.search.dispatch"],
                           "offcpu_share")
    assert got == pytest.approx(1 - 100 / 400)


@pytest.mark.parametrize("measure", ["self_ms", "self_cpu_ms", "cpu_ms",
                                     "offcpu_share"])
def test_nothing_without_cpu_time_or_a_statement(measure):
    parent = [{k: v for k, v in s.items() if k != "cpu_us"}
              for s in statement()]
    names = ["statement", "op.aggregate"]
    assert span_thread.read(ctx_of(parent), names, measure) is None
    orphan = [s for s in statement() if s["name"] != "statement"]
    assert span_thread.read(ctx_of(orphan), names, measure) is None
    assert span_thread.read(ctx_of([]), names, measure) is None
    assert span_thread.read(ctx_of(statement()), ["op.window"],
                            measure) is None
