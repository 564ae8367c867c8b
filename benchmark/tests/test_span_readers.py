"""The three readers of the program's spans (`span_ms_per_stmt`,
`span_uncovered_share`, `latency_less_span_ms`) on hand-made span lists,
and a CPU rehearsal with the tracer armed whose spans and counters feed
every per-layer metric of the split of `run` with a number."""

import json
import os

import pytest

from conftest import ROOT

import run
from readers import latency_less_span_ms, span_ms_per_stmt, \
    span_uncovered_share


def span(tid, sid, psid, name, ts_us, dur_us, thread="main"):
    return {"tid": tid, "sid": sid, "psid": psid, "name": name,
            "thread": thread, "ts_us": ts_us, "dur_us": dur_us}


def two_threads():
    """One statement of 1000 us.  Its thread waits 100..500 and works
    600..900; the prefetch thread reads a chunk 50..700 with a load
    100..400 (a read and a decode below it) and a zonemap check 450..650.
    A second trace has no `statement`: spans of a recovery."""
    return [
        span("t1", "st", "", "statement", 0, 1000),
        span("t1", "run", "st", "run", 20, 960),
        span("t1", "w", "run", "scan.wait", 100, 400),
        span("t1", "b", "run", "scan.batch", 600, 300),
        span("t1", "c", "run", "scan.chunk", 50, 650, "prefetch"),
        span("t1", "l", "c", "blockcache.load", 100, 300, "prefetch"),
        span("t1", "r", "l", "object.read", 100, 100, "prefetch"),
        span("t1", "d", "l", "object.decode", 200, 150, "prefetch"),
        span("t1", "z", "c", "scan.zonemap", 450, 200, "prefetch"),
        span("t2", "rec", "", "engine.recover", 0, 5000),
        span("t2", "x", "rec", "object.read", 10, 4000),
    ]


def ctx_of(spans, latencies_ms=()):
    return {"spans": spans,
            "executed": [{"t_send_ns": 0, "t_done_ns": int(ms * 1e6),
                          "error": None} for ms in latencies_ms]}


def test_span_ms_per_stmt_counts_only_traced_statements():
    ctx = ctx_of(two_threads())
    # the recovery's 4000 us of object.read belong to no statement
    assert span_ms_per_stmt.read(ctx, ["object.read"], False) == 0.1
    assert span_ms_per_stmt.read(ctx, ["object.read", "object.decode"],
                                 False) == 0.25
    # two statements share the spans of one: per statement, half
    more = two_threads() + [span("t3", "st3", "", "statement", 0, 10)]
    assert span_ms_per_stmt.read(ctx_of(more), ["object.read"],
                                 False) == 0.05


def test_span_ms_per_stmt_self_time_across_threads():
    ctx = ctx_of(two_threads())
    # chunk 650 less load 300 and zonemap 200; zonemap and batch are bare
    assert span_ms_per_stmt.read(
        ctx, ["scan.chunk", "scan.zonemap", "scan.batch"], True) \
        == (150 + 200 + 300) / 1e3
    # a parent with no children keeps all of its time
    assert span_ms_per_stmt.read(ctx, ["scan.wait"], True) == 0.4
    # busy times of two threads may sum past the statement
    assert span_ms_per_stmt.read(
        ctx, ["scan.wait", "scan.batch", "scan.chunk"], False) == 1.35
    # children that overlap on two threads are not taken off twice
    both = ctx_of([span("t", "st", "", "statement", 0, 100),
                   span("t", "p", "st", "run", 0, 100),
                   span("t", "a", "p", "x", 10, 50),
                   span("t", "b", "p", "y", 40, 40, "other")])
    assert span_ms_per_stmt.read(both, ["run"], True) == (100 - 70) / 1e3


def test_span_uncovered_share():
    ctx = ctx_of(two_threads())
    # run 20..980: children cover 50..900 (chunk 50..700, wait, batch)
    assert span_uncovered_share.read(ctx, "run") \
        == pytest.approx((960 - 850) / 960)
    # descendants count at any depth: the load covers 100..400 of the
    # chunk through its read and decode too, the zonemap 450..650
    assert span_uncovered_share.read(ctx, "scan.chunk") \
        == pytest.approx((650 - 500) / 650)
    assert span_uncovered_share.read(ctx, "scan.wait") == 1.0
    # a descendant that outlasts the span is clipped to it
    late = ctx_of([span("t", "p", "", "run", 0, 100),
                   span("t", "k", "p", "x", 80, 500, "other")])
    assert span_uncovered_share.read(late, "run") == 0.8


def test_latency_less_span_ms():
    ctx = ctx_of(two_threads(), latencies_ms=(1.5, 2.5))
    assert latency_less_span_ms.read(ctx, "statement") \
        == pytest.approx(2.0 - 1.0)
    failed = ctx_of(two_threads())
    failed["executed"] = [{"t_send_ns": 0, "t_done_ns": 9, "error": "x"}]
    assert latency_less_span_ms.read(failed, "statement") is None


@pytest.mark.parametrize("spans", [
    [],                                                  # tracer off
    [s for s in two_threads() if s["tid"] == "t2"],      # no statement
    [s for s in two_threads()                            # the parent: a
     if s["name"] in ("statement", "run")],              # program without
])                                                       # the new spans
def test_nothing_to_read_gives_nothing(spans):
    ctx = ctx_of(spans, latencies_ms=(1.0,))
    assert span_ms_per_stmt.read(ctx, ["scan.wait"], False) is None
    assert span_ms_per_stmt.read(ctx, ["scan.chunk"], True) is None
    assert span_uncovered_share.read(ctx, "scan.chunk") is None
    assert latency_less_span_ms.read(ctx, "parse") is None


NEW_METRICS = {
    "scan_read_ms.sql", "scan_decode_ms.sql", "upload_ms.sql",
    "scan_host_ms.sql", "scan_wait_ms.sql", "fused_host_ms.sql",
    "run_unattributed_share.sql", "outside_statement_ms.sql",
    "read_bytes_per_row.sql", "chunks_pruned_share.sql",
    "device_waits_per_stmt.sql", "spans_dropped_per_stmt.sql"}


def test_a_traced_rehearsal_feeds_every_new_metric(monkeypatch):
    """`--rehearse --trace 1` on the CPU.  The toy table (6.7 MB) would
    stay in the block cache and its batches under the fused path's row
    floor, so the test holds the cache to 1 MB and takes the floor away:
    every scan is cold and fused, as in the cell."""
    from matrixone_tpu.utils import motrace
    monkeypatch.setenv("MO_BLOCK_CACHE_MB", "1")
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    was = (motrace.TRACER.armed, motrace.TRACER.sample)
    try:
        result = run.run_cell("tpch-sf1.scan-agg", seed=2**31 + 27,
                              seconds=3.0, trace=True, rehearse=True)
    finally:
        motrace.TRACER.armed, motrace.TRACER.sample = was
        motrace.TRACER.clear()
    assert result["correct"] and result["failed"] == 0
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert NEW_METRICS <= listed
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert NEW_METRICS <= set(got), NEW_METRICS - set(got)
    assert got["spans_dropped_per_stmt.sql"] == 0
    for name in NEW_METRICS - {"spans_dropped_per_stmt.sql",
                               "chunks_pruned_share.sql"}:
        assert got[name] > 0, (name, got[name])
    assert 0 <= got["chunks_pruned_share.sql"] <= 1
    assert 0 <= got["run_unattributed_share.sql"] < 1
    # the stored bytes read are under the decoded bytes uploaded
    assert got["read_bytes_per_row.sql"] < got["upload_bytes_per_row.sql"]
