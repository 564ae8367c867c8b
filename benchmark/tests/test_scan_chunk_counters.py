"""`scan_zonemap_memo_share.sql` and `scan_slice_dispatches_per_stmt.sql`
(PR 32): two data files over readers the benchmark had.  They resolve by
the names the manifest gives and read, out of a recorded counter dump, how
the scan's zonemap checks were answered and how many programs its chunks
cost a statement.  From a program without the counters (the parent) the
share reads nothing; the count reads 0.0, which there says that nothing
was counted, not that nothing was dispatched."""

import json
import os

from conftest import BENCH, ROOT

from readers import counter_per, counter_share

SHARE = "scan_zonemap_memo_share.sql"
PER_STMT = "scan_slice_dispatches_per_stmt.sql"
CHECKS = 'mo_scan_zonemap_checks_total{source="%s"}'
SLICES = 'mo_scan_slice_dispatch_total{how="%s"}'


def _spec(name):
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        return json.load(f)


def _ctx():
    """Counters before and after a traced window of `tpch-sf1.scan-agg` on
    the chip (PR 32), as `run.counters()` flattens them, and the window's
    answered statements (all that `completed` reads of them)."""
    with open(os.path.join(BENCH, "tests", "data",
                           "scan_chunk_counters.json")) as f:
        dump = json.load(f)
    return {"before": dump["before"], "after": dump["after"],
            "executed": [{"error": None}] * dump["statements"]}


def _moved(ctx, key):
    return ctx["after"].get(key, 0) - ctx["before"].get(key, 0)


def test_the_manifest_lists_both_for_the_scan_cell_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    names = [m["name"] for m in per_layer]
    assert names.count(SHARE) == names.count(PER_STMT) == 1
    # appended: after what PR 30 appended, the share before the count
    assert names.index("scan_batch_device_pad_share.sql") \
        < names.index(SHARE) < names.index(PER_STMT)
    common = {"source": "program_counter",
              "layer": "scan: slicing, pruning (storage/engine.py)",
              "moves": "sql_rows_per_s", "workloads": ["tpch-sf1.scan-agg"]}
    assert per_layer[names.index(SHARE)] == dict(
        common, name=SHARE, unit="ratio", better="higher")
    assert per_layer[names.index(PER_STMT)] == dict(
        common, name=PER_STMT, unit="count", better="lower")


def test_the_memo_share_of_the_recorded_window():
    spec, ctx = _spec(SHARE), _ctx()
    assert spec["reader"] == "counter_share"
    moved = {s: _moved(ctx, CHECKS % s) for s in ("memo", "device", "host")}
    # the warm-up filled every summary: the window computes none
    assert moved["memo"] > 0 and moved["device"] == moved["host"] == 0
    assert counter_share.read(ctx, **spec["args"]) == 1.0
    # Q1 checks one predicate a chunk, Q6 five, over eight chunks each
    assert moved["memo"] % 8 == 0
    assert _moved(ctx, 'mo_device_wait_total{site="zonemap"}') == 0


def test_the_dispatches_a_statement_of_the_recorded_window():
    spec, ctx = _spec(PER_STMT), _ctx()
    assert spec["reader"] == "counter_per"
    assert _moved(ctx, SLICES % "column") == 0
    # four segments of lineitem, each two chunks, one program a chunk
    assert _moved(ctx, SLICES % "chunk") == 8 * len(ctx["executed"])
    assert counter_per.read(ctx, **spec["args"]) == 8.0


def test_a_program_without_the_counters():
    ctx = _ctx()
    gone = ("mo_scan_zonemap_checks_total", "mo_scan_slice_dispatch_total")
    parent = dict(ctx, **{side: {k: v for k, v in ctx[side].items()
                                 if not k.startswith(gone)}
                          for side in ("before", "after")})
    assert counter_share.read(parent, **_spec(SHARE)["args"]) is None
    assert counter_per.read(parent, **_spec(PER_STMT)["args"]) == 0.0
