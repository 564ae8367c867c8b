"""The roofline work functions and the table of peaks."""

import pytest

import peaks
import traffic
import work


def _columns(template):
    mix = traffic.load_mix("scan-agg")
    return next(t for t in mix["templates"]
                if t["name"] == template)["work"]["columns"]


def test_q1_and_q6_bytes_at_sf1():
    # Q1 names seven columns: two CHAR(1), four DECIMAL(15,2), one DATE
    rows = {"lineitem": 6_001_215}
    assert work.scan_bytes(_columns("q1"), rows) == 6_001_215 * 38
    assert work.scan_bytes(_columns("q6"), rows) == 6_001_215 * 28
    assert work.scan_bytes(_columns("q1"), rows) == pytest.approx(228e6,
                                                                  rel=1e-3)


def test_a_join_reads_each_tables_named_columns_once():
    rows = {"customer": 10, "orders": 100, "lineitem": 1000}
    cols = {"customer": {"c_custkey": "int64", "c_mktsegment": 10},
            "orders": {"o_orderkey": "int64", "o_orderdate": "date"},
            "lineitem": {"l_shipdate": "date"}}
    assert work.scan_bytes(cols, rows) == 10 * 18 + 100 * 12 + 1000 * 4


def test_ivf_query_work_and_what_binds():
    w = work.ivf_query_work(1_000_000, 768, 1024, 8)
    assert w["bytes"] == pytest.approx(27.1e6, rel=5e-3)
    assert w["flops"] == 2 * 768 * (1024 + 1_000_000 * 8 / 1024)
    seconds, binds = work.least_seconds(w, peaks.peaks("TPU v5 lite"))
    assert binds == "bytes"
    assert seconds == pytest.approx(33e-6, rel=0.02)


def test_flops_can_bind():
    seconds, binds = work.least_seconds({"bytes": 1, "flops": 197e12},
                                        peaks.peaks("TPU v5 lite"))
    assert (seconds, binds) == (1.0, "flops")


def test_unknown_device_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bytes_per_s"] == 819e9
    assert "source" in peaks.peaks("TPU v5 lite")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_statement_work_follows_the_statement():
    ctx = {"table_rows": {"lineitem": 1000},
           "config": {"vectors": 1024, "dim": 8, "lists": 16, "nprobe": 4}}
    st = {"template": "q6", "tables": ["lineitem"],
          "work": {"fn": "scan", "columns": _columns("q6")}}
    assert work.of(ctx, st) == {"bytes": 28000, "flops": 0}
    assert work.of(ctx, {"work": {"fn": "ivf_query"}})["bytes"] \
        == (16 + 1024 * 4 / 16) * 8 * 4
    assert work.of(ctx, {"work": None}) is None
