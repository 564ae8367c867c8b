"""The cell `tpch-sf1-refresh.rf-q1q6` at the rehearsal's size: the run
is correct, the reference's control is not, a commit that is acknowledged
and lost is caught, the harness's warm-up (the plan's last round, twice)
leaves every table as loaded, and a plan too short for its window is
caught by `rf_plan_wrapped`."""

import json
import os

import pytest

import loadgen
import run
import traffic
from loaders import tpch_refresh as loader
from references import tpch_refresh as reference

CELL = "tpch-sf1-refresh.rf-q1q6"
SEED = 2**31 + 91
ROUND = len(loader.ROUND)
WRITES = ("rf1_orders", "rf1_lineitem", "rf2_lineitem", "rf2_orders")


def _over(result):
    return {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}


def _config(**changed):
    with open(os.path.join(run.HERE, "configs", "tpch-sf1-refresh.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"], **changed)
    return cfg


def test_the_mix_is_rounds_of_fourteen_and_its_readers_are_scan_aggs():
    mix = traffic.load_mix("rf-q1q6")
    assert tuple(t["name"] for t in mix["templates"]) == loader.ROUND
    theirs = {t["name"]: t for t in traffic.load_mix("scan-agg")["templates"]}
    for t in mix["templates"]:
        assert t["reference"] == "tpch_refresh"
        if t["name"] in theirs:       # Q1 and Q6, letter for letter
            assert {k: t[k] for k in ("sql", "params", "tables", "work")} \
                == {k: theirs[t["name"]][k]
                    for k in ("sql", "params", "tables", "work")}
        elif t["name"] != "orders_check":
            assert t["tables"] == []  # a write answers no analytic row


def test_the_rehearsal_is_correct_and_its_control_is_not():
    from matrixone_tpu.utils import motrace
    try:
        result = run.run_cell(CELL, seed=SEED, seconds=3.0, trace=True,
                              rehearse=True, control=True)
    finally:
        # run.py arms the tracer and a process ends with it armed: a
        # later test's set-up would be traced into its span metrics
        motrace.TRACER.disarm()
        motrace.TRACER.clear()
    assert result["correct"] and not _over(result), result["compared"]
    assert result["attempted"] >= ROUND and result["failed"] == 0
    control = result["control"]
    assert control["correct"] is False
    assert control["compared"]["rf_avg_rel_err"][0] > \
        reference.LIMITS["rf_avg_rel_err"]
    for name in ("refresh_stmt_ms.sql", "commit_ms.sql", "wal_sync_ms.sql",
                 "dml_find_ms.sql", "scan_dead_rows_share.sql",
                 "scan_mem_chunks_per_stmt.sql", "spans_dropped_per_stmt.sql"):
        assert name in result["metrics"], sorted(result["metrics"])
    assert result["metrics"]["scan_dead_rows_share.sql"]["value"] > 0
    assert result["metrics"]["spans_dropped_per_stmt.sql"]["value"] == 0


def lose_the_second_commit(srv, eng):
    """The window's second commit (round 0's RF2) is acknowledged and
    never applied."""
    real, calls = eng.commit_txn, []

    def commit_txn(snapshot_ts, inserts, deletes):
        calls.append(1)
        if len(calls) == 2:
            return 0
        return real(snapshot_ts, inserts, deletes)

    eng.commit_txn = commit_txn


def test_an_acknowledged_commit_that_is_lost_is_not_correct():
    result = run.run_cell(CELL, seed=SEED, seconds=3.0, trace=False,
                          rehearse=True, fault=lose_the_second_commit)
    assert result["correct"] is False
    # the counts and sums of the rows that stayed, and the AVGs over them
    assert _over(result) == {"rf_cells_unequal", "rf_avg_rel_err"}, \
        result["compared"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A plan of two rounds (one, and the one that cancels itself) over a
    re-opened engine, warmed as run.py warms it."""
    from matrixone_tpu.frontend.server import MOServer
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import LocalFS
    cfg = _config(refresh_rounds=2)
    data = loader.generate(cfg, SEED)
    plan = traffic.generate(traffic.load_mix("rf-q1q6"), cfg,
                            loader.pools(cfg, data), SEED)
    path = str(tmp_path_factory.mktemp("rf"))
    eng = Engine(LocalFS(path))
    loader.load(cfg, data, eng)
    eng.checkpoint()
    eng.close()
    eng = Engine.open(LocalFS(path))
    srv = MOServer(engine=eng, port=0).start()
    conn = loadgen.Connection(srv.port)
    for _ in range(2):
        for sql in plan["statements"][-ROUND:]:
            conn.query(sql)
    yield cfg, data, plan, conn
    conn.close()
    srv.stop()
    eng.close()


def test_the_warm_up_leaves_count_of_every_table_as_loaded(served):
    cfg, data, _plan, conn = served
    assert loader.prepare(cfg, data, conn) == {"rows_not_read_back": [0, 0]}


def test_a_refresh_function_is_one_transaction_of_the_specs_size():
    """What the manifest says of the cell is what the files send: every
    round's RF1 inserts `refresh_orders` orders (SF x 1500) with 1 to 7
    lineitems each in one transaction, and its RF2 retires as many."""
    with open(os.path.join(run.HERE, "configs", "tpch-sf1-refresh.json")) as f:
        full = json.load(f)
    assert full["refresh_orders"] == 1500 * full["scale_factor"]
    cell = run.find(run.load_json(run.ROOT, "BENCHMARK.json")["workloads"],
                    CELL, "workload")
    assert f"{full['refresh_orders']:,} orders" in cell["why"]
    cfg = _config()
    data = loader.generate(cfg, SEED)
    per = cfg["refresh_orders"]
    sets, lo, hi = (data["refresh"][k] for k in ("sets", "lo", "hi"))
    assert len(sets) == cfg["refresh_rounds"]
    for r, (orders, lineitem) in enumerate(sets):
        assert len(orders["o_orderkey"]) == hi[r] - lo[r] + 1 == per
        assert per <= len(lineitem["l_orderkey"]) <= 7 * per
        assert set(lineitem["l_orderkey"]) == set(orders["o_orderkey"])
    plan = traffic.generate(traffic.load_mix("rf-q1q6"), cfg,
                            loader.pools(cfg, data), SEED)
    first = dict(zip(loader.ROUND, plan["statements"]))
    assert first["rf1_orders"].count("), (") == per - 1
    assert first["rf2_orders"].endswith(f"between {lo[0]} and {hi[0]}")
    assert hi[0] - lo[0] + 1 == per


def test_a_plan_too_short_for_its_window_trips_rf_plan_wrapped(served):
    """The client passes the plan's end and starts it again: set 0 would
    be inserted twice (the server refuses the duplicate keys when the
    transaction commits)."""
    cfg, data, plan, conn = served
    n = len(plan["statements"])
    assert n == 2 * ROUND
    executed = []
    for j in range(n + 4):
        idx = j % n
        try:
            rows, err = conn.query(plan["statements"][idx]), None
        except loadgen.WireError as e:
            rows, err = None, str(e)
        executed.append(dict(plan["meta"][idx], client=0, statement=idx,
                             t_send_ns=2 * j, t_done_ns=2 * j + 1,
                             rows=rows, error=err))
    assert [e["error"] is None for e in executed] == [True] * (n + 3) + [False]
    numbers, _facts = reference.compare(cfg, data, executed)
    assert numbers["rf_plan_wrapped"] == [1, 0]
    assert numbers["rf_statements_failed"] == [1, 0]
    assert numbers["rf_cells_unequal"] == [0, 0]
    numbers, _facts = reference.compare(cfg, data, executed[:n])
    assert [v for v, _limit in numbers.values()][2:] == [0, 0]


def test_refresh_stmt_ms_is_the_mean_over_the_write_templates():
    from readers import latency_mean_ms_of
    ctx = {"executed": [
        {"template": "rf1_orders", "t_send_ns": 0, "t_done_ns": 4e6,
         "error": None},
        {"template": "q1", "t_send_ns": 0, "t_done_ns": 100e6, "error": None},
        {"template": "rf2_orders", "t_send_ns": 0, "t_done_ns": 8e6,
         "error": None},
        {"template": "rf2_lineitem", "t_send_ns": 0, "t_done_ns": 50e6,
         "error": "lost"}]}
    assert latency_mean_ms_of.read(ctx, templates=list(WRITES)) == 6.0
    assert latency_mean_ms_of.read(ctx, templates=["commit"]) is None
