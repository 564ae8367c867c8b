"""TPC-H's refresh functions under Q1 and Q6 (the deployment
`tpch-sf1-refresh`, at toy size on the CPU).

A refresh function is one transaction over `orders` and `lineitem`; every
statement sent after its commit sees all of it.  A chunk that lost rows
keeps its sliced length and carries its dead rows in the batch's row mask
(`MVCCTable.iter_chunks` -> `chunk_to_execbatch`): no program runs a
column, and no program's shape follows how many rows a commit deleted.
A DELETE's scan reads the predicate's columns and the row id.

The statements, the refresh sets and the judge are the benchmark's own
(`benchmark/loaders/tpch_refresh.py`, `traffic/rf-q1q6.json`,
`references/tpch_refresh.py`, which imports nothing of the program).
"""

import contextlib
import json
import os
import sys

import jax
import numpy as np
import pytest

from matrixone_tpu.frontend import Session
from matrixone_tpu.frontend.server import MOServer
from matrixone_tpu.storage import blockcache
from matrixone_tpu.storage.engine import Engine
from matrixone_tpu.storage.fileservice import LocalFS
from matrixone_tpu.utils import metrics as M

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import loadgen                                           # noqa: E402
import traffic                                           # noqa: E402
from loaders import tpch_refresh as loader               # noqa: E402
from references import tpch_refresh as reference         # noqa: E402

SEED = 2**31 + 35
ROUND = len(loader.ROUND)
SETS = 6                     # five rounds and the one that cancels itself


@pytest.fixture(scope="module")
def world():
    """(cfg, data, plan) of the rehearsal's size: 15,000 orders, 60,012
    lineitems, refresh sets of 15 orders."""
    with open(os.path.join(BENCH, "configs", "tpch-sf1-refresh.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"], refresh_rounds=SETS)
    data = loader.generate(cfg, SEED)
    plan = traffic.generate(traffic.load_mix("rf-q1q6"), cfg,
                            loader.pools(cfg, data), SEED)
    assert len(plan["statements"]) == SETS * ROUND
    return cfg, data, plan


@contextlib.contextmanager
def _served(world, path):
    """The tables loaded, checkpointed and re-opened (object-backed, the
    columns served by the block cache's device tier), with the harness's
    warm-up applied: the plan's last round, twice.
    -> (engine, session, send) where send(sql) -> rows as the wire's text."""
    cfg, data, plan = world
    eng = Engine(LocalFS(str(path)))
    loader.load(cfg, data, eng)
    eng.checkpoint()
    eng.close()
    blockcache.CACHE.clear()
    eng = Engine.open(LocalFS(str(path)))
    session = Session(catalog=eng)
    srv = MOServer(engine=eng, port=0).start()
    conn = loadgen.Connection(srv.port)
    try:
        for _ in range(2):
            for sql in plan["statements"][-ROUND:]:
                conn.query(sql)
        yield eng, session, conn.query
    finally:
        conn.close()
        srv.stop()
        session.close()
        eng.close()
        blockcache.CACHE.clear()


def _through_session(session):
    def send(sql):
        result = session.execute(sql)
        if result.batch is None:
            return []
        return [[None if v is None else str(v) for v in row]
                for row in result.rows()]
    return send


def _window(plan, send, rounds):
    """`rounds` rounds from statement 0 on -> `executed`, as run.py
    builds it from what the generator child reports."""
    executed = []
    for idx in range(rounds * ROUND):
        executed.append(dict(plan["meta"][idx], client=0, statement=idx,
                             t_send_ns=2 * idx, t_done_ns=2 * idx + 1,
                             rows=send(plan["statements"][idx]), error=None))
    return executed


def _compared(world, executed):
    cfg, data, _plan = world
    numbers, facts = reference.compare(cfg, data, executed)
    return {k: v for k, (v, _limit) in numbers.items()}, facts


@contextlib.contextmanager
def _compiled():
    """-> the programs compiled while it is open (as `benchmark/run.py`'s
    `Meters` counts them; the tests run with no persistent cache)."""
    names = []

    def on_duration(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield names
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


# ------------------------------------- (a) every commit is read back whole

@pytest.mark.parametrize("path", ["session", "wire"])
def test_three_rounds_are_exact_after_every_commit(world, tmp_path, path):
    """Q1, Q6 and `orders_check` after each of six commits equal the
    reference's replay of the same statements: exact DECIMAL sums and
    counts, AVG within the DOUBLE's limit."""
    _cfg, _data, plan = world
    with _served(world, tmp_path) as (_eng, session, wire):
        send = wire if path == "wire" else _through_session(session)
        executed = _window(plan, send, rounds=3)
    numbers, facts = _compared(world, executed)
    assert facts["rf_commits"] == 6 and facts["rf_answers_judged"] == 18
    assert numbers["rf_cells_unequal"] == 0
    assert numbers["rf_avg_rel_err"] <= reference.LIMITS["rf_avg_rel_err"]
    assert numbers["rf_statements_failed"] == 0
    assert numbers["rf_plan_wrapped"] == 0
    # the sums moved with every commit: no two orders_checks agree
    checks = [tuple(e["rows"][0]) for e in executed
              if e["template"] == "orders_check"]
    assert len(set(checks)) == len(checks) == 6


def test_the_warm_up_leaves_every_table_as_loaded(world, tmp_path):
    cfg, data, _plan = world
    with _served(world, tmp_path) as (eng, _session, wire):
        for table, n in loader.rows(cfg, data).items():
            assert wire(f"select count(*) from {table}") == [[str(n)]]
        # two cancelled refresh pairs: their rows are dead, in memory
        for table in ("orders", "lineitem"):
            t = eng.get_table(table)
            assert sum(not s.is_lazy for s in t.segments) == 2
            assert len(t.tombstones) == 2


def test_a_rolled_back_refresh_leaves_every_answer_as_before(world,
                                                             tmp_path):
    _cfg, _data, plan = world
    readers = [plan["statements"][i] for i in (4, 5, 6)]
    with _served(world, tmp_path) as (_eng, _session, wire):
        before = [wire(sql) for sql in readers]
        for first in (0, 7):                      # RF1, then RF2
            begin, one, two, _commit = plan["statements"][first:first + 4]
            for sql in (begin, one, two):
                wire(sql)
            inside = [wire(sql) for sql in readers]
            assert inside[0] != before[0]         # it reads its own writes
            wire("rollback")
            assert [wire(sql) for sql in readers] == before


# ------------------------- (b) dead rows ride the mask at the chunk's length

QUERIES = (
    "select count(*), sum(l_quantity), min(l_shipdate), max(l_comment) "
    "from lineitem",
    "select l_returnflag, l_linestatus, count(*), sum(l_extendedprice * "
    "(1 - l_discount)), avg(l_tax) from lineitem where l_shipdate <= date "
    "'1998-09-02' group by l_returnflag, l_linestatus order by "
    "l_returnflag, l_linestatus",
    "select l_orderkey, l_linenumber, l_comment from lineitem where "
    "l_orderkey < 40 order by l_orderkey, l_linenumber",
    "select o_orderpriority, count(*), sum(l_quantity) from orders, "
    "lineitem where o_orderkey = l_orderkey and l_orderkey < 3000 group by "
    "o_orderpriority order by o_orderpriority",
    "select l_orderkey, sum(l_extendedprice) as s from lineitem group by "
    "l_orderkey order by s desc, l_orderkey limit 7",
)


@pytest.mark.parametrize("fused", [False, True])
def test_a_chunk_with_dead_rows_scans_like_one_that_never_held_them(
        world, tmp_path, monkeypatch, fused):
    """`lineitem` in chunks of 16,384 rows, object-backed and resident;
    rows of the first chunk are deleted.  Every statement answers as it
    does on a table loaded without those rows, through the eager
    operators and through the fused fragments, and nothing is gathered a
    column."""
    if fused:
        monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    cfg, data, _plan = world
    dead = "l_orderkey between 7 and 21 or l_orderkey = 1000"
    answers = []
    for name, delete_first in (("thinned", False), ("never", True)):
        eng = Engine(LocalFS(str(tmp_path / name)))
        loader.load(cfg, data, eng)
        s = Session(catalog=eng)
        if delete_first:
            # one live segment a table, flushed without the rows
            s.execute(f"delete from lineitem where {dead}")
            eng.merge_table("lineitem")
        eng.checkpoint()
        s.close()
        eng.close()
        blockcache.CACHE.clear()
        eng = Engine.open(LocalFS(str(tmp_path / name)))
        s = Session(catalog=eng)
        s.execute("set batch_rows = 16384")
        if not delete_first:
            for sql in QUERIES:
                s.execute(sql)                    # resident, summaries kept
            s.execute(f"delete from lineitem where {dead}")
        column0 = M.scan_slice_dispatch.get(how="column")
        dead0 = M.scan_chunk_rows.get(state="dead")
        answers.append([s.execute(sql).rows() for sql in QUERIES])
        assert M.scan_slice_dispatch.get(how="column") == column0
        if not delete_first:
            assert M.scan_chunk_rows.get(state="dead") > dead0
            t = eng.get_table("lineitem")
            assert all(seg.is_lazy for seg in t.segments)
        s.close()
        eng.close()
        blockcache.CACHE.clear()
    assert answers[0] == answers[1]
    assert answers[0][0][0][0] > 0


def test_compiles_do_not_follow_the_rows_a_commit_deleted(world, tmp_path):
    """Four refresh pairs that insert and delete different numbers of
    lineitems: whatever the first two compile, the third and the fourth
    compile nothing."""
    _cfg, data, plan = world
    deleted = [len(s[1]["l_orderkey"]) for s in data["refresh"]["sets"]]
    lo_hi = list(zip(data["refresh"]["lo"], data["refresh"]["hi"]))
    assert len(set(deleted[:4])) > 1 and len(set(lo_hi[:4])) == 4
    with _served(world, tmp_path) as (_eng, _session, wire):
        with _compiled() as names:
            executed = _window(plan, wire, rounds=2)
            after_second = len(names)
            for sql in plan["statements"][2 * ROUND:4 * ROUND]:
                wire(sql)
            assert names[after_second:] == []
    assert _compared(world, executed)[0]["rf_cells_unequal"] == 0


# ------------------------------- (c) a DELETE reads what its predicate names

def test_a_delete_scans_its_predicates_columns_and_the_row_id(world,
                                                              tmp_path):
    with _served(world, tmp_path) as (_eng, session, _wire):
        plan_text = session.execute(
            "explain delete from lineitem where l_orderkey between 3 and 9"
        ).text
        scans = [ln.strip() for ln in plan_text.splitlines()
                 if ln.strip().startswith("Scan")]
        assert len(scans) == 1
        assert "cols=['l_orderkey', '__rowid'] filters=2" in scans[0]
        read0 = M.scan_columns.get(outcome="read")
        pruned0 = M.scan_columns.get(outcome="pruned")
        session.execute("delete from lineitem where l_orderkey between 3 "
                        "and 9 and l_quantity < 100")
        # l_orderkey, l_quantity and the row id, counted as a SELECT's
        # scan counts them: the table's sixteen columns less the three
        assert M.scan_columns.get(outcome="read") - read0 == 3
        assert M.scan_columns.get(outcome="pruned") - pruned0 == 13
        # the predicate is the Scan's own: the zonemaps leave the keyed
        # DELETE one of lineitem's four flushed segments
        skipped0 = M.scan_chunks.get(outcome="pruned_segment")
        session.execute("delete from lineitem where l_orderkey = 11")
        assert M.scan_chunks.get(outcome="pruned_segment") - skipped0 == 3


# -------- (d) a DELETE and an UPDATE pick their rows on the host, one way

def _no_compaction(session, monkeypatch):
    """`_to_host` compacts a batch on the device (a program of the scan
    chunk's length: a cumsum that compiles for minutes on the chip); a
    DML statement's rows come through `_dml_rows` and never through it."""
    def refuse(ex, schema):
        raise AssertionError("a DML statement compacted a batch on the "
                             "device")
    monkeypatch.setattr(session, "_to_host", refuse)


@pytest.mark.parametrize("where, keys", [
    ("l_orderkey between 3 and 9", range(3, 10)),
    ("l_orderkey = 11 or l_orderkey > 1000000000", [11]),
    ("l_orderkey < 0", [])])
def test_an_update_rewrites_the_rows_its_predicate_names(
        world, tmp_path, monkeypatch, where, keys):
    """Every column of the rows it names comes back as it was, but the one
    assigned; every other row is untouched: checked on the whole table
    against the loaded arrays."""
    _cfg, data, _plan = world
    li = data["lineitem"]
    hit = np.isin(li["l_orderkey"], list(keys))
    columns = ("l_orderkey, l_linenumber, l_quantity, l_extendedprice, "
               "l_shipdate, l_returnflag, l_comment")
    everything = (f"select {columns} from lineitem "
                  f"order by l_orderkey, l_linenumber")
    with _served(world, tmp_path) as (_eng, session, _wire):
        before = session.execute(everything).rows()
        _no_compaction(session, monkeypatch)
        result = session.execute(
            f"update lineitem set l_quantity = l_quantity + 1 where {where}")
        monkeypatch.undo()
        assert result.affected == int(hit.sum())
        after = session.execute(everything).rows()
    assert len(after) == len(before) == len(hit)
    changed = 0
    for old, new in zip(before, after):
        if old[0] in keys:
            assert new[2] == old[2] + 1 and new[:2] + new[3:] \
                == old[:2] + old[3:]
            changed += 1
        else:
            assert new == old
    assert changed == int(hit.sum())


def test_a_delete_and_an_update_inside_a_transaction_roll_back(
        world, tmp_path, monkeypatch):
    _cfg, data, plan = world
    readers = [plan["statements"][i] for i in (4, 5, 6)]
    with _served(world, tmp_path) as (_eng, session, _wire):
        send = _through_session(session)
        before = [send(sql) for sql in readers]
        _no_compaction(session, monkeypatch)
        session.execute("begin")
        assert session.execute(
            "update lineitem set l_discount = 0.10, l_tax = l_tax + 0.01 "
            "where l_orderkey between 100 and 140").affected > 0
        assert session.execute(
            "delete from lineitem where l_orderkey between 120 and 160"
        ).affected > 0
        assert session.execute(
            "delete from orders where o_orderkey between 120 and 160"
        ).affected == 41
        monkeypatch.undo()
        inside = [send(sql) for sql in readers]
        assert inside[0] != before[0] and inside[1] != before[1]
        session.execute("rollback")
        assert [send(sql) for sql in readers] == before


def test_a_duplicate_key_is_refused_when_every_key_is_a_bloom_suspect(
        world, tmp_path, monkeypatch):
    """A commit's key check with a filter that suspects every key: a
    refresh function of fresh keys commits, a key that a flushed segment
    holds is refused, in `orders` (one key column) and in `lineitem`
    (two, hashed)."""
    from matrixone_tpu import native
    from matrixone_tpu.storage.engine import DuplicateKeyError
    monkeypatch.setattr(native.BloomFilter, "probe_int64",
                        lambda self, keys: np.ones(len(keys), np.bool_))
    _cfg, data, plan = world
    with _served(world, tmp_path) as (_eng, session, wire):
        for sql in plan["statements"][:4]:          # round 0's RF1
            wire(sql)
        key = int(data["orders"]["o_orderkey"][-1])
        with pytest.raises(DuplicateKeyError):
            session.execute(
                f"insert into orders select * from orders "
                f"where o_orderkey = {key}")
        with pytest.raises(DuplicateKeyError):
            session.execute(
                f"insert into lineitem select * from lineitem "
                f"where l_orderkey = {key} and l_linenumber = 1")
