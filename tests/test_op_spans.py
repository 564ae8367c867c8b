"""Every operator's step under a span `op.<kind>` (vm/operators.py
`Operator`, `motrace.steps`), thread CPU time on every span (`cpu_us`),
and a `mo_device_wait_total` count at every host wait of the eager
operators and the result path."""

import collections
import importlib
import pkgutil

import numpy as np
import pytest

import matrixone_tpu.vm
from matrixone_tpu.frontend.session import Session
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.utils import motrace
from matrixone_tpu.utils import tpch_full as T
from matrixone_tpu.vm import operators as O

#: operators that only hand back batches they were given: no span
REPLAYS = {"_ReplaySource", "_IterSource", "_ReplayOp", "_Given"}
KINDS = {"scan", "filter", "project", "aggregate", "udf_aggregate", "sort",
         "topk", "limit", "distinct", "union", "sample", "fill", "window",
         "join", "values", "materialized", "fused", "fused_join",
         "fused_window", "vector_topk", "fulltext_topk"}

Q3 = ("select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as "
      "revenue, o_orderdate, o_shippriority from customer, orders, lineitem "
      "where c_mktsegment = 'BUILDING' and c_custkey = o_custkey and "
      "l_orderkey = o_orderkey and o_orderdate < date '1995-03-15' and "
      "l_shipdate > date '1995-03-15' group by l_orderkey, o_orderdate, "
      "o_shippriority order by revenue desc, o_orderdate limit 10")


@pytest.fixture
def tracer():
    tr = motrace.TRACER
    was = (tr.armed, tr.sample)
    tr.arm(sample=1.0)
    tr.clear()
    yield tr
    tr.armed, tr.sample = was
    tr.clear()


@pytest.fixture(scope="module")
def corpus():
    s = Session()
    T.load_tpch(s.catalog, sf=0.002, seed=5)
    s.execute("set batch_rows = 4096")         # lineitem: several batches
    yield s
    s.close()


def _spans(tr):
    return [sp for tid in tr.trace_ids() for sp in tr.spans_of(tid)]


def _waits():
    return collections.Counter(
        {v["labels"]["site"]: v["value"]
         for v in M.device_wait.snapshot()["values"]})


def _uncovered_share(spans, name):
    """Share of the spans named `name` that no descendant covers."""
    below = collections.defaultdict(list)
    for sp in spans:
        below[sp["psid"]].append(sp)
    total = bare = 0
    for sp in spans:
        if sp["name"] != name:
            continue
        lo, hi = sp["ts_us"], sp["ts_us"] + sp["dur_us"]
        stack, cover = [sp["sid"]], []
        while stack:
            for k in below[stack.pop()]:
                stack.append(k["sid"])
                cover.append((max(k["ts_us"], lo),
                              min(k["ts_us"] + k["dur_us"], hi)))
        covered, edge = 0, lo
        for a, b in sorted(cover):
            if b > edge:
                covered += b - max(a, edge)
                edge = b
        total += hi - lo
        bare += hi - lo - covered
    return bare / total


def _subclasses(cls):
    for sub in cls.__subclasses__():
        yield sub
        yield from _subclasses(sub)


# ------------------------------------------------------------ the kinds

def test_every_operator_of_the_vm_has_a_kind_or_replays():
    for mod in pkgutil.iter_modules(matrixone_tpu.vm.__path__):
        importlib.import_module(f"matrixone_tpu.vm.{mod.name}")
    ops = {c for c in _subclasses(O.Operator)
           if c.__module__.startswith("matrixone_tpu.vm.")}
    assert {c.__name__ for c in ops if c.step_span is None} == REPLAYS
    kinds = [c.step_span for c in ops if c.step_span is not None]
    assert sorted(kinds) == sorted(f"op.{k}" for k in KINDS)  # one a class


# ------------------------------------------------ a statement, traced

def test_grouped_aggregate_above_a_fused_join(corpus, tracer, monkeypatch):
    """Q3's shape: the eager GROUP BY's steps pull the fused join's, and
    every span below `run` nests in an operator's step: `run` is almost
    all covered."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    corpus.execute(Q3).rows()                     # compile outside the trace
    tracer.clear()
    corpus.execute(Q3).rows()
    spans = _spans(tracer)
    by_sid = {sp["sid"]: sp for sp in spans}
    names = collections.Counter(sp["name"] for sp in spans)
    assert names["op.aggregate"] >= 1 and names["op.fused_join"] >= 2
    assert all(0 <= sp["cpu_us"] for sp in spans)
    # a child on the statement's thread lies inside its parent
    for sp in spans:
        p = by_sid.get(sp["psid"])
        assert p is not None or sp["name"] == "statement", sp
        if p is not None and p["thread"] == sp["thread"]:
            assert p["cpu_us"] + 1 >= sp["cpu_us"]
    parent = {sp["name"]: by_sid[sp["psid"]]["name"] for sp in spans
              if sp["name"] in ("op.fused_join", "join.build",
                                "join.probe.dispatch")}
    assert parent["join.probe.dispatch"] == "op.fused_join"
    assert parent["join.build"] == "op.fused_join"
    assert any(by_sid[sp["psid"]]["name"] == "op.aggregate"
               for sp in spans if sp["name"] == "op.fused_join")
    assert _uncovered_share(spans, "run") < 0.05


def test_the_chrome_export_carries_cpu_time(corpus, tracer):
    corpus.execute("select count(*) from nation").rows()
    tid = tracer.trace_ids()[-1]
    events = [e for e in motrace.chrome_trace(tid)["traceEvents"]
              if e["ph"] == "X"]
    assert events and all(e["args"]["cpu_us"] >= 0 for e in events)


def test_disarmed_execute_is_the_operators_own_generator(corpus):
    tr = motrace.TRACER
    assert not tr.armed
    tr.clear()
    op = O.FilterOp.__new__(O.FilterOp)
    op.child = O.ValuesOp.__new__(O.ValuesOp)
    gen = op.execute()
    assert gen.gi_code is O.FilterOp.execute.__wrapped__.__code__
    corpus.execute("select count(*) from orders where o_custkey > 3").rows()
    assert tr.trace_ids() == [] and tr.status()["spans"] == 0


# -------------------------------------------- the wrapper, by itself

class _Source(O.Operator):
    step_span = "op.test_source"

    def __init__(self, n):
        self.n, self.closed = n, False

    def execute(self):
        try:
            for i in range(self.n):
                yield i
        finally:
            self.closed = True


class _Derived(_Source):
    step_span = "op.test_derived"

    def execute(self):
        yield from super().execute()


def test_a_step_is_a_span_and_a_stop_closes_the_inner_generator(tracer):
    src = _Source(5)
    with motrace.root_span("statement") as root:
        it = src.execute()
        assert [next(it), next(it)] == [0, 1]
        assert motrace.current_ctx().span_id == root._sid
        it.close()                           # a LIMIT that has enough
        assert src.closed
        assert motrace.current_ctx().span_id == root._sid
    assert motrace.current_ctx() is None
    steps = [sp for sp in _spans(tracer) if sp["name"] == "op.test_source"]
    assert len(steps) == 2 and all(sp["psid"] == root._sid for sp in steps)


def test_a_limit_that_stops_early_leaves_no_context(corpus, tracer):
    rows = corpus.execute("select l_orderkey from lineitem limit 3").rows()
    assert len(rows) == 3
    assert motrace.current_ctx() is None
    names = {sp["name"] for sp in _spans(tracer)}
    assert "op.limit" in names or "op.fused" in names


def test_a_subclass_through_super_opens_one_span_a_step(tracer):
    with motrace.root_span("statement"):
        assert list(_Derived(3).execute()) == [0, 1, 2]
    names = collections.Counter(sp["name"] for sp in _spans(tracer))
    assert names["op.test_derived"] == 3 and names["op.test_source"] == 0


def test_the_last_step_is_a_span_only_if_it_did_work(tracer):
    """A one-batch operator is one span: the `next()` that only learns
    its input is done records nothing, one that works (or opens a span
    that is recorded) is kept."""
    class Busy(O.Operator):
        step_span = "op.test_busy"

        def execute(self):
            yield 1
            with motrace.span("after"):
                pass

    with motrace.root_span("statement"):
        assert list(_Source(1).execute()) == [0]
        assert list(Busy().execute()) == [1]
    names = collections.Counter(sp["name"] for sp in _spans(tracer))
    assert names["op.test_source"] == 1
    assert names["op.test_busy"] == 2 and names["after"] == 1


def test_an_error_in_a_step_is_recorded_and_raised(tracer):
    class Broken(O.Operator):
        step_span = "op.test_broken"

        def execute(self):
            yield 1
            raise ValueError("boom")

    with pytest.raises(ValueError):
        with motrace.root_span("statement"):
            list(Broken().execute())
    assert motrace.current_ctx() is None
    steps = [sp for sp in _spans(tracer) if sp["name"] == "op.test_broken"]
    assert [sp["attrs"].get("error") for sp in steps] == [None, "ValueError"]


def test_cpu_time_of_a_span_that_sleeps_is_small(tracer):
    import time
    with motrace.root_span("statement"):
        with motrace.span("sleeps"):
            time.sleep(0.05)
        with motrace.span("works"):
            x = 0
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.02:
                x += 1
    got = {sp["name"]: sp for sp in _spans(tracer)}
    assert got["sleeps"]["dur_us"] >= 50_000 > 10 * got["sleeps"]["cpu_us"]
    assert got["works"]["cpu_us"] >= 19_000


# ------------------------------------------------- host waits, counted

def test_the_eager_aggregate_and_the_result_count_their_waits(corpus):
    """A computed key takes the general group table: one wait for the
    groups of each batch, one for each merge into the table."""
    n = corpus.execute("select count(*) from lineitem").rows()[0][0]
    batches = -(-n // 4096)
    assert batches >= 3
    sql = ("select l_orderkey % 1000 as k, count(*) from lineitem "
           "group by k order by k limit 5")
    corpus.execute(sql).rows()
    w0 = _waits()
    corpus.execute(sql).rows()
    moved = _waits() - w0
    assert moved["agg_groups"] == 2 * batches - 1
    assert moved["result"] == 1


def test_the_unfused_join_counts_its_waits(corpus, monkeypatch):
    monkeypatch.setenv("MO_FUSION_JOIN", "0")
    sql = ("select count(*) from orders, customer where o_custkey = "
           "c_custkey and c_mktsegment = 'BUILDING'")
    want = corpus.execute(sql).rows()
    w0 = _waits()
    assert corpus.execute(sql).rows() == want
    moved = _waits() - w0
    # the runtime filter's range, then an overflow flag a probe batch
    assert moved["join_eager"] >= 2
    assert moved["result"] == 1


@pytest.mark.parametrize("sql,site", [
    ("select distinct o_orderstatus from orders", "distinct"),
    ("select o_orderkey from orders where o_custkey > 2 limit 2", "limit"),
])
def test_distinct_and_limit_count_their_waits(corpus, sql, site):
    corpus.execute(sql).rows()
    w0 = _waits()
    corpus.execute(sql).rows()
    assert (_waits() - w0)[site] >= 1


def test_fill_fetches_its_input_in_one_wait():
    s = Session()
    s.execute("create table f (k int, v int)")
    s.execute("insert into f values (1, 10), (2, null), (3, 30)")
    sql = "select k, sum(v) as v from f group by k fill(prev) order by k"
    got = s.execute(sql).rows()
    w0 = _waits()
    assert s.execute(sql).rows() == got
    assert (_waits() - w0)["fill"] == 1
    assert [tuple(r) for r in got] == [(1, 10), (2, 10), (3, 30)]
    s.close()


def test_a_vector_statement_waits_for_its_search_and_its_result():
    s = Session()
    rng = np.random.default_rng(4)
    x = rng.standard_normal((600, 8)).astype(np.float32)
    s.execute("create table docs (id bigint primary key, v vecf32(8))")
    s.catalog.get_table("docs").insert_numpy(
        {"id": np.arange(600, dtype=np.int64), "v": x})
    s.execute("create index docs_v using ivfflat on docs (v) lists = 4 "
              "op_type = 'vector_l2_ops'")
    lit = "[" + ",".join(f"{v:.5f}" for v in x[7]) + "]"
    sql = f"select id from docs order by l2_distance(v, '{lit}') limit 5"
    s.execute(sql).rows()
    w0 = _waits()
    assert s.execute(sql).rows()[0][0] == 7
    moved = _waits() - w0
    assert moved["result"] == 1 and moved["vector_search"] == 1
    s.close()
