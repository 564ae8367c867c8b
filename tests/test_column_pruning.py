"""Projection pruning (sql/optimize.prune_columns): a planned Scan carries
only the columns the plan references, and every answer stays what it was.

Reference analogue: plan/query_builder.go remapAllColRefs column pruning,
checked the way plan/tpch_test.go checks plan shapes: through EXPLAIN (what
runs) and against a session that plans without the pass."""

import re

import pytest

from matrixone_tpu.frontend import Session
from matrixone_tpu.sql import plan as P
from matrixone_tpu.sql.parser import parse_one
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.utils import tpch_full as T

Q1_COLS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", "l_shipdate"]
Q6_COLS = ["l_quantity", "l_extendedprice", "l_discount", "l_shipdate"]
N_LINEITEM_COLS = 16


@pytest.fixture(scope="module")
def tpch():
    s = Session()
    tables = T.load_tpch(s.catalog, sf=0.002, seed=3)
    conn = T.to_sqlite(tables)
    yield s, conn, tables
    conn.close()


@pytest.fixture(scope="module")
def small():
    s = Session()
    s.execute("create table t (id bigint primary key, a int, b int,"
              " c varchar(8), d double, e bigint)")
    s.execute("insert into t values " + ",".join(
        f"({i},{i % 7},{i % 5},'s{i % 3}',{i}.5,{i * 10})"
        for i in range(200)))
    s.execute("create table u (k int, w bigint, z varchar(8))")
    s.execute("insert into u values " + ",".join(
        f"({i},{i * 3},'z{i}')" for i in range(7)))
    return s


def _scans(s, sql):
    """[(table, [columns])] of every Scan line of EXPLAIN, in plan
    order."""
    txt = s.execute("explain " + sql).text
    out = []
    for m in re.finditer(r"Scan table=(\w+) cols=\[([^\]]*)\]", txt):
        cols = [c.strip().strip("'") for c in m.group(2).split(",")
                if c.strip()]
        out.append((m.group(1), cols))
    return out


def _plan(s, sql):
    return s._plan_select(parse_one(sql))


def _walk(node):
    yield node
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if c is not None:
            yield from _walk(c)
    for c in getattr(node, "children", None) or []:
        yield from _walk(c)


# ------------------------------------------------------------ TPC-H plans

@pytest.mark.parametrize("qnum,want", [(1, Q1_COLS), (6, Q6_COLS)])
def test_tpch_scan_columns_exact(tpch, qnum, want):
    s, conn, _ = tpch
    scans = _scans(s, T.QUERIES[qnum])
    assert [t for t, _ in scans] == ["lineitem"]
    assert sorted(scans[0][1]) == sorted(want)
    T.run_compare(s, conn, qnum)


def test_q3_scan_columns_per_table(tpch):
    s, conn, _ = tpch
    got = {t: sorted(c) for t, c in _scans(s, T.QUERIES[3])}
    assert got == {
        "customer": ["c_custkey", "c_mktsegment"],
        "orders": ["o_custkey", "o_orderdate", "o_orderkey",
                   "o_shippriority"],
        "lineitem": ["l_discount", "l_extendedprice", "l_orderkey",
                     "l_shipdate"]}
    T.run_compare(s, conn, 3)


@pytest.mark.parametrize("qnum", sorted(T.QUERIES))
def test_tpch_scans_name_only_text_columns(tpch, qnum):
    """Every column a pruned Scan keeps appears in the query's text (the
    one-column rule aside), and no Scan is left empty."""
    s, _conn, _ = tpch
    sql = T.QUERIES[qnum]
    for table, cols in _scans(s, sql):
        assert cols, f"Q{qnum}: empty scan of {table}"
        named = [c for c in cols if re.search(rf"\b{c}\b", sql)]
        assert named == cols or len(cols) == 1, (qnum, table, cols)


@pytest.mark.parametrize("qnum,read,pruned", [(1, 7, 9), (6, 4, 12)])
def test_scan_columns_counter(tpch, qnum, read, pruned):
    s, _conn, _ = tpch
    r0 = M.scan_columns.get(outcome="read")
    p0 = M.scan_columns.get(outcome="pruned")
    s.execute(T.QUERIES[qnum]).rows()
    assert M.scan_columns.get(outcome="read") - r0 == read
    assert M.scan_columns.get(outcome="pruned") - p0 == pruned
    assert read + pruned == N_LINEITEM_COLS


# ------------------------------------------------------- the one-column rule

@pytest.mark.parametrize("sql,cols,want", [
    ("select count(*) from t", ["a"], 200),
    ("select count(*) from t where e >= 1000", ["e"], 100),
    ("select count(*) from u", ["k"], 7),
])
def test_count_star_one_column(small, sql, cols, want):
    """Nothing is read from the rows: the scan keeps the narrowest
    fixed-width column (ties by schema order; never the varchar), or just
    the filter's column, and still counts every row."""
    assert [c for _, c in _scans(small, sql)] == [cols]
    assert small.execute(sql).rows() == [(want,)]


def test_one_column_rule_avoids_wide_columns():
    s = Session()
    s.execute("create table docs (v vecf32(8), id bigint, body text)")
    s.execute("insert into docs values " + ",".join(
        f"('[{','.join(['0.5'] * 8)}]',{i},'b{i}')" for i in range(5)))
    assert _scans(s, "select count(*) from docs") == [("docs", ["id"])]
    assert s.execute("select count(*) from docs").rows() == [(5,)]
    s.execute("create table words (w varchar(8), x text)")
    s.execute("insert into words values ('a','b'),('c','d')")
    # only varlen columns: the first one's codes
    assert _scans(s, "select count(*) from words") == [("words", ["w"])]
    assert s.execute("select count(*) from words").rows() == [(2,)]


# -------------------------------------------- where a column is referenced

def _unpruned(s, sql):
    """The same statement planned without the pass, executed."""
    from matrixone_tpu.sql.binder import Binder
    from matrixone_tpu.vm.compile import compile_plan
    sel = parse_one(sql)
    s._prepare_select(sel)
    node = s._cbo(Binder(s.catalog).bind_statement(sel))
    op = compile_plan(node, s._ctx())
    rows = []
    for ex in op.execute():
        b = s._to_host(ex, node.schema)
        rows += list(zip(*[b.columns[n].to_pylist() for n, _ in node.schema]))
    return [tuple(r) for r in rows], node


REFERENCED = [
    ("select_star", "select * from t where id < 5 order by id",
     {"t": ["id", "a", "b", "c", "d", "e"]}),
    ("order_by_only", "select a from t order by e desc limit 3",
     {"t": ["a", "e"]}),
    ("sort_no_limit", "select c from t where id < 9 order by d",
     {"t": ["id", "c", "d"]}),
    ("join_cond_only", "select t.c from t join u on t.a = u.k"
     " where t.id < 20 order by t.id", {"t": ["id", "a", "c"], "u": ["k"]}),
    ("having_only", "select a from t group by a having sum(e) > 20000"
     " order by a", {"t": ["a", "e"]}),
    ("window_only", "select id, row_number() over (partition by b"
     " order by e desc) from t where id < 30 order by id",
     {"t": ["id", "b", "e"]}),
    ("correlated_subquery", "select id from t where exists (select 1 from u"
     " where u.k = t.a and u.w > t.b) order by id",
     {"t": ["id", "a", "b"], "u": ["k", "w"]}),
    ("scalar_subquery", "select id from t where e = (select max(w) * 10"
     " from u where u.k = t.a) order by id",
     {"t": ["id", "a", "e"], "u": ["k", "w"]}),
    ("left_join_residual", "select t.id, u.z from t left join u"
     " on t.a = u.k and u.w > t.b where t.id < 12 order by t.id",
     {"t": ["id", "a", "b"], "u": ["k", "w", "z"]}),
    ("cross_join_count", "select count(*) from t, u", {"t": ["a"],
                                                       "u": ["k"]}),
    ("distinct", "select distinct b from t order by b", {"t": ["b"]}),
    ("derived_table", "select s.a from (select a, e from t where b = 1) s"
     " order by s.a limit 4", {"t": ["a", "b", "e"]}),
    ("cte", "with w as (select a, sum(e) as se from t group by a)"
     " select a from w where se > 100 order by a", {"t": ["a", "e"]}),
    ("case_and_in", "select case when b in (1, 2) then e else a end"
     " from t where id < 6 and c like 's1%' order by id",
     {"t": ["id", "a", "b", "c", "e"]}),
]


@pytest.mark.parametrize("name,sql,want", REFERENCED,
                         ids=[r[0] for r in REFERENCED])
def test_referenced_columns_survive(small, name, sql, want):
    got = {}
    for table, cols in _scans(small, sql):
        got.setdefault(table, set()).update(cols)
    assert {k: sorted(v) for k, v in got.items()} == \
        {k: sorted(v) for k, v in want.items()}
    rows = small.execute(sql).rows()
    ref, ref_node = _unpruned(small, sql)
    assert rows == ref
    # the statement's output schema and names are what they were
    assert _plan(small, sql).schema == ref_node.schema


def test_union_arms(small):
    sql = ("select a from t where id < 4 union all select k from u"
           " union all select b from t where id < 2")
    assert _scans(small, sql) == [("t", ["id", "a"]), ("u", ["k"]),
                                  ("t", ["id", "b"])]
    rows = small.execute(sql).rows()
    ref, _ = _unpruned(small, sql)
    assert sorted(rows) == sorted(ref) and len(rows) == 4 + 7 + 2


def test_schemas_consistent_after_pruning(tpch):
    """Every derived schema names only what its children still produce,
    and `columns` zips with `schema` on every Scan."""
    s, _conn, _ = tpch
    for qnum in sorted(T.QUERIES):
        sel = parse_one(T.QUERIES[qnum])
        for node in _walk(s._plan_select(sel)):
            if isinstance(node, P.Scan):
                assert len(node.columns) == len(node.schema) > 0
                assert all(q.endswith("." + c) for (q, _), c in
                           zip(node.schema, node.columns))
            elif isinstance(node, P.Join):
                # (a join hands up what is read above it: PR 34)
                below = [n for n, _ in node.left.schema]
                if node.kind not in ("semi", "anti"):
                    below += [n for n, _ in node.right.schema]
                names = [n for n, _ in node.schema]
                assert names and set(names) <= set(below)
                assert len(set(names)) == len(names)
            elif isinstance(node, (P.Filter, P.Sort, P.TopK, P.Limit)):
                # (by name: a CBO reorder leaves the pass-through schemas
                # above it in their bound order)
                assert sorted(node.schema) == sorted(node.child.schema)


# ------------------------------------------------------------- plan cache

def test_plan_cache_hit_runs_pruned_plan():
    """A plan-cache hit with new parameters serves the pruned plan: same
    narrowed scan (the counter says so) and the right answer."""
    from matrixone_tpu.serving import serving_for
    s = Session()
    s.execute("create table pc (id bigint primary key, a int, b int,"
              " c varchar(8), e bigint)")
    s.execute("insert into pc values " + ",".join(
        f"({i},{i % 7},{i % 5},'s{i}',{i * 10})" for i in range(300)))
    serving_for(s.catalog)
    sql = "select sum(e) from pc where a = {} and b < {}"
    want = lambda a, b: sum(i * 10 for i in range(300)      # noqa: E731
                            if i % 7 == a and i % 5 < b)
    assert s.execute(sql.format(1, 3)).rows() == [(want(1, 3),)]
    hits0 = (M.plan_cache_ops.get(outcome="hit")
             + M.plan_cache_ops.get(outcome="tree_hit"))
    r0 = M.scan_columns.get(outcome="read")
    p0 = M.scan_columns.get(outcome="pruned")
    assert s.execute(sql.format(4, 2)).rows() == [(want(4, 2),)]
    assert s.execute(sql.format(6, 5)).rows() == [(want(6, 5),)]
    hits = (M.plan_cache_ops.get(outcome="hit")
            + M.plan_cache_ops.get(outcome="tree_hit")) - hits0
    assert hits == 2
    assert M.scan_columns.get(outcome="read") - r0 == 2 * 3
    assert M.scan_columns.get(outcome="pruned") - p0 == 2 * 2


# --------------------------------------------------------- serde, exchange

def test_serde_round_trip_keeps_columns_aligned(tpch):
    from matrixone_tpu.sql.serde import plan_from_json, plan_to_json
    s, _conn, _ = tpch
    node = _plan(s, T.QUERIES[3])
    sub = next(n for n in _walk(node) if isinstance(n, P.Join))
    back = plan_from_json(plan_to_json(sub))
    a = [n for n in _walk(sub) if isinstance(n, P.Scan)]
    b = [n for n in _walk(back) if isinstance(n, P.Scan)]
    assert len(a) == len(b) == 3
    for x, y in zip(a, b):
        assert (x.table, x.columns, x.schema) == (y.table, y.columns,
                                                  y.schema)
        assert len(y.columns) < 9
    assert back.schema == sub.schema


def test_hash_exchanged_join_finds_its_column(monkeypatch):
    """The shuffle route names the join key as `hash_shard` after
    planning; the pruned scans still carry it (vm/operators._hash_route
    raises if not) and the answer is the local one."""
    monkeypatch.setenv("MO_SHUFFLE_BUILD_ROWS", "1")
    s = Session()
    s.execute("create table f (id bigint primary key, k bigint, v bigint,"
              " pad1 varchar(8), pad2 bigint)")
    s.execute("create table d (k bigint primary key, w bigint,"
              " pad3 varchar(8))")
    for lo in range(0, 3000, 500):
        s.execute("insert into f values " + ",".join(
            f"({i},{i % 97},{i % 13},'p{i % 5}',{i})"
            for i in range(lo, lo + 500)))
    s.execute("insert into d values " + ",".join(
        f"({k},{k % 11},'q{k}')" for k in range(97)))
    sql = ("select d.w, count(*), sum(f.v) from f join d on f.k = d.k"
           " group by d.w order by d.w")
    assert sorted((t, sorted(c)) for t, c in _scans(s, sql)) == \
        [("d", ["k", "w"]), ("f", ["k", "v"])]
    local = s.execute(sql).rows()
    moved0 = M.exchange_shuffle_rows.get()
    s.execute("set query_shards = 4")
    s.execute("set dist_min_rows = 0")
    s.execute("set batch_rows = 512")
    try:
        sharded = s.execute(sql).rows()
    finally:
        s.execute("set query_shards = 0")
    assert sharded == local
    assert M.exchange_shuffle_rows.get() > moved0


def test_vector_topk_source_is_narrowed():
    """The index rewrite copies the Scan's columns into a VectorTopK and,
    for an IVF-Flat index, adds the distance the index yields; the pass
    narrows that source the same way, so the vector column is fetched only
    where the statement selects it, and the ids are the exact scan's."""
    import numpy as np
    s = Session()
    s.execute("create table vt (id bigint primary key, title varchar(20),"
              " n int, v vecf32(4))")
    rng = np.random.default_rng(0)
    s.execute("insert into vt values " + ",".join(
        "({},'t{}',{},'[{}]')".format(
            i, i, i % 3, ",".join(f"{x:.3f}" for x in rng.normal(size=4)))
        for i in range(300)))
    sql = ("select id from vt order by l2_distance(v, '[0.1,0.2,0.3,0.4]')"
           " limit 5")
    exact = s.execute(sql).rows()
    s.execute("create index ix using ivfflat on vt (v) lists = 2"
              " op_type = 'vector_l2_ops'")
    s.execute("set ivf_nprobe = 2")
    src = next(n for n in _walk(_plan(s, sql))
               if isinstance(n, P.VectorTopK))
    assert src.dist_op == "l2_distance"
    assert src.columns == ["id", P.VECTOR_DIST]
    assert [n for n, _ in src.schema] == ["vt.id", P.VECTOR_DIST]
    assert s.execute(sql).rows() == exact
    with_v = sql.replace("select id", "select id, v")
    src = next(n for n in _walk(_plan(s, with_v))
               if isinstance(n, P.VectorTopK))
    assert src.columns == ["id", "v", P.VECTOR_DIST]
    assert [r[0] for r in s.execute(with_v).rows()] == [r[0] for r in exact]


def test_unknown_node_keeps_every_column(small):
    """Safe by construction: below a node type the pass does not know,
    nothing is pruned."""
    import dataclasses

    from matrixone_tpu.sql.optimize import prune_columns

    @dataclasses.dataclass
    class Opaque(P.PlanNode):
        child: P.PlanNode
        schema: list

    inner = _unpruned(small, "select a from t")[1]
    scan = next(n for n in _walk(inner) if isinstance(n, P.Scan))
    wrapped = prune_columns(Opaque(scan, scan.schema))
    assert len(wrapped.child.columns) == 6
    assert len(prune_columns(inner).child.columns) == 1
