"""A join hands up only the columns that are read above it (PR 34):
`sql/optimize.prune_columns` narrows a `Join`'s schema to what its parent
reads, `vm/join.emit_lanes` (shared by the fused probe, the eager
`JoinOp`, the grace spill and every degrade ladder) builds only those
columns, and the fused probe's program is keyed by the list.

The planner half is checked on the plans themselves (the thirteen Star
Schema Benchmark templates and hand-written joins of every kind), the
execution half against plain references: `utils/ssb.answer`, TPC-H Q3's
oracle and a nested-loop join in Python."""

import datetime
import random

import pytest

from matrixone_tpu.frontend.session import Session
from matrixone_tpu.sql import plan as P
from matrixone_tpu.sql.expr import columns_used
from matrixone_tpu.sql.parser import parse_one
from matrixone_tpu.utils import keys as keyaudit
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.utils import ssb, tpch

TEMPLATES = list(ssb.TEMPLATES)
SIZES = {"customer": 3000, "supplier": 2000, "part": 4000}

#: the build-side columns each template reads ABOVE the join that
#: gathers them (ISSUE 34's table, from the queries' text: a dimension's
#: key and its filter-only columns are read inside or below the join)
READ_ABOVE = {
    "q1.1": {"dates": []}, "q1.2": {"dates": []}, "q1.3": {"dates": []},
    "q2.1": {"part": ["p_brand1"], "supplier": [], "dates": ["d_year"]},
    "q2.2": {"part": ["p_brand1"], "supplier": [], "dates": ["d_year"]},
    "q2.3": {"part": ["p_brand1"], "supplier": [], "dates": ["d_year"]},
    "q3.1": {"customer": ["c_nation"], "supplier": ["s_nation"],
             "dates": ["d_year"]},
    "q3.2": {"customer": ["c_city"], "supplier": ["s_city"],
             "dates": ["d_year"]},
    "q3.3": {"customer": ["c_city"], "supplier": ["s_city"],
             "dates": ["d_year"]},
    "q3.4": {"customer": ["c_city"], "supplier": ["s_city"],
             "dates": ["d_year"]},
    "q4.1": {"customer": ["c_nation"], "supplier": [], "part": [],
             "dates": ["d_year"]},
    "q4.2": {"customer": [], "supplier": ["s_nation"],
             "part": ["p_category"], "dates": ["d_year"]},
    "q4.3": {"customer": [], "supplier": ["s_city"],
             "part": ["p_brand1"], "dates": ["d_year"]},
}


@pytest.fixture(scope="module")
def tables():
    return ssb.gen_ssb(0.01, 7, sizes=SIZES)


@pytest.fixture(scope="module")
def star(tables):
    return ssb.Star(tables)


@pytest.fixture(scope="module")
def session(tables):
    s = Session()
    ssb.load_ssb(s.catalog, tables, commits=2)
    yield s
    s.close()


@pytest.fixture(scope="module")
def q3():
    s = Session()
    arrays = tpch.load_lineitem(s.catalog, 20_000, seed=2)
    q3data = tpch.load_tpch_q3(s.catalog, 4_000, seed=2)
    yield s, tpch.q3_oracle(arrays, q3data)
    s.close()


def _plan(s, sql):
    return s._plan_select(parse_one(sql))


def _walk(node, parent=None):
    yield node, parent
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if c is not None:
            yield from _walk(c, node)
    for c in getattr(node, "children", None) or []:
        yield from _walk(c, node)


def _joins(node):
    return [(n, p) for n, p in _walk(node) if isinstance(n, P.Join)]


def _names(schema):
    return [n for n, _ in schema]


def _counter(name, **labels):
    for v in M.REGISTRY.snapshot().get(name, {}).get("values", []):
        if v["labels"] == labels:
            return v["value"]
    return 0


def _modes():
    return {m: _counter("mo_fusion_exec_total", mode=m)
            for m in ("fused", "fallback", "degraded", "eager")}


# ------------------------------------------------------------- the planner

def _read_by(parent, join):
    """The names `parent` reads of `join`'s output, from the parent's own
    expressions: an independent reading of the rule."""
    if isinstance(parent, P.Aggregate):
        exprs = list(parent.group_keys) + [a.arg for a in parent.aggs]
        return {c for e in exprs if e is not None for c in columns_used(e)}
    assert isinstance(parent, P.Join) and parent.left is join
    own = {c for k in parent.left_keys for c in columns_used(k)}
    return set(_names(parent.schema)) | own


@pytest.mark.parametrize("template", TEMPLATES)
def test_each_join_of_a_template_hands_up_what_its_parent_reads(
        session, template):
    plan = _plan(session, ssb.render(template, ssb.PAPER_PARAMS[template]))
    joins = _joins(plan)
    assert len(joins) == {"q1": 1, "q2": 3, "q3": 3, "q4": 4}[template[:2]]
    for join, parent in joins:
        produced = set(_names(join.left.schema) + _names(join.right.schema))
        assert set(_names(join.schema)) == _read_by(parent, join) & produced
        # the inputs still bring the keys, which are read inside
        keys = {c for k in join.left_keys + join.right_keys
                for c in columns_used(k)}
        assert keys <= produced


@pytest.mark.parametrize("template", TEMPLATES)
def test_a_level_gathers_only_the_build_columns_read_above_it(
        session, template):
    plan = _plan(session, ssb.render(template, ssb.PAPER_PARAMS[template]))
    got = {}
    for join, _parent in _joins(plan):
        assert isinstance(join.right, P.Scan)
        build = set(_names(join.right.schema))
        got[join.right.table] = sorted(
            n.split(".", 1)[1] for n in _names(join.schema) if n in build)
    assert got == {t: sorted(c) for t, c in READ_ABOVE[template].items()}


def test_q41_top_join_hands_up_four_columns_not_fifteen(session):
    plan = _plan(session, ssb.render("q4.1", ssb.PAPER_PARAMS["q4.1"]))
    top = _joins(plan)[0][0]
    assert sorted(_names(top.schema)) == [
        "customer.c_nation", "dates.d_year", "lineorder.lo_revenue",
        "lineorder.lo_supplycost"]
    assert len(top.left.schema) + len(top.right.schema) == 6
    text = session.execute(
        "explain " + ssb.render("q4.1", ssb.PAPER_PARAMS["q4.1"])).text
    line = next(ln for ln in text.splitlines() if "Join" in ln)
    assert "build=unique" in line and "join=build+probe" in line
    assert line.count("'") == 8          # EXPLAIN prints the narrowed list


@pytest.fixture(scope="module")
def small():
    s = Session()
    s.execute("create table t (id bigint primary key, k int, a bigint,"
              " b varchar(8))")
    s.execute("create table u (k int, c bigint, d varchar(8), e bigint)")
    # duplicates on both sides, NULL keys on both sides, rows without a
    # partner on both sides
    t_rows = [(i, None if i % 11 == 0 else i % 9, i * 10, f"b{i % 4}")
              for i in range(60)]
    u_rows = [(None if i % 7 == 0 else (i % 6) + 5, i * 3, f"d{i % 3}",
               i % 5) for i in range(40)]
    s.execute("insert into t values " + ",".join(
        "(%d,%s,%d,'%s')" % (i, "null" if k is None else k, a, b)
        for i, k, a, b in t_rows))
    s.execute("insert into u values " + ",".join(
        "(%s,%d,'%s',%d)" % ("null" if k is None else k, c, d, e)
        for k, c, d, e in u_rows))
    yield s, t_rows, u_rows
    s.close()


ALL_EIGHT = ["t.id", "t.k", "t.a", "t.b", "u.k", "u.c", "u.d", "u.e"]


@pytest.mark.parametrize("sql,expect", [
    ("select * from t join u on t.k = u.k", ALL_EIGHT),
    ("select distinct * from t join u on t.k = u.k", ALL_EIGHT),
    ("select id from t union all select * from"
     " (select t.id from t join u on t.k = u.k) x", None),
], ids=["select_star", "distinct", "union_arm"])
def test_a_parent_that_reads_everything_keeps_everything(small, sql, expect):
    s, _t, _u = small
    joins = _joins(_plan(s, sql))
    assert len(joins) == 1
    join = joins[0][0]
    produced = _names(join.left.schema) + _names(join.right.schema)
    if expect is None:
        # the arm's own Project reads t.id alone; the Union asked the
        # Project for everything and the Project asks for what it reads
        assert _names(join.schema) == ["t.id"]
        assert sorted(produced) == ["t.id", "t.k", "u.k"]
    else:
        assert sorted(_names(join.schema)) == sorted(expect)
        assert sorted(produced) == sorted(expect)
    s.execute(sql).rows()


@pytest.mark.parametrize("kind", ["inner", "left"])
def test_a_residuals_columns_are_read_inside_and_not_handed_up(small, kind):
    s, t_rows, u_rows = small
    join_kw = "join" if kind == "inner" else "left join"
    sql = (f"select t.a, u.c from t {join_kw} u"
           " on t.k = u.k and u.e < 3 and t.id > 4")
    join = _joins(_plan(s, sql))[0][0]
    assert join.kind == kind and join.residual is not None
    inside = set(columns_used(join.residual))
    assert inside and inside <= {"u.e", "t.id"}
    assert sorted(_names(join.schema)) == ["t.a", "u.c"]
    # the inputs still produce what the join reads inside
    assert inside <= set(_names(join.left.schema)
                         + _names(join.right.schema))
    want = _nested_loop(t_rows, u_rows, kind, ("a", "c"),
                        on=lambda t, u: u["e"] < 3 and t["id"] > 4)
    assert sorted(s.execute(sql).rows(), key=repr) == sorted(want, key=repr)


@pytest.mark.parametrize("kind", ["inner", "left", "full", "cross"])
def test_count_star_over_a_join_keeps_one_probe_column(small, kind):
    s, t_rows, u_rows = small
    on = "" if kind == "cross" else " on t.k = u.k"
    sql = f"select count(*) from t {kind} join u{on}"
    join = _joins(_plan(s, sql))[0][0]
    assert join.kind == kind
    assert len(join.schema) == 1
    # the carrier is a column of the probe side: a build column would
    # cost a gather a lane
    assert join.schema[0][0] in _names(join.left.schema)
    assert join.schema[0][1].np_dtype.itemsize <= 8
    want = len(_nested_loop(t_rows, u_rows, kind, ("id",)))
    assert s.execute(sql).rows() == [(want,)]


def test_a_semi_join_stays_as_it_was(small):
    s, t_rows, u_rows = small
    sql = ("select a from t where exists"
           " (select 1 from u where u.k = t.k and u.e > 1)")
    join = _joins(_plan(s, sql))[0][0]
    assert join.kind == "semi"
    assert _names(join.schema) == _names(join.left.schema)
    want = _nested_loop(t_rows, u_rows, "semi", ("a",),
                        on=lambda t, u: u["e"] > 1)
    assert sorted(s.execute(sql).rows()) == sorted(want)


# ------------------------------------------- execution against references

def _nested_loop(t_rows, u_rows, kind, out, on=None):
    """A plain nested-loop join of `small`'s two tables on k (SQL NULL
    never matches), projecting the columns `out` names."""
    ts = [dict(zip(("id", "k", "a", "b"), r)) for r in t_rows]
    us = [dict(zip(("k", "c", "d", "e"), r)) for r in u_rows]
    null_t = dict.fromkeys(("id", "k", "a", "b"))
    null_u = dict.fromkeys(("c", "d", "e"))

    def row(t, u):
        merged = {**u, **{k: v for k, v in t.items() if k != "k"},
                  "t.k": t["k"], "u.k": u.get("k")}
        return tuple(merged[c] for c in out)

    def match(t, u):
        if kind == "cross":
            return True
        return (t["k"] is not None and t["k"] == u["k"]
                and (on is None or on(t, u)))

    rows, hit_u = [], set()
    for t in ts:
        found = [j for j, u in enumerate(us) if match(t, u)]
        hit_u.update(found)
        if kind == "semi":
            rows += [row(t, null_u)] if found else []
        elif kind == "anti":
            rows += [] if found else [row(t, null_u)]
        else:
            rows += [row(t, us[j]) for j in found]
            if not found and kind in ("left", "full"):
                rows.append(row(t, {**null_u, "k": None}))
    if kind == "full":
        rows += [row(null_t, u) for j, u in enumerate(us) if j not in hit_u]
    return rows


JOIN_SQL = {
    "inner": "from t join u on t.k = u.k",
    "left": "from t left join u on t.k = u.k",
    "full": "from t full join u on t.k = u.k",
    "semi": "from t where exists (select 1 from u where u.k = t.k)",
    "anti": "from t where not exists (select 1 from u where u.k = t.k)",
}
#: (select list, the reference's columns): a parent that reads one column
#: a side, one that reads the build side alone, one that reads everything
PARENTS = {
    "pruned": ("t.a, u.d", ("a", "d")),
    "build_only": ("u.c", ("c",)),
    "unpruned": ("*", ("id", "t.k", "a", "b", "u.k", "c", "d", "e")),
}


@pytest.mark.parametrize("path", ["fused", "eager"])
@pytest.mark.parametrize("parent", list(PARENTS))
@pytest.mark.parametrize("kind", list(JOIN_SQL))
def test_every_join_kind_equals_a_nested_loop(small, monkeypatch, kind,
                                              parent, path):
    """Left and full joins NULL-extend only the columns that are kept; a
    build with duplicates (u.k) expands its lanes and compacts fewer
    columns; semi and anti hand up the probe side."""
    s, t_rows, u_rows = small
    if path == "fused":
        monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    else:
        monkeypatch.delenv("MO_FUSION_MIN_ROWS", raising=False)
    select, out = PARENTS[parent]
    if kind in ("semi", "anti"):
        if parent == "build_only":
            pytest.skip("a semi/anti join has no build column to read")
        select = {"pruned": "t.a", "unpruned": "*"}[parent]
        out = {"pruned": ("a",), "unpruned": ("id", "t.k", "a", "b")}[parent]
    sql = f"select {select} {JOIN_SQL[kind]}"
    join = _joins(_plan(s, sql))[0][0]
    assert join.kind == kind
    if parent == "unpruned":
        assert len(join.schema) == (4 if kind in ("semi", "anti") else 8)
    elif kind not in ("semi", "anti"):
        assert len(join.schema) == len(out)
    want = _nested_loop(t_rows, u_rows, kind, out)
    got = s.execute(sql).rows()
    assert sorted(got, key=repr) == sorted(want, key=repr)


def _check_ssb(session, star, template, rng_seed):
    params = ssb.draw_world(random.Random(rng_seed))
    got = [tuple(r) for r in session.execute(
        ssb.render(template, params)).rows()]
    want = ssb.answer(star, template, params)
    if template.startswith("q3"):         # ORDER BY d_year, revenue desc
        assert sorted(got) == sorted(want)
    else:
        assert got == want


def _check_q3(q3):
    s, want = q3
    got = s.execute(tpch.Q3_SQL).rows()
    epoch = datetime.date(1970, 1, 1)
    assert [(g[0], round(g[1] * 10000), (g[2] - epoch).days)
            for g in got] == [tuple(w) for w in want]


def _path(monkeypatch, s, path, budget=1000):
    """Steer a statement's joins down one path of the ladder."""
    monkeypatch.delenv("MO_FUSION_MIN_ROWS", raising=False)
    monkeypatch.delenv("MO_FUSION_JOIN", raising=False)
    s.execute("set join_build_budget = %d" % (1 << 22))
    if path == "fused":
        monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    elif path == "unfused":               # the plain JoinOp chain
        monkeypatch.setenv("MO_FUSION_JOIN", "0")
    elif path == "spill":                 # fallback -> JoinOp -> grace
        monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
        s.execute("set join_build_budget = %d" % budget)
    else:
        assert path == "eager"            # tiny batches: handed-off build


#: the grace spill partitions both sides to host disk and joins sixteen
#: partitions: seconds a level at test size, so one template takes it
#: (its unfiltered `dates` passes the budget; Q3 below takes it too)
TEMPLATE_PATHS = [(t, p) for t in TEMPLATES
                  for p in ("fused", "eager", "unfused")] \
    + [("q2.1", "spill")]


@pytest.mark.parametrize("template,path", TEMPLATE_PATHS)
def test_template_equals_the_reference_on_every_path(
        session, star, monkeypatch, template, path):
    _path(monkeypatch, session, path)
    before = _modes()
    spills = _counter("mo_join_spill_total")
    try:
        _check_ssb(session, star, template, TEMPLATES.index(template) + 100)
    finally:
        session.execute("set join_build_budget = %d" % (1 << 22))
    moved = {m: v - before[m] for m, v in _modes().items() if v != before[m]}
    joins = {"q1": 1, "q2": 3, "q3": 3, "q4": 4}[template[:2]]
    if path == "fused":
        assert moved == {"fused": joins}
    elif path == "eager":
        assert moved == {"eager": joins}
    elif path == "unfused":
        # (a fragment above the plain JoinOp may still run, eagerly)
        assert not set(moved) & {"fused", "fallback", "degraded"}
    else:
        assert moved == {"fallback": 1, "fused": joins - 1}
        assert _counter("mo_join_spill_total") - spills == 1


@pytest.mark.parametrize("path", ["fused", "eager", "unfused", "spill"])
def test_tpch_q3_equals_its_oracle_on_every_path(q3, monkeypatch, path):
    _path(monkeypatch, q3[0], path, budget=100)
    spills = _counter("mo_join_spill_total")
    try:
        _check_q3(q3)
    finally:
        q3[0].execute("set join_build_budget = %d" % (1 << 22))
    assert (_counter("mo_join_spill_total") > spills) == (path == "spill")


# --------------------------------------------------- program key, counter

def test_two_readers_of_one_join_do_not_share_a_probe_program(
        session, star, monkeypatch):
    """The same joins over the same scans, read differently above: the
    scans carry the same columns (each statement filters on the column
    the other groups by), so only the join's output list tells the two
    probe programs apart."""
    from matrixone_tpu.vm import fusion as FF
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    a = ("select d_year, sum(lo_revenue) from lineorder, dates"
         " where lo_orderdate = d_datekey and d_yearmonthnum >= 199401"
         " group by d_year order by d_year")
    b = ("select d_yearmonthnum, sum(lo_revenue) from lineorder, dates"
         " where lo_orderdate = d_datekey and d_year >= 1994"
         " group by d_yearmonthnum order by d_yearmonthnum")
    ja, jb = (_joins(_plan(session, q))[0][0] for q in (a, b))
    assert _names(ja.left.schema) == _names(jb.left.schema)
    assert sorted(_names(ja.right.schema)) == sorted(_names(jb.right.schema))
    assert _names(ja.schema) != _names(jb.schema)
    d, lo = star.t["dates"], star.t["lineorder"]
    year = dict(zip(d["d_datekey"].tolist(), d["d_year"].tolist()))
    ym = dict(zip(d["d_datekey"].tolist(), d["d_yearmonthnum"].tolist()))
    want_a, want_b = {}, {}
    for date, rev in zip(lo["lo_orderdate"].tolist(),
                         lo["lo_revenue"].tolist()):
        if ym[date] >= 199401:
            want_a[year[date]] = want_a.get(year[date], 0) + rev
        if year[date] >= 1994:
            want_b[ym[date]] = want_b.get(ym[date], 0) + rev
    FF.CACHE.clear()
    with keyaudit.armed_scope(), keyaudit.capture() as cap:
        for _ in range(2):
            got_a = session.execute(a).rows()
            got_b = session.execute(b).rows()
    assert cap.findings() == []
    assert [tuple(r) for r in got_a] == sorted(want_a.items())
    assert [tuple(r) for r in got_b] == sorted(want_b.items())


@pytest.mark.parametrize("path", ["fused", "eager", "unfused", "spill"])
def test_join_build_columns_counter_moves_by_what_q21_implies(
        session, monkeypatch, path):
    """q2.1: part brings p_partkey, p_category, p_brand1 and the group key
    p_brand1 is gathered; supplier brings s_suppkey, s_region and nothing
    is; dates brings d_datekey, d_year and d_year is: 2 gathered, 5
    pruned, once a join on every path (a build handed over by the fused
    fragment is not counted again, nor are the grace spill's sixteen
    partition joins of one join)."""
    _path(monkeypatch, session, path)
    before = {o: _counter("mo_join_build_columns_total", outcome=o)
              for o in ("gathered", "pruned")}
    try:
        session.execute(ssb.render("q2.1", ssb.PAPER_PARAMS["q2.1"])).rows()
    finally:
        session.execute("set join_build_budget = %d" % (1 << 22))
    moved = {o: _counter("mo_join_build_columns_total", outcome=o) - v
             for o, v in before.items()}
    assert moved == {"gathered": 2, "pruned": 5}


def test_the_benchmark_reads_the_counter_as_a_share(session, monkeypatch):
    """`join_columns_pruned_share.sql` is a data file over the reader that
    `scan_columns_pruned_share.sql` uses: `pruned` over both outcomes of
    the flat keys `benchmark/run.counters` makes, and nothing from a
    program that has no such counter (the parent)."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(root, "benchmark"))
    from readers import counter_share
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    name = "join_columns_pruned_share.sql"
    entry = [m for m in manifest["per_layer"] if m["name"] == name]
    assert entry == [{
        "name": name, "unit": "ratio", "better": "higher",
        "source": "program_counter",
        "layer": next(m["layer"] for m in manifest["per_layer"]
                      if m["name"] == "join_build_ms.sql"),
        "moves": "sql_rows_per_s",
        "workloads": ["ssb-sf1.star-join", "tpch-sf1.join-q3"]}]
    with open(os.path.join(root, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_share"

    def flat():
        out = {}
        for metric, snap in M.REGISTRY.snapshot().items():
            for v in snap.get("values", []):
                labels = ",".join(f'{k}="{val}"' for k, val in
                                  sorted(v["labels"].items()))
                out[f"{metric}{{{labels}}}" if labels else metric] = \
                    v["value"]
        return out

    before = flat()
    for template in ("q2.1", "q4.1"):    # 2 of 7 and 2 of 9 gathered
        session.execute(
            ssb.render(template, ssb.PAPER_PARAMS[template])).rows()
    ctx = {"before": before, "after": flat()}
    assert counter_share.read(ctx, **spec["args"]) == 12 / 16
    parent = {side: {k: v for k, v in ctx[side].items()
                     if not k.startswith("mo_join_build_columns_total")}
              for side in ctx}
    assert counter_share.read(parent, **spec["args"]) is None
