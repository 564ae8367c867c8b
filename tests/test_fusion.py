"""Whole-plan XLA fusion (vm/fusion.py): fused vs unfused lockstep
bit-identicality, compile-cache single-trace + dispatch-bound guards,
fragment invalidation, and fusion-barrier splits."""

import os

import numpy as np
import pytest

from matrixone_tpu.frontend import Session
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.vm import fusion
from matrixone_tpu.vm.compile import compile_plan, iter_ops
from matrixone_tpu.vm.fusion import FusedFragmentOp


@pytest.fixture()
def env():
    """Snapshot/restore the fusion env knobs around every test."""
    keys = ("MO_PLAN_FUSION", "MO_FUSION_MIN_ROWS")
    saved = {k: os.environ.get(k) for k in keys}
    yield os.environ
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


@pytest.fixture()
def sess(env):
    env["MO_FUSION_MIN_ROWS"] = "0"      # force the traced path
    s = Session()
    s.execute("create table t (g varchar(4), v bigint, d double, "
              "dt date, q decimal(15,2))")
    rows = []
    rng = np.random.default_rng(7)
    gs = ["aa", "bb", "cc", None]
    for i in range(200):
        g = gs[int(rng.integers(0, 4))]
        gtxt = "null" if g is None else f"'{g}'"
        v = "null" if i % 11 == 0 else str(int(rng.integers(-5, 50)))
        d = f"{float(rng.random() * 10):.4f}"
        day = 1 + int(rng.integers(0, 27))
        q = f"{float(rng.random() * 100):.2f}"
        rows.append(f"({gtxt}, {v}, {d}, '1995-03-{day:02d}', {q})")
    s.execute("insert into t values " + ",".join(rows))
    return s


def _lockstep(s, sql, params=None):
    os.environ["MO_PLAN_FUSION"] = "0"
    r0 = s.execute(sql, params).rows()
    os.environ["MO_PLAN_FUSION"] = "1"
    r1 = s.execute(sql, params).rows()
    assert r0 == r1, f"fused differs for {sql!r}:\n{r0}\nvs\n{r1}"
    return r1


BREADTH = [
    # the Q1 shape: pushed date filter, dense dict-key group-by,
    # decimal-exact sums, averages, count(*)
    "select g, count(*) c, sum(q) sq, avg(q) aq, sum(v) sv, avg(d) ad"
    " from t where dt <= date '1995-03-20' group by g order by g",
    # scalar aggregates incl. min/max/stddev over a filter
    "select count(*), sum(v), avg(d), min(d), max(v), stddev_samp(d),"
    " var_pop(d) from t where v > 3",
    # projection arithmetic + CASE + IS NULL
    "select v + 1 a, d * 2 - 1 b, case when v > 10 then d else -d end c,"
    " v is null nn from t where d > 1.5 order by v, d",
    # string predicates (dict LUTs baked per content)
    "select v from t where g like 'a%' and v is not null order by v",
    "select v, g from t where g in ('aa', 'cc') order by v, g",
    "select v from t where g >= 'bb' order by v",
    # string CASE group key + bool group key
    "select case when v > 10 then 'hi' else 'lo' end k, count(*) n,"
    " sum(q) sq from t group by k order by k",
    "select d > 5 k, count(*) n from t group by k order by k",
    # limit / offset streams through the fused chain
    "select v from t where d > 1 order by v, d limit 7",
    "select v from t where d > 1 order by v, d limit 5 offset 3",
    # distinct / topk tails consuming a fused stream
    "select distinct g from t where v > 0 order by g",
    "select v, d from t where v is not null order by d limit 4",
    # date function family
    "select year(dt) y, month(dt) m, count(*) n from t"
    " group by y, m order by y, m",
    # empty result + all-NULL group behavior
    "select g, sum(v) s from t where d > 99 group by g order by g",
]


def test_fused_lockstep_breadth(sess):
    for sql in BREADTH:
        _lockstep(sess, sql)


def test_fused_lockstep_eager_threshold(sess, env):
    """Below MO_FUSION_MIN_ROWS the fragment runs the ORIGINAL chain
    (eager mode) — results identical there too."""
    env["MO_FUSION_MIN_ROWS"] = "1000000000"
    for sql in BREADTH[:4]:
        _lockstep(sess, sql)
    assert M.fusion_exec.get(mode="eager") > 0


def _plan_of(sess, sql):
    from matrixone_tpu.sql.binder import Binder
    from matrixone_tpu.sql.parser import parse
    sel = parse(sql)[0]
    sess._prepare_select(sel)
    node = Binder(sess.catalog).bind_statement(sel)
    node = sess._cbo(node)
    return compile_plan(node, sess._ctx())


def test_join_fuses_into_probe_fragment(sess):
    """A fusable equi-join is no longer a barrier: it becomes a
    build/probe fragment (FusedJoinProbeOp) fused WITH the chain above
    it, and `MO_FUSION_JOIN=0` restores the barrier bit-identically."""
    from matrixone_tpu.vm.fusion_join import FusedJoinProbeOp
    from matrixone_tpu.vm.join import JoinOp
    sess.execute("create table dim (k bigint, label varchar(8))")
    sess.execute("insert into dim values (1,'one'),(2,'two'),(3,'three')"
                 ",(4,'four'),(5,'five')")
    sql = ("select dim.label, sum(t.v) s, count(*) n from t"
           " join dim on t.v = dim.k where t.d > 0.5 and dim.k > 1"
           " group by dim.label order by dim.label")
    r = _lockstep(sess, sql)
    os.environ["MO_PLAN_FUSION"] = "1"
    op = _plan_of(sess, sql)
    frags = [o for o in iter_ops(op)
             if isinstance(o, FusedJoinProbeOp)]
    assert frags, "the equi-join must fuse into a probe fragment"
    assert frags[0]._agg_op is not None, \
        "the grouped aggregate above the join must ride the fragment"
    assert "join=build+probe" in frags[0].node_roles.values()
    # the ORIGINAL JoinOp survives inside the fragment as the
    # degradation ladder, its children pointed at the fused sources
    assert isinstance(frags[0]._join, JoinOp)
    # MO_FUSION_JOIN=0: the join is a barrier again, same rows
    os.environ["MO_FUSION_JOIN"] = "0"
    try:
        op = _plan_of(sess, sql)
        assert not [o for o in iter_ops(op)
                    if isinstance(o, FusedJoinProbeOp)]
        assert "JoinOp" in [type(o).__name__ for o in iter_ops(op)]
        assert sess.execute(sql).rows() == r
    finally:
        os.environ.pop("MO_FUSION_JOIN", None)


def test_barrier_udf_row_loop_splits_chain(sess):
    """A row-loop UDF mid-pipeline is a barrier: the projection holding
    it stays per-operator, surrounding stages still run, results match."""
    sess.execute(
        "create function rowy(x BIGINT) returns BIGINT language python"
        " properties ('vectorized' = 'false') as $$ x * 2 + 1 $$")
    sql = ("select count(*) n, sum(w) s from "
           "(select rowy(v) w, d from t where v > 5) q where d > 1.0")
    _lockstep(sess, sql)
    os.environ["MO_PLAN_FUSION"] = "1"
    from matrixone_tpu.sql.binder import Binder
    from matrixone_tpu.sql.parser import parse
    sel = parse(sql)[0]
    sess._prepare_select(sel)
    node = Binder(sess.catalog).bind_statement(sel)
    op = compile_plan(node, sess._ctx())
    kinds = [type(o).__name__ for o in iter_ops(op)]
    assert "ProjectOp" in kinds     # the UDF projection did not fuse
    assert any(isinstance(o, FusedFragmentOp) for o in iter_ops(op))


def test_single_trace_guard(sess):
    """Second execution of an identical plan shape performs ZERO
    re-traces (mirrors the kmeans jit-cache-miss guard)."""
    sql = BREADTH[0]
    os.environ["MO_PLAN_FUSION"] = "1"
    sess.execute(sql)                       # trace + compile
    m0 = M.fusion_compile.get(outcome="miss")
    t0 = M.fusion_trace_seconds.get()
    sess.execute(sql)
    assert M.fusion_compile.get(outcome="miss") == m0
    assert M.fusion_trace_seconds.get() == t0
    assert M.fusion_compile.get(outcome="hit") > 0


def test_param_values_share_one_program(sess):
    """Lifted literals: distinct parameter values of the same plan shape
    reuse ONE compiled program (no per-value retrace)."""
    os.environ["MO_PLAN_FUSION"] = "1"
    q = "select sum(v) s, count(*) c from t where v > ? and d > ?"
    r_direct = {}
    for hi in (1, 5, 9):
        r_direct[hi] = sess.execute(
            f"select sum(v) s, count(*) c from t where v > {hi} "
            f"and d > 0.5").rows()
    sess.execute(q, [1, 0.5])               # traces once
    m0 = M.fusion_compile.get(outcome="miss")
    for hi in (1, 5, 9, 5, 1):
        rows = sess.execute(q, [hi, 0.5]).rows()
        assert rows == r_direct[hi]
    assert M.fusion_compile.get(outcome="miss") == m0, \
        "distinct parameter values must not retrace"


def test_grouped_agg_untraceable_arg_is_barrier(sess):
    """A host-LUT aggregate argument (string function) must bar the
    fused grouped terminal: if it traced, the dictionary behind the
    LUT would be missing from the compile key and a grown dictionary
    would be served a stale program (review-round regression)."""
    os.environ["MO_PLAN_FUSION"] = "1"
    sess.execute("create table sl (k varchar(2), s varchar(16))")
    sess.execute("insert into sl values ('a','xy'),('a','pqr'),"
                 "('b','z')")
    q = "select k, sum(length(s)) n from sl group by k order by k"
    assert sess.execute(q).rows() == [("a", 5), ("b", 1)]
    # grow the dictionary behind the LUT; the same shape must recompute
    sess.execute("insert into sl values ('b','longerstring')")
    assert sess.execute(q).rows() == [("a", 5), ("b", 13)]
    os.environ["MO_PLAN_FUSION"] = "0"
    assert sess.execute(q).rows() == [("a", 5), ("b", 13)]
    # and the planner kept the aggregate on the per-operator path
    from matrixone_tpu.sql.binder import Binder
    from matrixone_tpu.sql.parser import parse
    os.environ["MO_PLAN_FUSION"] = "1"
    sel = parse(q)[0]
    sess._prepare_select(sel)
    node = Binder(sess.catalog).bind_statement(sel)
    op = compile_plan(node, sess._ctx())
    assert any(type(o).__name__ == "AggOp" for o in iter_ops(op))


def test_dict_growth_invalidates_lut(sess):
    """The dictionary-content key: new strings entering a scanned
    dictionary must re-trace the baked LIKE/compare LUT, never serve a
    stale one."""
    os.environ["MO_PLAN_FUSION"] = "1"
    sql = "select count(*) from t where g like 'z%'"
    assert sess.execute(sql).rows() == [(0,)]
    sess.execute("insert into t values ('zz', 1, 1.0, '1995-03-01', 1.0)")
    assert sess.execute(sql).rows() == [(1,)]
    os.environ["MO_PLAN_FUSION"] = "0"
    assert sess.execute(sql).rows() == [(1,)]


def test_ddl_recreate_invalidation(sess):
    """DROP + recreate with a different column type re-keys the
    fragment (dtype signature) and the plan-cache tree (ddl_gen)."""
    os.environ["MO_PLAN_FUSION"] = "1"
    sess.execute("create table inv (a bigint, b bigint)")
    sess.execute("insert into inv values (1, 10), (2, 20)")
    q = "select sum(b) s from inv where a > 0"
    assert sess.execute(q).rows() == [(30,)]
    m0 = M.fusion_compile.get(outcome="miss")
    sess.execute("drop table inv")
    sess.execute("create table inv (a bigint, b double)")
    sess.execute("insert into inv values (1, 1.5), (2, 2.25)")
    assert sess.execute(q).rows() == [(3.75,)]
    assert M.fusion_compile.get(outcome="miss") > m0, \
        "a changed dtype signature must trace a fresh program"


def test_plan_cache_tree_reuse_and_invalidation(sess):
    """The compiled operator tree rides the plan-cache entry (pop
    discipline) and dies with it on DDL/ANALYZE."""
    from matrixone_tpu.serving import serving_for
    os.environ["MO_PLAN_FUSION"] = "1"
    sv = serving_for(sess.catalog)
    plan_was = sv.plan_cache.enabled
    sv.plan_cache.enabled = True
    try:
        q = "select sum(v) s from t where v > ?"
        for k in (1, 2, 3):
            sess.execute(q, [k])            # activate + store template
        h0 = M.plan_cache_ops.get(outcome="tree_hit")
        want = sess.execute(q, [2]).rows()
        assert M.plan_cache_ops.get(outcome="tree_hit") > h0
        # ANALYZE bumps stats_gen: the tree must not be served stale
        sess.execute("analyze table t")
        h1 = M.plan_cache_ops.get(outcome="tree_hit")
        assert sess.execute(q, [2]).rows() == want
        assert M.plan_cache_ops.get(outcome="tree_hit") == h1
        # and the rebuilt tree is re-cached afterwards
        sess.execute(q, [2])
        assert sess.execute(q, [2]).rows() == want
        assert M.plan_cache_ops.get(outcome="tree_hit") > h1
    finally:
        sv.plan_cache.enabled = plan_was


def test_union_dict_growth_degrades_not_corrupts(sess, env):
    """A group-key dictionary growing mid-stream (union arms with
    different string sets) degrades the fused aggregate to the general
    path with the partials folded in — results stay exact."""
    sess.execute("create table u1 (g varchar(4), v bigint)")
    sess.execute("create table u2 (g varchar(4), v bigint)")
    sess.execute("insert into u1 values ('aa',1),('bb',2),('aa',3)")
    sess.execute("insert into u2 values ('cc',10),('dd',20),('aa',30)")
    sql = ("select g, sum(v) s, count(*) n from "
           "(select g, v from u1 union all select g, v from u2) q "
           "group by g order by g")
    _lockstep(sess, sql)


def test_multi_batch_carry_and_limit(env):
    """Multiple scan chunks through one fragment: the aggregate carry
    folds across batches (including the differently-bucketed tail
    chunk), and a fused LIMIT stops pulling once satisfied."""
    env["MO_FUSION_MIN_ROWS"] = "0"
    s = Session()
    s.execute("create table mb (g varchar(2), v bigint, d double)")
    rng = np.random.default_rng(3)
    n = 5000
    vals = ",".join(
        f"('{'ab'[int(rng.integers(0, 2))]}', {int(rng.integers(0, 99))},"
        f" {float(rng.random()):.5f})" for _ in range(n))
    s.execute("insert into mb values " + vals)
    s.execute("set batch_rows = 1024")        # 5 chunks per scan
    for sql in (
            "select g, count(*) c, sum(v) sv, avg(d) ad from mb"
            " where d > 0.25 group by g order by g",
            "select sum(v) s, min(d) mn, max(d) mx from mb where v > 10",
            "select v from mb where d > 0.5 order by v, d limit 9",
            "select v from mb limit 3 offset 2"):
        os.environ["MO_PLAN_FUSION"] = "0"
        r0 = s.execute(sql).rows()
        os.environ["MO_PLAN_FUSION"] = "1"
        r1 = s.execute(sql).rows()
        assert r0 == r1, sql


def test_q1_dispatch_bound_and_oracle():
    """Warm fused Q1: <= 2 device dispatches per fragment per batch
    (asserted via mo_fusion_dispatch_total), zero re-traces on the
    second execution, exact vs the pandas oracle."""
    from matrixone_tpu.utils import tpch
    saved = {k: os.environ.get(k)
             for k in ("MO_PLAN_FUSION", "MO_FUSION_MIN_ROWS")}
    os.environ["MO_PLAN_FUSION"] = "1"
    os.environ.pop("MO_FUSION_MIN_ROWS", None)   # production threshold
    try:
        s = Session()
        n = 120_000
        arrays = tpch.load_lineitem(s.catalog, n)
        oracle = tpch.q1_oracle(arrays)
        rows = s.execute(tpch.Q1_SQL).rows()     # cold: trace+compile
        assert tpch.q1_check(rows, oracle)
        d0 = M.fusion_dispatch.get(kind="step")
        m0 = M.fusion_compile.get(outcome="miss")
        t0 = M.fusion_trace_seconds.get()
        rows2 = s.execute(tpch.Q1_SQL).rows()
        assert tpch.q1_check(rows2, oracle)
        n_batches = 1                            # 120k rows, one chunk
        n_frags = 1                              # scan>agg fragment
        dispatches = M.fusion_dispatch.get(kind="step") - d0
        assert 0 < dispatches <= 2 * n_batches * n_frags, dispatches
        assert M.fusion_compile.get(outcome="miss") == m0, \
            "warm Q1 re-traced"
        assert M.fusion_trace_seconds.get() == t0
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_explain_marks_fragments(sess):
    os.environ["MO_PLAN_FUSION"] = "1"
    txt = sess.execute(
        "explain select g, count(*) from t where v > 1 group by g").text
    assert "fragment=f" in txt
    txt = sess.execute(
        "explain analyze select g, count(*) c from t where v > 1 "
        "group by g").text
    assert "fragment f" in txt and "dispatches=" in txt \
        and "trace_ms=" in txt and "compile_cache=" in txt
    # the fused chain names its covered operators on the fragment line
    assert "AggOp" in txt
    os.environ["MO_PLAN_FUSION"] = "0"
    txt = sess.execute(
        "explain select g, count(*) from t where v > 1 group by g").text
    assert "fragment=" not in txt


def test_mo_ctl_fusion_surface(sess):
    import json
    os.environ["MO_PLAN_FUSION"] = "1"
    sess.execute(BREADTH[0])
    st = json.loads(
        sess.execute("select mo_ctl('fusion','status')").rows()[0][0])
    assert st["compile_cache"]["entries"] > 0
    assert st["executions"]["fused"] > 0
    out = sess.execute("select mo_ctl('fusion','clear')").rows()[0][0]
    assert "cleared" in out
    st = json.loads(
        sess.execute("select mo_ctl('fusion','status')").rows()[0][0])
    assert st["compile_cache"]["entries"] == 0


def _bvt_lockstep(env, dirs, cap=None):
    """MO_PLAN_FUSION=0/1 lockstep over real bvt case shapes: the
    goldens were recorded on the per-operator path, so matching them
    byte-for-byte with fusion FORCED onto every batch size is the
    bit-identicality proof for those shapes."""
    from matrixone_tpu.utils import bvt
    env["MO_PLAN_FUSION"] = "1"
    env["MO_FUSION_MIN_ROWS"] = "0"
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "bvt", "cases")
    cases = [c for c in bvt.iter_cases(root)
             if os.path.basename(os.path.dirname(c)) in dirs]
    if cap is not None:
        # deterministic spread across the dirs, bounded for tier-1
        cases = cases[::max(1, len(cases) // cap)][:cap]
    assert len(cases) >= 10
    for case in cases:
        with open(case) as f:
            text = f.read()
        with open(case[:-4] + ".result") as f:
            golden = f.read()
        s = Session()
        try:
            got = bvt.run_case(s, text)
        finally:
            s.close()
        assert got == golden, f"fusion lockstep mismatch for {case}"


def test_bvt_shapes_lockstep(env):
    """Tier-1 slice: explain goldens (annotation-bearing), joins, and a
    spread of query/tpch_mini shapes under forced fusion."""
    _bvt_lockstep(env, ("explain", "join", "tpch_mini"), cap=18)


@pytest.mark.slow
def test_bvt_shapes_lockstep_full(env):
    """The full bvt lockstep sweep (slow tier): every query / join /
    tpch_mini / explain / joins case byte-identical under forced
    fusion."""
    _bvt_lockstep(env, ("query", "join", "joins", "tpch_mini",
                        "explain"))


def test_session_variable_disables_fusion(sess):
    sess.execute("set plan_fusion = 0")
    from matrixone_tpu.sql.binder import Binder
    from matrixone_tpu.sql.parser import parse
    sel = parse("select v from t where v > 1")[0]
    sess._prepare_select(sel)
    node = Binder(sess.catalog).bind_statement(sel)
    op = compile_plan(node, sess._ctx())
    assert not any(isinstance(o, FusedFragmentOp) for o in iter_ops(op))
    sess.execute("set plan_fusion = 1")
