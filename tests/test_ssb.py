"""The Star Schema Benchmark deployment (`utils/ssb.py`): the seeded
generator keeps the paper's sizes, domains, hierarchies and foreign keys;
each of the thirteen queries runs on the normal path (plan -> fused join
fragments), `lineorder` probing every join, equal to the plain numpy
reference cell for cell; fresh constants compile nothing; the join's spans
and counters say what a known statement implies."""

import random

import jax
import numpy as np
import pytest

from matrixone_tpu.frontend.session import Session
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.utils import motrace, ssb
from matrixone_tpu.vm import join as J

TEMPLATES = list(ssb.TEMPLATES)
#: a fact table of about 60,000 rows over dimensions large enough for
#: every filter of every draw to find rows
SIZES = {"customer": 3000, "supplier": 2000, "part": 4000}


@pytest.fixture(scope="module")
def tables():
    return ssb.gen_ssb(0.01, 7, sizes=SIZES)


@pytest.fixture(scope="module")
def star(tables):
    return ssb.Star(tables)


@pytest.fixture(scope="module")
def session(tables):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MO_FUSION_MIN_ROWS", "0")
        s = Session()
        ssb.load_ssb(s.catalog, tables, commits=2)
        yield s
        s.close()


def _counter(name, **labels):
    for v in M.REGISTRY.snapshot().get(name, {}).get("values", []):
        if v["labels"] == labels:
            return v["value"]
    return 0


def _modes():
    return {m: _counter("mo_fusion_exec_total", mode=m)
            for m in ("fused", "fallback", "degraded", "eager")}


def _rows(session, sql):
    return [tuple(r) for r in session.execute(sql).rows()]


# ------------------------------------------------------------- generator

def test_generator_is_deterministic_in_the_seed():
    a, b, c = (ssb.gen_ssb(0.002, seed) for seed in (3, 3, 4))
    for table, cols in a.items():
        for name, col in cols.items():
            same = (col.codes if isinstance(col, ssb.Coded) else col)
            again = b[table][name]
            again = again.codes if isinstance(again, ssb.Coded) else again
            assert np.array_equal(same, again), (table, name)
    assert not np.array_equal(a["lineorder"]["lo_partkey"][:1000],
                              c["lineorder"]["lo_partkey"][:1000])


@pytest.mark.parametrize("n_orders", [1, 6, 7, 12, 15_000])
def test_every_seed_yields_one_lineorder_row_count(n_orders):
    """Four lines an order in all, 1 to 7 each, whatever the seed: the
    programs keyed by a segment's length are the same in every run."""
    per_order = ssb._lines_per_order(n_orders)
    assert len(per_order) == n_orders and per_order.sum() == 4 * n_orders
    assert per_order.min() >= 1 and per_order.max() <= 7
    a, b = (ssb.gen_ssb(1.0, seed, sizes={"orders": n_orders, "part": 40,
                                          "customer": 10, "supplier": 10})
            ["lineorder"]["lo_orderkey"] for seed in (1, 2))
    assert len(a) == len(b) == 4 * n_orders
    if n_orders > 7:
        assert not np.array_equal(a, b)     # the seed permutes the counts


def test_generator_keeps_the_papers_sizes_and_columns(tables):
    assert {t: len(s) for t, s in ssb.SCHEMAS.items()} == {
        "dates": 17, "customer": 8, "supplier": 7, "part": 9,
        "lineorder": 17}
    for table, schema in ssb.SCHEMAS.items():
        assert list(tables[table]) == [c for c, _ in schema]
    assert ssb.table_sizes(1.0) == {"orders": 1_500_000, "customer": 30_000,
                                    "supplier": 2_000, "part": 200_000}
    assert ssb.table_sizes(10.0)["part"] == 800_000     # 200,000 x 4
    d = tables["dates"]
    assert len(d["d_datekey"]) == 2557                  # 1992 .. 1998
    assert d["d_datekey"][0] == 19920101 and d["d_datekey"][-1] == 19981231
    assert d["d_date"].cats[59] == "February 29, 1992"  # a real calendar
    lo = tables["lineorder"]
    lines = np.bincount(lo["lo_orderkey"])[1:]
    assert lines.min() >= 1 and lines.max() <= 7
    assert np.array_equal(np.unique(lo["lo_linenumber"]), np.arange(1, 8))


def test_generator_keeps_the_papers_domains_and_hierarchies(tables):
    assert (len(ssb.REGIONS), len(ssb.NATION_NAMES), len(ssb.CITIES),
            len(ssb.MFGRS), len(ssb.CATEGORIES), len(ssb.BRANDS)) == (
        5, 25, 250, 5, 25, 1000)
    assert "UNITED KI1" in ssb.CITIES and "MFGR#2239" in ssb.BRANDS
    for t, p in (("customer", "c"), ("supplier", "s")):
        city, nation, region = (tables[t][f"{p}_{k}"].values()
                                for k in ("city", "nation", "region"))
        assert all(c[:9].rstrip() == n[:9].rstrip()
                   for c, n in zip(city, nation))
        of = dict(zip(ssb.NATION_NAMES, ssb.NATION_REGION))
        assert all(of[n] == r for n, r in zip(nation, region))
    part = tables["part"]
    mfgr, cat, brand = (part[k].values()
                        for k in ("p_mfgr", "p_category", "p_brand1"))
    assert all(b.startswith(c) and c.startswith(m)
               for m, c, b in zip(mfgr, cat, brand))
    lo = tables["lineorder"]
    for col, low, high in (("lo_quantity", 1, 50), ("lo_discount", 0, 10),
                           ("lo_tax", 0, 8)):
        assert lo[col].min() == low and lo[col].max() == high


def test_every_foreign_key_finds_its_dimension_row(tables):
    lo = tables["lineorder"]
    for fk, table, key in (("lo_custkey", "customer", "c_custkey"),
                           ("lo_suppkey", "supplier", "s_suppkey"),
                           ("lo_partkey", "part", "p_partkey"),
                           ("lo_orderdate", "dates", "d_datekey"),
                           ("lo_commitdate", "dates", "d_datekey")):
        assert np.isin(lo[fk], tables[table][key]).all(), fk
    for table, key in (("customer", "c_custkey"), ("supplier", "s_suppkey"),
                       ("part", "p_partkey")):
        k = tables[table][key]
        assert np.array_equal(k, np.arange(1, len(k) + 1))


def test_revenue_identity_and_order_totals(tables):
    lo = tables["lineorder"]
    assert np.array_equal(
        lo["lo_revenue"],
        lo["lo_extendedprice"] * (100 - lo["lo_discount"]) // 100)
    price = lo["lo_extendedprice"] // lo["lo_quantity"]
    assert np.array_equal(lo["lo_supplycost"], 6 * price // 10)
    first = np.flatnonzero(lo["lo_linenumber"] == 1)
    charged = (lo["lo_revenue"] * (100 + lo["lo_tax"]) // 100)
    assert np.array_equal(np.add.reduceat(charged, first),
                          lo["lo_ordtotalprice"][first])


def test_draws_keep_each_querys_filter_factor():
    rng = random.Random(11)
    for _ in range(200):
        w = ssb.draw_world(rng)
        assert w["discount_hi"] - w["discount_lo"] == 2
        assert w["quantity_hi"] - w["quantity_lo"] == 9
        brands = [b for b in ssb.BRANDS
                  if w["brand_lo"] <= b <= w["brand_hi"]]
        assert len(brands) == 8 and len({b[:7] for b in brands}) == 1
        assert w["city_a"] != w["city_b"] \
            and w["city_a"][:9] == w["city_b"][:9]
        assert w["mfgr_a"] != w["mfgr_b"] and w["year_b"] == w["year_a"] + 1
        assert dict(zip(ssb.NATION_NAMES, ssb.NATION_REGION))[
            w["nation_x"]] == w["region_x"]
    for name, params in ssb.PAPER_PARAMS.items():
        assert "{" not in ssb.render(name, params), name


# ---------------------------------------------------- the thirteen queries

def test_ssb_q1x_exact():
    """Flight 1 with the paper's own constants (moved here from
    test_tpch.py with the generator it now uses)."""
    tables = ssb.gen_ssb(0.005, 5)
    s = Session()
    ssb.load_ssb(s.catalog, tables)
    star = ssb.Star(tables)
    for name in ("q1.1", "q1.2", "q1.3"):
        params = ssb.PAPER_PARAMS[name]
        got = s.execute(ssb.render(name, params)).rows()
        assert [tuple(r) for r in got] == ssb.answer(star, name, params)
    s.close()


@pytest.mark.parametrize("template", TEMPLATES)
def test_template_equals_the_reference_on_the_fused_path(
        session, star, template):
    rng = random.Random(TEMPLATES.index(template))
    eager0 = _counter("mo_fusion_dispatch_total", kind="eager")
    for _ in range(2):
        params = ssb.draw_world(rng)
        before = _modes()
        got = _rows(session, ssb.render(template, params))
        after = _modes()
        want = ssb.answer(star, template, params)
        if template.startswith("q3"):     # ORDER BY d_year, revenue desc
            assert sorted(got) == sorted(want)
            assert [(r[2], -r[3]) for r in got] == sorted(
                (r[2], -r[3]) for r in got)
        else:
            assert got == want
        joins = {"q1": 1, "q2": 3, "q3": 3, "q4": 4}[template[:2]]
        assert after["fused"] - before["fused"] == joins
        assert all(after[m] == before[m]
                   for m in ("fallback", "degraded", "eager"))
    assert _counter("mo_fusion_dispatch_total", kind="eager") == eager0


@pytest.mark.parametrize("template", TEMPLATES)
def test_lineorder_probes_every_join_and_every_build_is_unique(
        session, template):
    plan = session.execute(
        "explain " + ssb.render(template, ssb.PAPER_PARAMS[template])).text
    lines = [ln.strip() for ln in plan.splitlines()]
    joins = [ln for ln in lines if ln.startswith("Join")]
    assert len(joins) == {"q1": 1, "q2": 3, "q3": 3, "q4": 4}[template[:2]]
    assert all("build=unique" in j and "join=build+probe" in j
               for j in joins)
    # left-deep with the fact table at the bottom: the innermost join's
    # first (probe) child is the lineorder scan
    inner = max(i for i, ln in enumerate(lines) if ln.startswith("Join"))
    assert lines[inner + 1].startswith("Scan table=lineorder ")


@pytest.mark.parametrize("template", TEMPLATES)
def test_fresh_constants_compile_nothing(session, template):
    rng = random.Random(77)
    for _ in range(3):                   # warm: the template's own shapes
        session.execute(ssb.render(template, ssb.draw_world(rng))).rows()
    compiles = []

    def on_duration(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for _ in range(3):
            session.execute(
                ssb.render(template, ssb.draw_world(rng))).rows()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []


# ------------------------------------------------------ spans and counters

NAMES = ("mo_join_probe_rows_total", "mo_join_probe_lanes_total",
         "mo_join_probe_retries_total", "mo_join_build_rows_total",
         "mo_device_wait_total")


def _join_counters():
    out = {}
    for name in NAMES:
        for v in M.REGISTRY.snapshot().get(name, {}).get("values", []):
            label = ",".join(f"{k}={x}" for k, x in v["labels"].items())
            out[f"{name}{{{label}}}"] = v["value"]
    return out


def test_join_counters_move_by_what_a_known_statement_implies(
        session, tables):
    """q2.1: lineorder probes supplier (one region), part (one category)
    and dates (all of them): one lane a probe row, no retry,
    one wait a build for its scalars and one a join for its row counts,
    none for an overflow flag."""
    params = ssb.PAPER_PARAMS["q2.1"]
    sql = ssb.render("q2.1", params)
    session.execute(sql).rows()
    before = _join_counters()
    session.execute(sql).rows()
    moved = {k: v - before.get(k, 0) for k, v in _join_counters().items()
             if v != before.get(k, 0)}
    lo, s, p = tables["lineorder"], tables["supplier"], tables["part"]
    in_region = ssb._eq(s["s_region"], params["region"])
    in_category = ssb._eq(p["p_category"], params["category"])
    # every build pushes its key range down to the lineorder scan before
    # the first chunk is read (the runtime filters)
    supp, partk = s["s_suppkey"][in_region], p["p_partkey"][in_category]
    scanned = ((lo["lo_suppkey"] >= supp.min())
               & (lo["lo_suppkey"] <= supp.max())
               & (lo["lo_partkey"] >= partk.min())
               & (lo["lo_partkey"] <= partk.max()))
    # the joins in the order the plan took them (left-deep: the dimension
    # scans follow the lineorder scan, innermost first), dates last
    plan = session.execute("explain " + sql).text
    order = [ln.split("table=")[1].split()[0] for ln in plan.splitlines()
             if ln.strip().startswith("Scan")]
    assert order[0] == "lineorder" and sorted(order[1:]) == [
        "dates", "part", "supplier"]
    passes = {"supplier": in_region[lo["lo_suppkey"] - 1],
              "part": in_category[lo["lo_partkey"] - 1],
              "dates": np.ones(len(scanned), bool)}
    rows_in = matched = 0
    live = scanned
    for table in order[1:]:
        rows_in += int(live.sum())
        live = live & passes[table]
        matched += int(live.sum())
    assert moved.pop("mo_join_probe_rows_total{stage=in}") == rows_in
    assert moved.pop("mo_join_probe_lanes_total{}") == rows_in
    assert moved.pop("mo_join_probe_rows_total{stage=matched}") == matched
    assert moved.pop("mo_join_build_rows_total{}") == \
        int(in_region.sum()) + int(in_category.sum()) + 2557
    assert moved.pop("mo_device_wait_total{site=join_rf}") == 3
    assert moved.pop("mo_device_wait_total{site=join_stats}") == 3
    assert moved.pop("mo_device_wait_total{site=agg_slots}") == 1
    assert not any("join" in k for k in moved), moved


def test_join_spans_nest_under_run(session):
    tr = motrace.TRACER
    tr.clear()
    tr.arm(sample=1.0)
    try:
        session.execute(ssb.render("q4.1", ssb.PAPER_PARAMS["q4.1"])).rows()
    finally:
        tr.disarm()
    spans = [s for tid in tr.trace_ids() for s in tr.spans_of(tid)]
    by_sid = {s["sid"]: s for s in spans}

    def under_run(s):
        while s is not None:
            if s["name"] == "run":
                return True
            s = by_sid.get(s["psid"])
        return False

    builds = [s for s in spans if s["name"] == "join.build"]
    assert sorted(s["attrs"]["table"] for s in builds) == [
        "customer", "dates", "part", "supplier"]
    for name in ("join.build", "join.build.wait", "join.probe.dispatch",
                 "join.probe.wait"):
        found = [s for s in spans if s["name"] == name]
        assert found and all(under_run(s) for s in found), name
    threads = {s["thread"] for s in spans
               if s["name"].startswith("join.")}
    run = next(s for s in spans if s["name"] == "run")
    assert threads == {run["thread"]}    # the statement's own thread
    tr.clear()


# ------------------------------------------------------- lookups and lanes

def _orders_and_lines(session_):
    session_.execute("create table o (k bigint primary key, a bigint)")
    session_.execute("create table l (k bigint, b bigint)")
    n = 70_000
    keys = np.arange(n, dtype=np.int64) * 1_000_003       # a sparse key
    session_.catalog.get_table("o").insert_numpy(
        {"k": keys, "a": np.arange(n, dtype=np.int64)})
    rng = np.random.default_rng(3)
    pick = rng.integers(0, n, 90_000)
    session_.catalog.get_table("l").insert_numpy(
        {"k": keys[pick], "b": np.ones(90_000, np.int64)})
    return keys, pick


def test_a_unique_sparse_key_is_searched_with_one_lane(monkeypatch):
    """A declared primary key whose values span more than a direct-address
    table may hold: the sorted search, one lane a row, no overflow flag."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    s = Session()
    keys, pick = _orders_and_lines(s)
    before = _join_counters()
    got = s.execute("select sum(a), count(*) from l, o where l.k = o.k"
                    ).rows()[0]
    moved = {k: v - before.get(k, 0) for k, v in _join_counters().items()}
    assert tuple(got) == (int(pick.sum()), 90_000)
    assert keys[-1] - keys[0] > (1 << 22)
    assert moved["mo_join_probe_lanes_total{}"] == \
        moved["mo_join_probe_rows_total{stage=in}"] == 90_000
    assert moved.get("mo_device_wait_total{site=join_overflow}", 0) == 0
    s.close()


def test_a_build_with_duplicates_keeps_its_lanes_and_its_flag(monkeypatch):
    """The same join the other way round: `l` is not unique on k, so its
    probe expands four lanes a row, reads the overflow flag a batch, and
    re-runs a batch whose key has more duplicates than lanes."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    s = Session()
    keys, pick = _orders_and_lines(s)
    s.execute("set cbo = 0")            # keep l on the build side
    before = _join_counters()
    got = s.execute("select sum(b), count(*) from o join l on o.k = l.k"
                    ).rows()[0]
    moved = {k: v - before.get(k, 0) for k, v in _join_counters().items()}
    assert tuple(got) == (90_000, 90_000)
    assert moved["mo_join_probe_lanes_total{}"] >= \
        4 * moved["mo_join_probe_rows_total{stage=in}"] > 0
    assert moved["mo_device_wait_total{site=join_overflow}"] >= 1
    assert np.bincount(pick).max() > 4
    assert moved["mo_join_probe_retries_total{}"] >= 1
    s.close()


def test_unique_builds_are_derived_from_primary_keys():
    from matrixone_tpu.utils import tpch
    s = Session()
    tpch.load_lineitem(s.catalog, 2_000, seed=2)
    tpch.load_tpch_q3(s.catalog, 400, seed=2)
    plan = s.execute("explain " + tpch.Q3_SQL).text
    joins = [ln for ln in plan.splitlines() if ln.strip().startswith("Join")]
    # customer on its key; then customer x orders, which a unique customer
    # build leaves unique on o_orderkey
    assert len(joins) == 2 and all("build=unique" in j for j in joins)
    plan = s.execute("explain select count(*) from orders o, lineitem l "
                     "where o.o_custkey = l.l_suppkey").text
    assert "build=unique" not in plan
    s.close()


def _eager_answer(session_, sql):
    """The statement through the per-operator JoinOp (no fused join)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("MO_FUSION_JOIN", "0")
        return _rows(session_, sql)


@pytest.mark.parametrize("ktype, k1, k2", [
    ("date", "'2020-01-01'", "'2020-01-02'"),
    ("decimal(12,2)", "1.50", "2.50"),
    ("timestamp", "'2020-01-01 00:00:01'", "'2020-01-01 00:00:02'")])
def test_a_declared_key_the_engine_does_not_check_is_not_unique(
        monkeypatch, ktype, k1, k2):
    """`check_pk_unique` covers integer and varlen keys only, so a DATE,
    DECIMAL or TIMESTAMP primary key can hold a key twice: such a build
    is not `build=unique`, keeps its lanes and its flag, and the join
    answers what the eager JoinOp answers."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    s = Session()
    s.execute(f"create table d (k {ktype} primary key, a bigint)")
    s.execute(f"create table f (k {ktype}, b bigint)")
    s.execute(f"insert into d values ({k1}, 1), ({k2}, 2)")
    s.execute(f"insert into d values ({k1}, 10)")       # not refused
    s.execute(f"insert into f values ({k1}, 1), ({k2}, 1), ({k1}, 1)")
    assert s.catalog.get_table("d").enforced_key == []
    sql = "select sum(a), count(*) from f, d where f.k = d.k"
    plan = s.execute("explain " + sql).text
    assert "join=build+probe" in plan and "build=unique" not in plan
    before = _modes()
    got = _rows(s, sql)
    assert _modes()["fused"] == before["fused"] + 1
    assert got == [(24, 5)] == _eager_answer(s, sql)
    s.close()


def test_an_open_transaction_plans_no_unique_build(monkeypatch):
    """A transaction reads its own workspace, which is held to the
    primary key only at commit: a key written twice inside it must match
    twice (and the commit is then refused)."""
    from matrixone_tpu.storage.engine import DuplicateKeyError
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    s = Session()
    s.execute("create table o (k bigint primary key, a bigint)")
    s.execute("create table l (k bigint, b bigint)")
    s.execute("insert into o values (1, 1), (2, 2)")
    s.execute("insert into l values (1, 1), (2, 1), (1, 1)")
    sql = "select sum(a), count(*) from l, o where l.k = o.k"
    assert "build=unique" in s.execute("explain " + sql).text
    assert _rows(s, sql) == [(4, 3)]
    s.execute("begin")
    s.execute("insert into o values (1, 100)")
    assert "build=unique" not in s.execute("explain " + sql).text
    assert _rows(s, sql) == [(204, 5)]
    with pytest.raises(DuplicateKeyError):
        s.execute("commit")
    s.close()


@pytest.mark.parametrize("stride, lookup", [(1, "dense"),
                                            (1_000_003, "sorted")])
def test_a_broken_unique_build_fails_the_statement(monkeypatch, stride,
                                                   lookup):
    """The tripwire behind `build=unique`: with the commit's check taken
    away a key lands twice under an enforced primary key; the one-lane
    probe would answer short, so the statement fails instead, whichever
    lookup the build chose."""
    from matrixone_tpu.storage.engine import MVCCTable
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    s = Session()
    s.execute("create table o (k bigint primary key, a bigint)")
    s.execute("create table l (k bigint, b bigint)")
    keys = np.arange(5_000, dtype=np.int64) * stride
    monkeypatch.setattr(MVCCTable, "check_pk_unique",
                        lambda self, *a, **kw: None)
    s.catalog.get_table("o").insert_numpy(
        {"k": np.concatenate([keys, keys[:1]]),
         "a": np.ones(5_001, np.int64)})
    s.catalog.get_table("l").insert_numpy(
        {"k": keys, "b": np.ones(5_000, np.int64)})
    sql = "select sum(a), count(*) from l, o where l.k = o.k"
    assert "build=unique" in s.execute("explain " + sql).text
    from matrixone_tpu.ops import kernels as HK
    assert HK.join_lookup(True, 1, int(keys[-1]) + 1) == lookup
    with pytest.raises(RuntimeError, match="two build rows under one key"):
        s.execute(sql)
    s.close()


def test_dense_table_lookup_equals_the_sorted_search():
    """`build_dense_table` + the gather against hash, sort and search, on
    keys with holes, NULLs, masked rows and probes outside the range."""
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    nb, np_ = 512, 4096
    bkey = rng.permutation(np.arange(100, 100 + 2 * nb, 2))[:nb] \
        .astype(np.int32)
    bvalid = rng.random(nb) < 0.9
    table, dup = J.build_dense_table(
        jnp.asarray(bkey), jnp.asarray(bvalid), np.int64(100), 2048)
    table = np.asarray(table)
    assert not bool(dup)
    twice = np.concatenate([bkey[:-1], bkey[:1]])       # one key twice
    assert bool(J.build_dense_table(jnp.asarray(twice), jnp.ones(nb, bool),
                                    np.int64(100), 2048)[1])
    pkey = rng.integers(0, 100 + 2 * nb + 50, np_).astype(np.int32)
    slot = pkey.astype(np.int64) - 100
    inside = (slot >= 0) & (slot < 2048)
    row = np.where(inside, table[np.clip(slot, 0, 2047)], -1)
    want = {int(k): i for i, (k, v) in enumerate(zip(bkey, bvalid)) if v}
    assert [int(r) for r in row] == [want.get(int(k), -1) for k in pkey]


@pytest.mark.parametrize("lanes, live_share", [
    (5000, 0.7),          # a short batch: every lane is scattered
    (1 << 17, 0.03),      # packed first: the live rows fit lanes / 16
    (1 << 17, 0.5),       # too many live rows to pack: all scattered
])
def test_wide_dense_fold_equals_numpy(lanes, live_share):
    """The grouped aggregate's wide dense path (`_wide_fold`): mixed-radix
    slots over a dictionary code and a ranged integer, NULL keys in their
    own slots, masked rows nowhere; packed or not, the same sums."""
    import jax.numpy as jnp
    from matrixone_tpu.ops import agg as A
    from matrixone_tpu.vm import operators as O
    rng = np.random.default_rng(2)
    n, sizes, los = lanes, (8, 25), np.array([1992, 0], np.int64)
    year = rng.integers(1992, 1999, n).astype(np.int32)
    code = rng.integers(0, 25, n).astype(np.int32)
    yvalid, cvalid = rng.random(n) < 0.95, np.ones(n, bool)
    mask, v = rng.random(n) < live_share, rng.integers(0, 10 ** 9, n)
    vvalid = rng.random(n) < 0.9
    strides, g = A.dense_slot_strides(sizes)

    def fold(year_):
        return O._wide_fold(
            (jnp.asarray(year_), jnp.asarray(code)),
            (jnp.asarray(yvalid), jnp.asarray(cvalid)), jnp.asarray(mask),
            (jnp.asarray(v),), (jnp.asarray(vvalid),), jnp.asarray(los),
            jnp.zeros((g, 3), jnp.int64), jnp.zeros((), jnp.bool_),
            sizes=sizes, fields=(("sum", "count"),))

    acc, outside = fold(year)
    slot = np.where(yvalid, year - 1992, 8) * strides[0] + code * strides[1]
    want = np.zeros((g, 3), np.int64)
    np.add.at(want[:, 0], slot[mask & vvalid], v[mask & vvalid])
    np.add.at(want[:, 1], slot[mask & vvalid], 1)
    np.add.at(want[:, 2], slot[mask], 1)
    assert not bool(outside)
    assert np.array_equal(np.asarray(acc), want)
    assert bool(fold(year + 20)[1])      # a key outside its code space


def test_a_dimension_row_deleted_changes_the_answer(tables):
    """The guarantee behind the benchmark's planted fault: the join reads
    the dimension as it is, not as it was loaded."""
    s = Session()
    ssb.load_ssb(s.catalog, tables)
    sql = ssb.render("q3.1", ssb.PAPER_PARAMS["q3.1"])
    whole = s.execute(sql).rows()
    victim = int(tables["lineorder"]["lo_custkey"][
        ssb._eq(tables["customer"]["c_region"], "ASIA")[
            tables["lineorder"]["lo_custkey"] - 1]][0])
    s.execute(f"delete from customer where c_custkey = {victim}")
    assert s.execute(sql).rows() != whole
    s.close()
