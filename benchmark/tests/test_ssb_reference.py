"""`references/ssb.py` at a size a test run can hold: the numpy reference
against a second evaluation written as plain loops over the rows; the
float32 control comes out NOT correct; the cell's rehearsal executes every
template and is correct; with a dimension row deleted behind the
reference's back after the load (a guarantee broken: an acknowledged row
is gone) it is not."""

import json
import os
import random

import pytest

import run
import traffic
from conftest import BENCH
from loaders import ssb as loader
from references import ssb as ref

CELL = "ssb-sf1.star-join"


def _config(**over):
    with open(os.path.join(BENCH, "configs", "ssb-sf1.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def cfg():
    return _config(scale_factor=0.002)


@pytest.fixture(scope="module")
def data(cfg):
    return loader.generate(cfg, 2 ** 31 + 5)


@pytest.fixture(scope="module")
def executed(cfg, data):
    """Four cycles of the thirteen templates, answered by the reference."""
    plan = traffic.generate(traffic.load_mix("star-join"), cfg,
                            loader.pools(cfg, data), 2 ** 31 + 5)
    star = ref.Star(data["tables"])
    out = []
    for idx, meta in enumerate(plan["meta"][:52]):
        st = dict(meta, statement=idx, error=None)
        st["rows"] = [list(r) for r in ref._text(ref.answer(
            star, st["template"], ref.params_of(data, st)))]
        out.append(st)
    return out


def _value(col, i):
    return col[1][col[0][i]] if isinstance(col, tuple) else int(col[i])


def looped(tables, template, p):
    """The same semantics as `references/ssb.answer`, one fact row at a
    time: look the four dimension rows up, test the WHERE clause as the
    query's text states it, add into a dict keyed by the GROUP BY."""
    lo, d, c, s, pt = (tables[t] for t in ("lineorder", "dates", "customer",
                                           "supplier", "part"))
    date_row = {int(k): i for i, k in enumerate(d["d_datekey"])}
    flight = template[:2]
    total, groups = None, {}
    for i in range(len(lo["lo_orderkey"])):
        di = date_row[int(lo["lo_orderdate"][i])]
        ci, si, pi = (int(lo[k][i]) - 1 for k in ("lo_custkey", "lo_suppkey",
                                                  "lo_partkey"))
        year = int(d["d_year"][di])
        if flight == "q1":
            q, disc = int(lo["lo_quantity"][i]), int(lo["lo_discount"][i])
            when = {"q1.1": lambda: year == p["year"],
                    "q1.2": lambda: int(d["d_yearmonthnum"][di])
                    == p["yearmonthnum"],
                    "q1.3": lambda: int(d["d_weeknuminyear"][di])
                    == p["week"] and year == p["week_year"]}[template]()
            qty = q < 25 if template == "q1.1" else \
                p["quantity_lo"] <= q <= p["quantity_hi"]
            if when and qty and p["discount_lo"] <= disc <= p["discount_hi"]:
                total = (total or 0) + int(lo["lo_extendedprice"][i]) * disc
            continue
        if flight == "q2":
            brand = _value(pt["p_brand1"], pi)
            part_ok = {"q2.1": lambda: _value(pt["p_category"], pi)
                       == p["category"],
                       "q2.2": lambda: p["brand_lo"] <= brand
                       <= p["brand_hi"],
                       "q2.3": lambda: brand == p["brand"]}[template]()
            if part_ok and _value(s["s_region"], si) == p["region"]:
                key = (year, brand)
                groups[key] = groups.get(key, 0) + int(lo["lo_revenue"][i])
            continue
        if flight == "q3":
            level = {"q3.1": "region", "q3.2": "nation"}.get(template)
            if level:
                ok = (_value(c[f"c_{level}"], ci) == p[level]
                      and _value(s[f"s_{level}"], si) == p[level])
            else:
                two = (p["city_a"], p["city_b"])
                ok = (_value(c["c_city"], ci) in two
                      and _value(s["s_city"], si) in two)
            when = (_value(d["d_yearmonth"], di) == p["yearmonth"]
                    if template == "q3.4" else 1992 <= year <= 1997)
            if ok and when:
                by = "nation" if template == "q3.1" else "city"
                key = (_value(c[f"c_{by}"], ci), _value(s[f"s_{by}"], si),
                       year)
                groups[key] = groups.get(key, 0) + int(lo["lo_revenue"][i])
            continue
        region = p["region_x" if template == "q4.3" else "region"]
        ok = _value(c["c_region"], ci) == region
        if template == "q4.3":
            ok = ok and _value(s["s_nation"], si) == p["nation_x"] \
                and _value(pt["p_category"], pi) == p["category"]
        else:
            ok = ok and _value(s["s_region"], si) == region \
                and _value(pt["p_mfgr"], pi) in (p["mfgr_a"], p["mfgr_b"])
        if template != "q4.1":
            ok = ok and year in (p["year_a"], p["year_b"])
        if ok:
            key = {"q4.1": lambda: (year, _value(c["c_nation"], ci)),
                   "q4.2": lambda: (year, _value(s["s_nation"], si),
                                    _value(pt["p_category"], pi)),
                   "q4.3": lambda: (year, _value(s["s_city"], si),
                                    _value(pt["p_brand1"], pi))}[template]()
            groups[key] = groups.get(key, 0) + int(lo["lo_revenue"][i]) \
                - int(lo["lo_supplycost"][i])
    if flight == "q1":
        return [(total,)]
    rows = [k + (v,) for k, v in groups.items()]
    if flight == "q2":
        return [(v, y, b) for y, b, v in sorted(rows)]
    if flight == "q3":
        return sorted(rows, key=lambda r: (r[2], -r[3]))
    return sorted(rows)


def test_reference_equals_the_looped_evaluation(data):
    star = ref.Star(data["tables"])
    rng = random.Random(3)
    from matrixone_tpu.utils import ssb
    filled = 0
    for template in ssb.TEMPLATES:
        for params in (ssb.PAPER_PARAMS[template], ssb.draw_world(rng)):
            got = ref.answer(star, template, params)
            want = looped(data["tables"], template, params)
            if template.startswith("q3"):
                assert sorted(got) == sorted(want), template
                assert [(r[2], -r[3]) for r in got] == [
                    (r[2], -r[3]) for r in want]
            else:
                assert got == want, template
            filled += bool(got and got[0][0] is not None)
    assert filled >= 13                  # not a comparison of empty answers


def test_the_reference_accepts_its_own_answers(cfg, data, executed):
    numbers, facts = ref.compare(cfg, data, executed)
    assert numbers == {"ssb_cells_unequal": [0, 0],
                       "ssb_statements_failed": [0, 0]}
    assert set(facts["ssb_statements_by_template"].values()) == {4}


def test_one_altered_cell_or_one_row_out_of_order_is_counted(
        cfg, data, executed):
    import copy
    broken = copy.deepcopy(executed)
    grouped = next(s for s in broken if s["template"] == "q2.1"
                   and len(s["rows"]) > 1)
    grouped["rows"][0][0] = str(int(grouped["rows"][0][0]) + 1)
    assert ref.compare(cfg, data, broken)[0]["ssb_cells_unequal"][0] == 1
    broken = copy.deepcopy(executed)
    q3 = next(s for s in broken if s["template"] == "q3.1"
              and len(s["rows"]) > 1)
    q3["rows"][0], q3["rows"][-1] = q3["rows"][-1], q3["rows"][0]
    assert ref.compare(cfg, data, broken)[0]["ssb_cells_unequal"][0] >= 1
    broken = copy.deepcopy(executed)
    broken[0]["error"] = "(1105) lost"
    assert ref.compare(cfg, data, broken)[0]["ssb_statements_failed"][0] == 1


def test_float32_control_is_not_correct():
    """Sums accumulated in float32: at 120,000 fact rows a flight-1 sum is
    about 1e10 and a grouped revenue about 1e9, both past float32's 2^24
    of exact integers, so scalar and grouped flights both trip."""
    cfg = _config(scale_factor=0.02)
    data = loader.generate(cfg, 2 ** 31 + 9)
    plan = traffic.generate(traffic.load_mix("star-join"), cfg,
                            loader.pools(cfg, data), 2 ** 31 + 9)
    executed = [dict(m, statement=i, error=None, rows=[])
                for i, m in enumerate(plan["meta"][:26])]
    control = ref.control_answers(cfg, data, executed)
    numbers, _ = ref.compare(cfg, data, control)
    assert numbers["ssb_cells_unequal"][0] > 0
    tripped = {s["template"][:2] for s in control
               if ref.compare(cfg, data, [s])[0]["ssb_cells_unequal"][0]}
    assert {"q1", "q2"} <= tripped


def test_every_constant_reaches_the_wire_as_the_reference_reads_it():
    """`traffic.generate` collapses runs of whitespace, inside a literal
    too: a constant the reference reads from a pool has to stand in the
    statement's text letter for letter (a city like 'CHINA    5' would
    not, which is why the loader draws cities among those that do)."""
    cfg = _config(scale_factor=0.001)
    for seed in (1, 2 ** 31 + 11):
        data = loader.generate(cfg, seed)
        plan = traffic.generate(traffic.load_mix("star-join"), cfg,
                                loader.pools(cfg, data), seed)
        assert len(plan["statements"]) == 520
        for sql, meta in zip(plan["statements"], plan["meta"]):
            for name, value in ref.params_of(data, meta).items():
                shown = str(value) if name in ref.INT_PARAMS \
                    else f"'{value}'"
                assert shown in sql, (meta["template"], name, value)


def test_plan_check_wants_lineorder_probing_a_fused_join():
    good = ("Sort\n  Aggregate\n"
            "    Join kind=inner build=unique fragment=f2 join=build+probe\n"
            "      Join kind=inner fragment=f1 join=build+probe\n"
            "        Scan table=lineorder cols=['lo_custkey']  -> []\n"
            "        Scan table=supplier cols=['s_suppkey']  -> []\n"
            "      Scan table=dates cols=['d_datekey']  -> []\n")
    assert loader.joins_not_fused_with_lineorder_probing(good) == 0
    swapped = good.replace("table=dates", "table=X").replace(
        "table=lineorder", "table=dates").replace("table=X",
                                                  "table=lineorder")
    assert loader.joins_not_fused_with_lineorder_probing(swapped) == 2
    assert loader.joins_not_fused_with_lineorder_probing(
        good.replace(" fragment=f1 join=build+probe", "")) == 1
    assert loader.joins_not_fused_with_lineorder_probing("Scan table=t") == 1


# ------------------------------------------------------------ the whole run

def delete_a_customer(srv, eng):
    """One dimension row is gone behind the reference's back: the customer
    with the most fact rows, so that every flight that joins customer
    loses them."""
    import loadgen
    conn = loadgen.Connection(srv.port)
    key = conn.query("select lo_custkey from lineorder group by lo_custkey "
                     "order by count(*) desc, lo_custkey limit 1")[0][0]
    conn.query(f"delete from customer where c_custkey = {key}")
    conn.close()


def _rehearse(fault=None):
    return run.run_cell(CELL, seed=2 ** 31 + 77, seconds=14.0, trace=False,
                        rehearse=True, fault=fault)


def test_the_rehearsal_runs_every_template_and_is_correct(capsys):
    assert run.main(["--workload", CELL, "--seed", "2147483725", "--seconds",
                     "14", "--trace", "0", "--rehearse"]) == 3
    out = capsys.readouterr().out.strip().splitlines()
    assert out[-1] == "rehearsal: no result line"
    result = json.loads(out[-2])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"sql_rows_per_s", "setup_s"}
    facts = next(json.loads(ln) for ln in out
                 if '"phase": "reference"' in ln)["facts"]
    assert len(facts["ssb_statements_by_template"]) == 13


def test_a_deleted_dimension_row_is_not_correct():
    result = _rehearse(delete_a_customer)
    assert result["correct"] is False
    over = {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert over == {"ssb_cells_unequal"}, result["compared"]
