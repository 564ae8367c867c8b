"""The plain references and their controls, at a size a test run can hold.

Each reference accepts the exact answers; each control (the reference in
the program's place, one precision step below what the configuration
states) comes out NOT correct under the limits the benchmark runs with."""

from fractions import Fraction

import numpy as np
import pytest

from references import tpch, vecsql


def _within(numbers):
    return {k: v <= limit for k, (v, limit) in numbers.items()}


# ----------------------------------------------------------------- TPC-H

@pytest.fixture(scope="module")
def lineitem():
    """1.5M rows in the spec's value ranges: enough for sum_charge (scale
    6) to pass 2^53, where float64 sums stop being exact."""
    rng = np.random.default_rng(5)
    n = 1_500_000
    qty = rng.integers(1, 51, n)
    ship = rng.integers(tpch._days(1992, 1, 2), tpch.Q1_END + 1, n)
    return {"lineitem": {
        "l_quantity": qty * 100,
        "l_extendedprice": qty * rng.integers(90_000, 209_900, n),
        "l_discount": rng.integers(0, 11, n), "l_tax": rng.integers(0, 9, n),
        "l_returnflag": rng.choice(np.array(["A", "N", "R"], object), n),
        "l_linestatus": rng.choice(np.array(["F", "O"], object), n),
        "l_shipdate": ship.astype(np.int32)}}


STATEMENTS = [
    {"template": "q1", "params": {"delta": 60}},
    {"template": "q1", "params": {"delta": 97}},
    {"template": "q1", "params": {"delta": 120}},
    {"template": "q6", "params": {"year": 1994, "discount": 6,
                                  "quantity": 24}},
    {"template": "q6", "params": {"year": 1997, "discount": 2,
                                  "quantity": 25}}]


def _as_the_wire_renders(rows):
    return [[None if v is None else
             repr(float(v)) if isinstance(v, Fraction) else str(v)
             for v in row] for row in rows]


def test_q1_matches_a_straight_pandas_groupby(lineitem):
    import pandas as pd
    li = lineitem["lineitem"]
    table = tpch.Lineitem(li)
    m = li["l_shipdate"] <= tpch.Q1_END - 97
    df = pd.DataFrame({"rf": li["l_returnflag"][m], "ls": li["l_linestatus"][m],
                       "ext": li["l_extendedprice"][m],
                       "ch": li["l_extendedprice"][m]
                       * (100 - li["l_discount"][m]) * (100 + li["l_tax"][m])})
    g = df.groupby(["rf", "ls"], sort=True).agg(ext=("ext", "sum"),
                                                ch=("ch", "sum"),
                                                n=("ext", "size"))
    want = tpch.expected(table, STATEMENTS[1])
    assert [(r[0], r[1]) for r in want] == list(g.index)
    assert [r[3] for r in want] == [tpch._money(v, 2) for v in g.ext]
    assert [r[5] for r in want] == [tpch._money(v, 6) for v in g.ch]
    assert [r[9] for r in want] == list(g.n)


def test_exact_answers_are_correct_and_the_float64_control_is_not(lineitem):
    table = tpch.Lineitem(lineitem["lineitem"])
    exact = [dict(st, error=None,
                  rows=_as_the_wire_renders(tpch.expected(table, st)))
             for st in STATEMENTS]
    numbers, _ = tpch.compare({}, lineitem, exact)
    assert all(_within(numbers).values()), numbers
    control = tpch.control_answers({}, lineitem, exact)
    numbers, _ = tpch.compare({}, lineitem, control)
    ok = _within(numbers)
    assert not ok["sql_cells_unequal"], numbers      # float64 sums past 2^53
    assert not ok["sql_avg_rel_err"], numbers        # float32 AVG
    assert numbers["sql_avg_rel_err"][0] > 3 * 1e-14


def test_a_missing_row_an_altered_digit_and_a_failure_are_caught(lineitem):
    table = tpch.Lineitem(lineitem["lineitem"])
    rows = _as_the_wire_renders(tpch.expected(table, STATEMENTS[0]))
    base = dict(STATEMENTS[0], error=None)
    short, _ = tpch.compare({}, lineitem, [dict(base, rows=rows[:-1])])
    assert short["sql_cells_unequal"][0] > 0
    bent = [list(r) for r in rows]
    bent[0][5] = bent[0][5][:-1] + str((int(bent[0][5][-1]) + 1) % 10)
    altered, _ = tpch.compare({}, lineitem, [dict(base, rows=bent)])
    assert altered["sql_cells_unequal"][0] == 1
    failed, _ = tpch.compare({}, lineitem,
                             [dict(base, rows=None, error="WireError: x")])
    assert failed["sql_statements_failed"][0] == 1


# ---------------------------------------------------------------- vectors

@pytest.fixture(scope="module")
def vectors():
    rng = np.random.default_rng(9)
    cent = rng.standard_normal((16, 768), dtype=np.float32)
    x = (cent[rng.integers(0, 16, 20_000)]
         + 2.0 * rng.standard_normal((20_000, 768), dtype=np.float32))
    q = (cent[rng.integers(0, 16, 48)]
         + 2.0 * rng.standard_normal((48, 768), dtype=np.float32))
    return {"x": x, "queries": q}


VCFG = {"k": 20}


def test_brute_force_is_the_exact_top_k(vectors):
    x, q = vectors["x"], vectors["queries"]
    got = vecsql.brute_force_topk(x, q[:4], 20)
    for qv, ids in zip(q[:4].astype(np.float64), got):
        d = ((x.astype(np.float64) - qv) ** 2).sum(1)
        assert ids.tolist() == np.argsort(d, kind="stable")[:20].tolist()


def test_exact_answers_are_correct_and_the_half_data_control_is_not(vectors):
    x, q = vectors["x"], vectors["queries"]
    truth = vecsql.brute_force_topk(x, q, 20)
    exact = [{"template": "search", "params": {"query": j}, "error": None,
              "rows": [[str(i)] for i in ids]}
             for j, ids in enumerate(truth.tolist())]
    numbers, facts = vecsql.compare(VCFG, vectors, exact)
    assert all(_within(numbers).values()), numbers
    assert facts["recall_at_k"] == 1.0
    control = vecsql.control_answers(VCFG, vectors, exact)
    numbers, facts = vecsql.compare(VCFG, vectors, control)
    assert not _within(numbers)["vec_recall_deficit"], numbers
    assert 0.3 < facts["recall_at_k"] < 0.7          # half of the rows


def test_a_precision_step_is_below_what_the_answers_can_show(vectors):
    """Why the control is not a precision step: brute force over
    int8-rounded vectors keeps nearly all neighbours."""
    x, q = vectors["x"], vectors["queries"]
    truth = vecsql.brute_force_topk(x, q, 20)
    rounded = vecsql.brute_force_topk(vecsql.int8_round(x),
                                      vecsql.int8_round(q), 20)
    kept = np.mean([len(set(a) & set(b)) / 20
                    for a, b in zip(truth.tolist(), rounded.tolist())])
    assert kept > 0.9


def test_altered_and_malformed_answers_are_caught(vectors):
    x, q = vectors["x"], vectors["queries"]
    truth = vecsql.brute_force_topk(x, q[:8], 20)
    base = {"template": "search", "error": None}
    shifted = [dict(base, params={"query": j},
                    rows=[[str((i + 1) % len(x))] for i in ids])
               for j, ids in enumerate(truth.tolist())]
    numbers, facts = vecsql.compare(VCFG, vectors, shifted)
    assert numbers["vec_answers_malformed"][0] == 8        # out of order
    bad = [dict(base, params={"query": 0}, rows=[["1"]] * 20),      # repeats
           dict(base, params={"query": 1}, rows=[[str(i)] for i in range(19)]),
           dict(base, params={"query": 2},
                rows=[[str(len(x) + i)] for i in range(20)]),       # no rows
           dict(base, params={"query": 3},
                rows=[[str(i)] for i in truth[3][::-1]]),           # descending
           dict(base, params={"query": 4}, rows=None, error="WireError: x")]
    numbers, _ = vecsql.compare(VCFG, vectors, bad)
    assert numbers["vec_answers_malformed"][0] == 4
    assert numbers["vec_statements_failed"][0] == 1
