"""The cell `tpch-sf1.join-q3` at the rehearsal's size: the run is
correct, the reference's control is not, one customer row deleted behind
the reference's back is caught, and the ORDER BY's ties are allowed in
either order and in no other way."""

import json
import os

import run
import traffic
from loaders import tpch as loader
from references import tpch_q3 as reference

CELL = "tpch-sf1.join-q3"
SEED = 2**31 + 93


def _over(result):
    return {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}


def _world():
    with open(os.path.join(run.HERE, "configs", "tpch-sf1.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    data = loader.generate(cfg, SEED)
    plan = traffic.generate(traffic.load_mix("join-q3"), cfg, {}, SEED)
    return cfg, data, plan


def test_the_rehearsal_is_correct_and_its_control_is_not():
    result = run.run_cell(CELL, seed=SEED, seconds=2.0, trace=False,
                          rehearse=True, control=True)
    assert result["correct"] and not _over(result), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["control"]["correct"] is False
    assert result["control"]["compared"]["q3_cells_unequal"][0] > 0


def test_a_customer_deleted_behind_the_references_back_is_not_correct():
    """The customer who placed the first statement's best order."""
    _cfg, data, plan = _world()
    p = plan["meta"][0]["params"]
    groups = reference.Sales(data).q3(p["segment"], p["day"])
    best = reference._ordered(groups)[0][0]
    orders = data["orders"]
    custkey = int(orders["o_custkey"][orders["o_orderkey"] == best][0])

    def drop_the_customer(srv, eng):
        import loadgen
        conn = loadgen.Connection(srv.port)
        conn.query(f"delete from customer where c_custkey = {custkey}")
        conn.close()

    result = run.run_cell(CELL, seed=SEED, seconds=2.0, trace=False,
                          rehearse=True, fault=drop_the_customer)
    assert result["correct"] is False
    assert _over(result) == {"q3_cells_unequal"}, result["compared"]


def test_ties_may_stand_in_either_order_and_nothing_else_may():
    groups = {1: (900000, 9000, 0), 2: (900000, 9000, 0),
              3: (800000, 9001, 0), 4: (800000, 8999, 0)}
    day = reference._date_text

    def row(key):
        rev, date, prio = groups[key]
        return [str(key), f"{rev // 10000}.{rev % 10000:04d}", day(date),
                str(prio)]

    assert reference._unequal([row(k) for k in (1, 2, 4, 3)], groups) == 0
    assert reference._unequal([row(k) for k in (2, 1, 4, 3)], groups) == 0
    assert reference._unequal([row(k) for k in (1, 2, 3, 4)], groups) > 0
    assert reference._unequal([row(k) for k in (1, 1, 4, 3)], groups) > 0
    assert reference._unequal([row(k) for k in (1, 2, 4)], groups) > 0
    wrong = row(1)
    wrong[1] = "90.0001"
    assert reference._unequal([wrong] + [row(k) for k in (2, 4, 3)],
                              groups) > 0
    assert reference._unequal([], {}) == 0
