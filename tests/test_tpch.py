"""TPC-H Q1/Q6 end-to-end vs exact integer-domain oracle
(reference analogue: plan/tpch golden tests + BVT benchmark cases)."""

import numpy as np
import pytest

from matrixone_tpu.frontend import Session
from matrixone_tpu.utils import tpch


@pytest.fixture(scope="module")
def sess_arrays():
    s = Session()
    arrays = tpch.load_lineitem(s.catalog, 50_000, seed=7)
    return s, arrays


def test_q1_exact(sess_arrays):
    s, arrays = sess_arrays
    rows = s.execute(tpch.Q1_SQL).rows()
    oracle = tpch.q1_oracle(arrays)
    # group ordering: flag asc, status asc
    keys = [(r[0], r[1]) for r in rows]
    assert keys == sorted(keys)
    assert tpch.q1_check(rows, oracle)
    # the checker itself must catch corruption
    bad = [tuple([rows[0][0], rows[0][1], rows[0][2] + 1] + list(rows[0][3:]))] \
        + rows[1:]
    assert not tpch.q1_check(bad, oracle)
    assert not tpch.q1_check(rows[:-1], oracle)


def test_q6_exact(sess_arrays):
    s, arrays = sess_arrays
    rows = s.execute(tpch.Q6_SQL).rows()
    sel = (arrays["l_shipdate"] >= 8766) & (arrays["l_shipdate"] < 9131) & \
          (arrays["l_discount"] >= 5) & (arrays["l_discount"] <= 7) & \
          (arrays["l_quantity"] < 2400)
    rev = int((arrays["l_extendedprice"][sel].astype(object)
               * arrays["l_discount"][sel]).sum())
    assert abs(rows[0][0] - rev / 10000) < 1e-9


def test_q1_streaming_multi_batch():
    """Same result when the scan is split into many device batches
    (exercises the streaming partial-aggregate merge)."""
    s = Session()
    arrays = tpch.load_lineitem(s.catalog, 30_000, seed=3)
    big = s.execute(tpch.Q1_SQL).rows()
    # re-plan with tiny scan batches
    from matrixone_tpu.sql.binder import Binder
    from matrixone_tpu.sql.parser import parse_one
    from matrixone_tpu.vm import operators as O
    from matrixone_tpu.vm.compile import compile_plan
    node = Binder(s.catalog).bind_select(parse_one(tpch.Q1_SQL))

    def small_scan_compile(n, catalog):
        op = compile_plan(n, catalog)

        def patch(o):
            if isinstance(o, O.ScanOp):
                o.batch_rows = 4096
            for attr in ("child", "left", "right"):
                c = getattr(o, attr, None)
                if c is not None:
                    patch(c)
        patch(op)
        return op

    op = small_scan_compile(node, s.catalog)
    batches = [s._to_host(ex, node.schema) for ex in op.execute()]
    assert len(batches) == 1
    small = [tuple(vals) for vals in zip(*[batches[0].columns[n].to_pylist()
                                           for n in batches[0].columns])]
    assert sorted(map(repr, small)) == sorted(map(repr, big))


def test_q3_exact():
    import datetime
    s = Session()
    arrays = tpch.load_lineitem(s.catalog, 20_000, seed=2)
    q3data = tpch.load_tpch_q3(s.catalog, 4_000, seed=2)
    got = s.execute(tpch.Q3_SQL).rows()
    exp = tpch.q3_oracle(arrays, q3data)
    assert len(got) == len(exp)
    epoch = datetime.date(1970, 1, 1)
    for g, e in zip(got, exp):
        assert g[0] == e[0]                       # l_orderkey
        assert round(g[1] * 10000) == e[1]        # revenue scale-4 exact
        assert (g[2] - epoch).days == e[2]        # o_orderdate
