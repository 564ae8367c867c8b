"""Plain reference for TPC-H Q3 with its substitution parameters (SEGMENT,
DATE), in numpy int64 / `Decimal` from the seeded host arrays.  It imports
nothing of the program and takes nothing the program made.

Money is cents and a discount hundredths, so x*(1-d) carries scale 4; an
order's revenue is under 1e11 scaled, far inside int64.

The answer is the first ten groups by `revenue desc, o_orderdate`.  Two
orders may tie on both keys, and then either may stand first or, at the
tenth place, in or out: a row is right when its order qualifies, its
cells are that order's, and its sort keys are those of its place in the
reference's order; no order may stand twice.

`control_answers` puts the reference in the program's place with the
revenue accumulated in float32.  (A revenue is under 2^53, so float64
holds it exactly and is no step down from the exact DECIMAL arithmetic the
configuration states; `references/ssb.py`'s control takes the same step.)
It has to come out not correct.
"""

import datetime
from decimal import Decimal, InvalidOperation

import numpy as np

EPOCH = datetime.date(1970, 1, 1)
LIMITS = {"q3_cells_unequal": 0, "q3_statements_failed": 0}
LIMIT_ROWS, COLUMNS = 10, 4


def _days(y, m, d):
    return (datetime.date(y, m, d) - EPOCH).days


class Sales:
    """The columns Q3 reads.  `lineitem` is in order-key order, so the
    lines of one order are neighbours and a revenue is a `reduceat`."""

    def __init__(self, data, dtype=np.int64):
        cust, orders, li = data["customer"], data["orders"], data["lineitem"]
        self.dtype = dtype
        self.c_key = cust["c_custkey"].astype(np.int64)
        self.c_segment = cust["c_mktsegment"].astype(str)
        self.o_key = orders["o_orderkey"].astype(np.int64)
        self.o_cust = orders["o_custkey"].astype(np.int64)
        self.o_date = orders["o_orderdate"].astype(np.int64)
        self.o_prio = orders["o_shippriority"].astype(np.int64)
        self.l_key = li["l_orderkey"].astype(np.int64)
        self.l_ship = li["l_shipdate"].astype(np.int64)
        self.l_rev = (li["l_extendedprice"].astype(np.int64)
                      * (100 - li["l_discount"].astype(np.int64)))
        if np.any(np.diff(self.l_key) < 0):
            raise ValueError("lineitem is not in order-key order")

    def q3(self, segment, day):
        """-> {order key: (revenue scaled 1e4, o_orderdate in days,
        o_shippriority)} of every group of the statement."""
        date = _days(1995, 3, day)
        in_segment = np.zeros(self.c_key.max() + 1, bool)
        in_segment[self.c_key[self.c_segment == segment]] = True
        o = np.flatnonzero(in_segment[self.o_cust] & (self.o_date < date))
        wanted = np.zeros(max(self.o_key.max(), self.l_key.max()) + 1, bool)
        wanted[self.o_key[o]] = True
        lines = np.flatnonzero(wanted[self.l_key] & (self.l_ship > date))
        if not len(lines):
            return {}
        keys = self.l_key[lines]
        starts = np.concatenate([[0], np.flatnonzero(np.diff(keys)) + 1])
        rev = self.l_rev[lines].astype(self.dtype)
        if self.dtype == np.int64:
            sums = np.add.reduceat(rev, starts)
        else:                      # the control: one running float32 sum
            ends = np.concatenate([starts[1:], [len(rev)]])
            sums = np.array([np.cumsum(rev[a:b], dtype=self.dtype)[-1]
                             for a, b in zip(starts, ends)])
        of_key = dict(zip(self.o_key[o].tolist(),
                          zip(self.o_date[o].tolist(),
                              self.o_prio[o].tolist())))
        return {k: (s, *of_key[k])
                for k, s in zip(keys[starts].tolist(), sums.tolist())}


def _ordered(groups):
    """The groups as Q3 orders them: [(key, revenue, date, priority)]."""
    return sorted(((k, *v) for k, v in groups.items()),
                  key=lambda g: (-g[1], g[2]))


def _date_text(days):
    return str(EPOCH + datetime.timedelta(days=int(days)))


def _unequal(rows, groups):
    """Cells of one answer that are not what the reference allows."""
    want = _ordered(groups)[:LIMIT_ROWS]
    if len(rows) != len(want):
        return COLUMNS * max(len(want), 1)
    bad, seen = 0, set()
    for got, (_key, rev, date, _prio) in zip(rows, want):
        try:
            key = int(got[0])
            group = groups.get(key)
            if len(got) != COLUMNS or group is None or key in seen:
                bad += COLUMNS
                continue
            seen.add(key)
            cells = (Decimal(got[1]), got[2], int(got[3]))
        except (TypeError, ValueError, InvalidOperation):
            bad += COLUMNS
            continue
        # the row's own order, and the sort keys of its place
        bad += int(cells[0] != Decimal(group[0]).scaleb(-4)
                   or cells[0] != Decimal(rev).scaleb(-4))
        bad += int(cells[1] != _date_text(group[1])
                   or cells[1] != _date_text(date))
        bad += int(cells[2] != group[2])
    return bad


def compare(cfg, data, executed):
    """Every answer of the window against the exact reference.
    -> (numbers {name: [value, limit]}, facts {})."""
    sales = Sales(data)
    unequal = failed = 0
    for st in executed:
        if st["error"] is not None:
            failed += 1
            continue
        p = st["params"]
        unequal += _unequal(st["rows"], sales.q3(p["segment"], p["day"]))
    return ({"q3_cells_unequal": [unequal, LIMITS["q3_cells_unequal"]],
             "q3_statements_failed":
                 [failed, LIMITS["q3_statements_failed"]]}, {})


def control_answers(cfg, data, executed):
    """The same statements answered with float32 revenues, rendered as
    the wire renders them.  -> executed, with control rows."""
    sales = Sales(data, dtype=np.float32)
    out = []
    for st in executed:
        p = st["params"]
        top = _ordered(sales.q3(p["segment"], p["day"]))[:LIMIT_ROWS]
        rows = [[str(k), str(Decimal(int(rev)).scaleb(-4)), _date_text(date),
                 str(prio)] for k, rev, date, prio in top]
        out.append(dict(st, rows=rows, error=None))
    return out
