"""Plain reference for `tpch-sf1-refresh`: TPC-H's refresh functions under
Q1, Q6 and a read-back of `orders`, in numpy int64 / `Decimal` / `Fraction`
from the seeded host arrays.  It imports nothing of the program and takes
nothing the program made (Q1's and Q6's arithmetic is `references/tpch.py`'s,
whose helpers and comparison of one cell it shares).

It replays the window's statements in the order the one client sent them.
A refresh function is applied at its `commit`, whole (RF1: the orders and
the lineitems of its set; RF2: every order of its key range and their
lineitems); every `q1`, `q6` and `orders_check` is judged on exactly the
rows committed before it was sent.  A statement in flight at the window's
close completes and counts; a transaction the close leaves open has no
later reader and is not judged.

Q1's sums over 6M rows are not summed again a statement: the rows shipped
on or before the earliest cutoff are kept as sums a group and moved by a
refresh function's own rows; the few shipped later are filtered a
statement, as `references/tpch.py` does.

`control_answers` puts the reference in the program's place with the sums
accumulated in float64 (the step below the exact DECIMAL arithmetic the
configuration states) and AVG taken in float32 (the step below DOUBLE);
it has to come out not correct.
"""

from fractions import Fraction

import numpy as np

from references.tpch import Q1_END, _cell_gap, _days, _money

#: an exact comparison has the limit 0; AVG is a DOUBLE (the limit and its
#: two readings are `sql_avg_rel_err`'s: PERF.md section 2)
LIMITS = {"rf_cells_unequal": 0, "rf_avg_rel_err": 1e-11,
          "rf_statements_failed": 0, "rf_plan_wrapped": 0}
ROUND = 14                      # statements a round of the traffic mix
MEASURES = ("qty", "ext", "dp", "ch", "disc")
READERS = ("q1", "q6", "orders_check")


class Warehouse:
    """`orders` and `lineitem` as they stand after the commits replayed so
    far: the loaded rows followed by every refresh set's, with a flag a row
    for whether it is committed and not yet deleted.  Order keys ascend
    over the whole of it (a set's keys lie past every key before it), so
    the rows of a key range are one slice of each table."""

    def __init__(self, data, dtype=np.int64):
        import pandas as pd
        sets = data["refresh"]["sets"]
        self.dtype = dtype
        #: RF2's key range a round: the oldest orders still present
        self.ranges = list(zip(data["refresh"]["lo"], data["refresh"]["hi"]))

        def column(table, name, part, kind):
            return np.concatenate([data[table][name]]
                                  + [s[part][name] for s in sets]
                                  ).astype(kind)

        self.o_key = column("orders", "o_orderkey", 0, np.int64)
        self.o_total = column("orders", "o_totalprice", 0, dtype)
        n_orders = len(data["orders"]["o_orderkey"])
        self.o_set = np.cumsum([n_orders]
                               + [len(s[0]["o_orderkey"]) for s in sets])
        self.o_live = np.arange(len(self.o_key)) < n_orders

        self.l_key = column("lineitem", "l_orderkey", 1, np.int64)
        self.ship = column("lineitem", "l_shipdate", 1, np.int64)
        self.qty = column("lineitem", "l_quantity", 1, dtype)
        self.ext = column("lineitem", "l_extendedprice", 1, dtype)
        self.disc = column("lineitem", "l_discount", 1, dtype)
        self.dp = self.ext * (100 - self.disc)
        self.ch = self.dp * (100 + column("lineitem", "l_tax", 1, dtype))
        n_lines = len(data["lineitem"]["l_orderkey"])
        self.l_set = np.cumsum([n_lines]
                               + [len(s[1]["l_orderkey"]) for s in sets])
        self.l_live = np.arange(len(self.l_key)) < n_lines
        rf = np.concatenate([data["lineitem"]["l_returnflag"]]
                            + [s[1]["l_returnflag"] for s in sets])
        ls = np.concatenate([data["lineitem"]["l_linestatus"]]
                            + [s[1]["l_linestatus"] for s in sets])
        rf, self.rf_names = pd.factorize(rf, sort=True)
        ls, self.ls_names = pd.factorize(ls, sort=True)
        self.group = rf.astype(np.int64) * len(self.ls_names) + ls
        self.n_groups = len(self.rf_names) * len(self.ls_names)
        # Q1: summed once and moved by deltas / filtered a statement
        self.early = self.ship <= Q1_END - 120
        self.late = ~self.early & (self.ship <= Q1_END - 60)
        self.base = self._sums(np.flatnonzero(self.early & self.l_live))
        # Q6 reads one year of ship dates: the rows of each, found once
        year = (self.ship >= _days(1993, 1, 1)).astype(np.int8)
        for y in range(1994, 1999):
            year += self.ship >= _days(y, 1, 1)
        self.of_year = {1992 + k: np.flatnonzero(year == k)
                        for k in range(1, 6)}

    # ---- the two refresh functions, applied at their commit
    def _sums(self, rows):
        """-> [n_groups, 6]: the five measures and the row count."""
        out = np.zeros((self.n_groups, len(MEASURES) + 1), self.dtype)
        g = self.group[rows]
        cols = [getattr(self, name)[rows] for name in MEASURES]
        for code in np.unique(g):
            m = g == code
            out[code, -1] = m.sum()
            for j, col in enumerate(cols):
                out[code, j] = col[m].sum()
        return out

    def _move(self, l_rows, o_rows, live):
        """Rows `l_rows` of lineitem and `o_rows` of orders (index arrays)
        become committed (`live`) or deleted."""
        early = l_rows[self.early[l_rows]]
        self.base = self.base + (1 if live else -1) * self._sums(early)
        self.l_live[l_rows] = live
        self.o_live[o_rows] = live

    def rf1(self, r):
        self._move(np.arange(self.l_set[r], self.l_set[r + 1]),
                   np.arange(self.o_set[r], self.o_set[r + 1]), True)

    def rf2(self, lo, hi):
        def present(keys, live):
            a, b = np.searchsorted(keys, [lo, hi + 1])
            return a + np.flatnonzero(live[a:b])
        self._move(present(self.l_key, self.l_live),
                   present(self.o_key, self.o_live), False)

    # ---- the three readers
    def q1_groups(self, delta):
        late = np.flatnonzero(self.late & self.l_live
                              & (self.ship <= Q1_END - delta))
        for code, sums in enumerate(self.base + self._sums(late)):
            if sums[-1]:
                yield (str(self.rf_names[code // len(self.ls_names)]),
                       str(self.ls_names[code % len(self.ls_names)]),
                       sums[:-1], int(sums[-1]))

    def q6(self, year, discount, quantity):
        rows = self.of_year[year]
        rows = rows[self.l_live[rows]]
        disc, qty = self.disc[rows], self.qty[rows]
        m = ((disc >= discount - 1) & (disc <= discount + 1)
             & (qty < quantity * 100))
        return (self.ext[rows][m] * disc[m]).sum() if m.any() else None

    def orders_check(self):
        live = np.flatnonzero(self.o_live)
        if not len(live):
            return 0, None, None, None
        return (len(live), self.o_total[live].sum(),
                int(self.o_key[live[0]]), int(self.o_key[live[-1]]))


def expected(house, st):
    """The exact answer of one reader: rows of Python values
    (str, Decimal, Fraction, int)."""
    p = st["params"]
    if st["template"] == "q1":
        rows = []
        for rf, ls, sums, n in house.q1_groups(p["delta"]):
            qty, ext, dp, ch, disc = (int(v) for v in sums)
            rows.append((rf, ls, _money(qty, 2), _money(ext, 2),
                         _money(dp, 4), _money(ch, 6), Fraction(qty, 100 * n),
                         Fraction(ext, 100 * n), Fraction(disc, 100 * n), n))
        return rows
    if st["template"] == "q6":
        rev = house.q6(p["year"], p["discount"], p["quantity"])
        return [(None if rev is None else _money(rev, 4),)]
    n, total, lo, hi = house.orders_check()
    return [(n, None if total is None else _money(total, 2), lo, hi)]


def replay(house, executed):
    """Walk the window in the order it was sent, applying each refresh
    function to `house` at its commit, and yield every reader that was
    answered at its own place in that order: what `house` holds then is
    what the reader has to have seen."""
    pending = None                    # the open transaction's functions
    for st in executed:
        if st["error"] is not None:
            continue
        name, r = st["template"], st["statement"] // ROUND
        if name == "begin":
            pending = set()
        elif name == "commit":
            # a function changes both tables or, as far as this replay
            # goes, neither: half a function is a failed statement
            if pending and {"rf1_orders", "rf1_lineitem"} <= pending:
                house.rf1(r)
            if pending and {"rf2_lineitem", "rf2_orders"} <= pending:
                house.rf2(*house.ranges[r])
            pending = None
        elif name in READERS:
            yield st
        elif pending is not None:
            pending.add(name)


def compare(cfg, data, executed):
    """Every answer of the window against the exact reference.
    -> (numbers {name: [value, limit]}, facts)."""
    house = Warehouse(data)
    unequal, worst, judged = 0, 0.0, 0
    for st in replay(house, executed):
        want = expected(house, st)
        judged += 1
        if len(st["rows"]) != len(want):
            unequal += sum(len(r) for r in want)
            continue
        for got_row, want_row in zip(st["rows"], want):
            if len(got_row) != len(want_row):
                unequal += len(want_row)
                continue
            for got, ref in zip(got_row, want_row):
                bad, err = _cell_gap(got, ref)
                unequal += bad
                worst = max(worst, err)
    failed = sum(st["error"] is not None for st in executed)
    # one client, from statement 0 on: a number that falls is the plan
    # started again, and a refresh set sent twice
    wrapped = sum(b["statement"] < a["statement"]
                  for a, b in zip(executed, executed[1:]))
    commits = sum(st["template"] == "commit" and st["error"] is None
                  for st in executed)
    return ({"rf_cells_unequal": [unequal, LIMITS["rf_cells_unequal"]],
             "rf_avg_rel_err": [worst, LIMITS["rf_avg_rel_err"]],
             "rf_statements_failed": [failed, LIMITS["rf_statements_failed"]],
             "rf_plan_wrapped": [wrapped, LIMITS["rf_plan_wrapped"]]},
            {"rf_commits": commits, "rf_answers_judged": judged,
             "rf_rounds_planned": len(house.ranges)})


def control_answers(cfg, data, executed):
    """The same statements answered with float64 sums and float32 AVGs,
    rendered as the wire renders them.  -> executed, with control rows
    for the readers (the writes keep what the program answered)."""
    house = Warehouse(data, dtype=np.float64)

    def text(units, scale):
        return str(_money(int(round(float(units))), scale))

    answers = {}
    for st in replay(house, executed):
        p = st["params"]
        if st["template"] == "q1":
            rows = []
            for rf, ls, (qty, ext, dp, ch, disc), n in house.q1_groups(
                    p["delta"]):
                rows.append([rf, ls, text(qty, 2), text(ext, 2), text(dp, 4),
                             text(ch, 6)]
                            + [repr(float(np.float32(v) / np.float32(100 * n)))
                               for v in (qty, ext, disc)] + [str(n)])
        elif st["template"] == "q6":
            rev = house.q6(p["year"], p["discount"], p["quantity"])
            rows = [[None if rev is None else text(rev, 4)]]
        else:
            n, total, lo, hi = house.orders_check()
            rows = [[str(n), None if total is None else text(total, 2),
                     None if lo is None else str(lo),
                     None if hi is None else str(hi)]]
        answers[id(st)] = rows
    return [dict(st, rows=answers[id(st)]) if id(st) in answers else st
            for st in executed]
