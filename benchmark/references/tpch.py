"""Plain reference for TPC-H Q1 and Q6 with their substitution parameters,
in numpy int64 / `Decimal` / `Fraction` from the seeded host arrays.  It
imports nothing of the program and takes nothing the program made.

Money is cents, so x*(1-d) carries scale 4 and x*(1-d)*(1+t) scale 6; every
sum stays inside int64 (SF1 sum_charge is about 7e16 scaled).

`compare` checks every answer the window received.  `control_answers`
puts the reference in the program's place with the sums accumulated in
float64 (the step below the exact DECIMAL arithmetic the configuration
states) and AVG taken in float32 (the step below DOUBLE); it has to come
out not correct.
"""

import datetime
from decimal import Decimal, InvalidOperation
from fractions import Fraction

import numpy as np

EPOCH = datetime.date(1970, 1, 1)
Q1_END = (datetime.date(1998, 12, 1) - EPOCH).days
# Limits, each set from two readings (PERF.md section 2, "Limits of
# `correct`"): an exact comparison has the limit 0; AVG is a DOUBLE.
LIMITS = {"sql_cells_unequal": 0, "sql_avg_rel_err": 1e-11,
          "sql_statements_failed": 0}


def _days(y, m, d):
    return (datetime.date(y, m, d) - EPOCH).days


def _money(units, scale):
    return Decimal(int(units)).scaleb(-scale)


class Lineitem:
    """The columns Q1 and Q6 read, as int64, with Q1's group key coded.
    Q1's cutoff is 1998-12-01 minus 60..120 days: rows shipped on or
    before the earliest cutoff are summed once (`base`), the few shipped
    later are kept apart and filtered per statement."""

    MEASURES = ("qty", "ext", "dp", "ch", "disc")

    def __init__(self, li, dtype=np.int64):
        import pandas as pd
        self.dtype = dtype
        self.ship = li["l_shipdate"].astype(np.int64)
        self.qty = li["l_quantity"].astype(dtype)
        self.ext = li["l_extendedprice"].astype(dtype)
        self.disc = li["l_discount"].astype(dtype)
        tax = li["l_tax"].astype(dtype)
        self.dp = self.ext * (100 - self.disc)
        self.ch = self.dp * (100 + tax)
        rf, self.rf_names = pd.factorize(li["l_returnflag"], sort=True)
        ls, self.ls_names = pd.factorize(li["l_linestatus"], sort=True)
        self.group = rf.astype(np.int64) * len(self.ls_names) + ls
        self.n_groups = len(self.rf_names) * len(self.ls_names)
        early = self.ship <= Q1_END - 120
        self.base = self._sums(early)
        self.late = np.flatnonzero(~early & (self.ship <= Q1_END - 60))

    def _sums(self, rows):
        """-> [n_groups, 6]: the five measures and the row count."""
        out = np.zeros((self.n_groups, len(self.MEASURES) + 1), self.dtype)
        g = self.group[rows]
        cols = [getattr(self, name)[rows] for name in self.MEASURES]
        for code in range(self.n_groups):
            m = g == code
            out[code, -1] = m.sum()
            for j, col in enumerate(cols):
                out[code, j] = col[m].sum()
        return out

    def q1(self, delta):
        late = self.late[self.ship[self.late] <= Q1_END - delta]
        return self.base + self._sums(late)

    def q1_groups(self, delta):
        """-> (returnflag, linestatus, [qty, ext, dp, ch, disc], count) of
        every non-empty group, in key order."""
        for code, sums in enumerate(self.q1(delta)):
            if sums[-1]:
                yield (str(self.rf_names[code // len(self.ls_names)]),
                       str(self.ls_names[code % len(self.ls_names)]),
                       sums[:-1], int(sums[-1]))

    def q6(self, year, discount, quantity):
        m = ((self.ship >= _days(year, 1, 1))
             & (self.ship < _days(year + 1, 1, 1))
             & (self.disc >= discount - 1) & (self.disc <= discount + 1)
             & (self.qty < quantity * 100))
        return (self.ext[m] * self.disc[m]).sum() if m.any() else None


def expected(table, meta):
    """The exact answer of one statement: rows of Python values
    (str, Decimal, Fraction, int)."""
    p = meta["params"]
    if meta["template"] == "q1":
        rows = []
        for rf, ls, sums, n in table.q1_groups(p["delta"]):
            qty, ext, dp, ch, disc = (int(v) for v in sums)
            rows.append((rf, ls, _money(qty, 2), _money(ext, 2),
                         _money(dp, 4), _money(ch, 6), Fraction(qty, 100 * n),
                         Fraction(ext, 100 * n), Fraction(disc, 100 * n), n))
        return rows
    if meta["template"] == "q6":
        rev = table.q6(p["year"], p["discount"], p["quantity"])
        return [(None if rev is None else _money(rev, 4),)]
    raise ValueError(f"no reference for template {meta['template']!r}")


def _cell_gap(got, want):
    """(unequal, relative error): exact kinds compare equal or not; a
    Fraction is an AVG and is held by its relative error."""
    if want is None or got is None:
        return int(got is not want), 0.0
    try:
        if isinstance(want, Fraction):
            return 0, float(abs(Fraction(got) - want) / want)
        if isinstance(want, Decimal):
            return int(Decimal(got) != want), 0.0
        if isinstance(want, int):
            return int(int(got) != want), 0.0
    except (ValueError, InvalidOperation, ZeroDivisionError):
        return 1, 0.0
    return int(got != want), 0.0


def compare(cfg, data, executed):
    """Every answer of the window against the exact reference.
    -> (numbers {name: [value, limit]}, facts {})."""
    table = Lineitem(data["lineitem"])
    unequal, worst, failed = 0, 0.0, 0
    for st in executed:
        if st["error"] is not None:
            failed += 1
            continue
        want = expected(table, st)
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
    return ({"sql_cells_unequal": [unequal, LIMITS["sql_cells_unequal"]],
             "sql_avg_rel_err": [worst, LIMITS["sql_avg_rel_err"]],
             "sql_statements_failed":
                 [failed, LIMITS["sql_statements_failed"]]}, {})


def control_answers(cfg, data, executed):
    """The same statements answered with float64 sums and float32 AVGs,
    rendered as the wire renders them.  -> executed, with control rows."""
    table = Lineitem(data["lineitem"], dtype=np.float64)

    def text(units, scale):
        return str(_money(int(round(float(units))), scale))

    out = []
    for st in executed:
        p = st["params"]
        if st["template"] == "q1":
            rows = []
            for rf, ls, (qty, ext, dp, ch, disc), n in table.q1_groups(
                    p["delta"]):
                rows.append([rf, ls, text(qty, 2), text(ext, 2), text(dp, 4),
                             text(ch, 6)]
                            + [repr(float(np.float32(v) / np.float32(100 * n)))
                               for v in (qty, ext, disc)] + [str(n)])
        else:
            rev = table.q6(p["year"], p["discount"], p["quantity"])
            rows = [[None if rev is None else text(rev, 4)]]
        out.append(dict(st, rows=rows, error=None))
    return out
