"""Plain reference for the thirteen Star Schema Benchmark queries with their
substitution parameters: straightforward numpy over the seeded host arrays.
It imports nothing of the program and takes nothing the program answered.

A string column arrives as (codes, cats): `cats[codes[i]]` is row i's
value.  Each dimension is a boolean mask over its own rows; the join is an
index by key (customer, supplier and part are keyed 1..N, the date key goes
through a lookup table); a grouped sum is np.unique over the mixed-radix
group code + np.add.at in int64; ORDER BY is a stable sort on the query's
own keys.  The largest sum is lo_revenue over a region pair: under
6.0e6 rows x 1.05e7 (50 units of the dearest part) = 6.3e13 at SF1, below
2^53 = 9.0e15 and far below 2^63, so int64 is exact with room.

`compare` holds every answer the window received to it exactly:
`ssb_cells_unequal` counts cells (keys and sums, in the row order wherever
the ORDER BY is total: flights 2 and 4; flight 3 orders by `d_year asc,
revenue desc`, which two groups of one year with equal revenue leave open,
so there the rows are compared as a set and the sort keys' order is held
besides).  `control_answers` puts the reference in the program's place with
the sums accumulated in float32, the step below the exact integer
arithmetic the configuration states; it has to come out not correct.
"""

import numpy as np

LIMITS = {"ssb_cells_unequal": 0, "ssb_statements_failed": 0}
INT_PARAMS = {"year", "yearmonthnum", "week", "week_year", "discount_lo",
              "discount_hi", "quantity_lo", "quantity_hi", "year_a", "year_b"}


def _eq(col, value):
    codes, cats = col
    return np.asarray([c == value for c in cats], bool)[codes]


def _between(col, lo, hi):
    codes, cats = col
    return np.asarray([lo <= c <= hi for c in cats], bool)[codes]


def _is_coded(col):
    return isinstance(col, tuple)


class Star:
    """The fact table's foreign keys resolved to dimension row numbers,
    once for all statements."""

    def __init__(self, tables, sum_dtype=np.int64):
        self.t = tables
        self.sum_dtype = sum_dtype
        lo, d = tables["lineorder"], tables["dates"]
        by_key = np.full(int(d["d_datekey"].max()) + 1, -1, np.int64)
        by_key[d["d_datekey"]] = np.arange(len(d["d_datekey"]))
        self.row = {"customer": lo["lo_custkey"].astype(np.int64) - 1,
                    "supplier": lo["lo_suppkey"].astype(np.int64) - 1,
                    "part": lo["lo_partkey"].astype(np.int64) - 1,
                    "dates": by_key[lo["lo_orderdate"]]}
        for name, key in (("customer", "c_custkey"),
                          ("supplier", "s_suppkey"), ("part", "p_partkey")):
            k = tables[name][key]
            if k[0] != 1 or k[-1] != len(k):
                raise ValueError(f"{name}: keys are not 1..N")

    def total(self, values):
        if self.sum_dtype == np.int64:
            return int(values.sum())
        return int(round(float(values.astype(self.sum_dtype).sum())))

    def grouped(self, fact_mask, keys, measure, order):
        """`keys` is [(table, column)], `measure` an int64 array over
        lineorder.  -> rows (key values..., sum) ordered by `order`: 1-based
        positions in the row, negative for descending."""
        rows = np.flatnonzero(fact_mask)
        if len(rows) == 0:
            return []
        code = np.zeros(len(rows), np.int64)
        parts = []
        for table, col in keys:
            c = self.t[table][col]
            v = (c[0] if _is_coded(c) else c)[self.row[table][rows]]
            v = v.astype(np.int64)
            lo, span = int(v.min()), int(v.max()) - int(v.min()) + 1
            code = code * span + (v - lo)
            parts.append((lo, span))
        uniq, inverse = np.unique(code, return_inverse=True)
        sums = np.zeros(len(uniq), self.sum_dtype)
        np.add.at(sums, inverse, measure[rows].astype(self.sum_dtype))
        cols = []
        for (lo, span), (table, col) in zip(reversed(parts), reversed(keys)):
            c = self.t[table][col]
            v = uniq % span + lo
            uniq = uniq // span
            cols.append([c[1][i] for i in v.tolist()] if _is_coded(c)
                        else v.tolist())
        out = [tuple(r) + (int(round(float(s))),)
               for r, s in zip(zip(*reversed(cols)), sums.tolist())]
        for i in reversed(order):
            out.sort(key=lambda r, i=i: r[abs(i) - 1], reverse=i < 0)
        return out


def answer(star, template, p):
    """The exact answer of one statement: rows of (str | int | None)."""
    t, lo = star.t, star.t["lineorder"]
    d, c, s, pt = t["dates"], t["customer"], t["supplier"], t["part"]
    row = star.row
    if template.startswith("q1"):
        if template == "q1.1":
            dm = d["d_year"] == p["year"]
            qm = lo["lo_quantity"] < 25
        else:
            dm = (d["d_yearmonthnum"] == p["yearmonthnum"]
                  if template == "q1.2" else
                  (d["d_weeknuminyear"] == p["week"])
                  & (d["d_year"] == p["week_year"]))
            qm = ((lo["lo_quantity"] >= p["quantity_lo"])
                  & (lo["lo_quantity"] <= p["quantity_hi"]))
        m = (dm[row["dates"]] & qm & (lo["lo_discount"] >= p["discount_lo"])
             & (lo["lo_discount"] <= p["discount_hi"]))
        if not m.any():
            return [(None,)]
        return [(star.total(lo["lo_extendedprice"][m]
                            * lo["lo_discount"][m]),)]
    if template.startswith("q2"):
        pm = (_eq(pt["p_category"], p["category"]) if template == "q2.1"
              else _between(pt["p_brand1"], p["brand_lo"], p["brand_hi"])
              if template == "q2.2" else _eq(pt["p_brand1"], p["brand"]))
        m = pm[row["part"]] & _eq(s["s_region"], p["region"])[row["supplier"]]
        rows = star.grouped(m, [("dates", "d_year"), ("part", "p_brand1")],
                            lo["lo_revenue"], [1, 2])
        return [(r[2], r[0], r[1]) for r in rows]
    if template.startswith("q3"):
        years = (d["d_year"] >= 1992) & (d["d_year"] <= 1997)
        keys = ("c_city", "s_city")
        if template == "q3.1":
            cm, sm = (_eq(c["c_region"], p["region"]),
                      _eq(s["s_region"], p["region"]))
            keys = ("c_nation", "s_nation")
        elif template == "q3.2":
            cm, sm = (_eq(c["c_nation"], p["nation"]),
                      _eq(s["s_nation"], p["nation"]))
        else:
            cm = _eq(c["c_city"], p["city_a"]) | _eq(c["c_city"], p["city_b"])
            sm = _eq(s["s_city"], p["city_a"]) | _eq(s["s_city"], p["city_b"])
            if template == "q3.4":
                years = _eq(d["d_yearmonth"], p["yearmonth"])
        m = cm[row["customer"]] & sm[row["supplier"]] & years[row["dates"]]
        return star.grouped(m, [("customer", keys[0]), ("supplier", keys[1]),
                                ("dates", "d_year")], lo["lo_revenue"],
                            [3, -4])
    if not template.startswith("q4"):
        raise ValueError(f"no reference for template {template!r}")
    cm = _eq(c["c_region"], p["region_x" if template == "q4.3" else "region"])
    dm = np.ones(len(d["d_year"]), bool)
    if template != "q4.1":
        dm = (d["d_year"] == p["year_a"]) | (d["d_year"] == p["year_b"])
    if template == "q4.3":
        sm = _eq(s["s_nation"], p["nation_x"])
        pm = _eq(pt["p_category"], p["category"])
        keys = [("dates", "d_year"), ("supplier", "s_city"),
                ("part", "p_brand1")]
    else:
        sm = _eq(s["s_region"], p["region"])
        pm = _eq(pt["p_mfgr"], p["mfgr_a"]) | _eq(pt["p_mfgr"], p["mfgr_b"])
        keys = ([("dates", "d_year"), ("customer", "c_nation")]
                if template == "q4.1" else
                [("dates", "d_year"), ("supplier", "s_nation"),
                 ("part", "p_category")])
    m = (cm[row["customer"]] & sm[row["supplier"]] & pm[row["part"]]
         & dm[row["dates"]])
    return star.grouped(m, keys, lo["lo_revenue"] - lo["lo_supplycost"],
                        list(range(1, len(keys) + 1)))


def params_of(data, st):
    """A statement's constants as values: a parameter drawn from a pool is
    held as the statement's number, the pool entry is its literal."""
    out = {}
    for name, v in st["params"].items():
        if name in data["pools"]:
            v = data["pools"][name][v]
        out[name] = int(v) if name in INT_PARAMS else v
    return out


def _text(rows):
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


def _unequal(template, got, want):
    """Cells of one answer that differ from the reference's."""
    got = [tuple(r) for r in got]
    if not template.startswith("q3"):
        if len(got) != len(want):
            return sum(len(r) for r in want) or 1
        return sum(g != w for gr, wr in zip(got, want)
                   for g, w in zip(gr + (None,) * len(wr), wr))
    left = {}
    for r in want:
        left[r] = left.get(r, 0) + 1
    bad = 0
    for r in got:
        if left.get(r, 0) > 0:
            left[r] -= 1
        else:
            bad += len(r)
    bad += sum(n * 4 for n in left.values())
    try:
        keys = [(int(r[2]), -int(r[3])) for r in got]
    except (TypeError, ValueError, IndexError):
        return bad + 1
    return bad + sum(a > b for a, b in zip(keys, keys[1:]))


def compare(cfg, data, executed):
    """Every answer of the window against the exact reference.
    -> (numbers {name: [value, limit]}, facts)."""
    star = Star(data["tables"])
    unequal = failed = 0
    seen = {}
    for st in executed:
        if st["error"] is not None:
            failed += 1
            continue
        want = _text(answer(star, st["template"], params_of(data, st)))
        unequal += _unequal(st["template"], st["rows"], want)
        seen[st["template"]] = seen.get(st["template"], 0) + 1
    return ({"ssb_cells_unequal": [unequal, LIMITS["ssb_cells_unequal"]],
             "ssb_statements_failed":
                 [failed, LIMITS["ssb_statements_failed"]]},
            {"ssb_statements_by_template": seen})


def control_answers(cfg, data, executed):
    """The same statements answered with float32 sums, rendered as the
    wire renders them.  -> executed, with control rows."""
    star = Star(data["tables"], sum_dtype=np.float32)
    return [dict(st, error=None, rows=[list(r) for r in _text(
        answer(star, st["template"], params_of(data, st)))])
        for st in executed]
