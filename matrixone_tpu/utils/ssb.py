"""The Star Schema Benchmark (O'Neil, O'Neil, Chen, Revilak 2009, rev. 3):
a seeded generator of its five tables, the engine loader, the thirteen
queries Q1.1-Q4.3 as templates with their substitution parameters, and a
plain numpy reference.

NOT dbgen (none in this image): cardinalities, columns, domains and
hierarchies follow section 2 of the paper: `lineorder` 1,500,000 x SF
orders of 1 to 7 lines, `customer` 30,000 x SF, `supplier` 2,000 x SF,
`part` 200,000 x floor(1 + log2 SF), the date dimension seven calendar years
from 1992-01-01; 5 regions > 25 nations > 250 cities (the nation's first
nine characters and a digit); MFGR#1-5 > MFGR#11-55 > 40 brands a
category; uniform foreign keys.  The date dimension is the table `dates`
(`date` is a reserved word).  Money is in the paper's integer units.

A string column is held as `Coded(codes, cats)`: int32 codes into a short
list of values, which is also what the engine's bulk insert takes, so SF1
is generated in seconds and no 6M-row string array is ever made.
"""

from __future__ import annotations

import calendar
import datetime
from typing import Dict, NamedTuple, Optional

import numpy as np

from matrixone_tpu.container import dtypes as dt
from matrixone_tpu.storage.engine import TableMeta
from matrixone_tpu.utils.tpch_full import (COLORS, CONT_S1, CONT_S2, NATIONS,
                                           PRIORITIES, REGIONS, SEGMENTS,
                                           SHIPMODES, TYPE_S1, TYPE_S2,
                                           TYPE_S3)


class Coded(NamedTuple):
    """A string column: `cats[codes[i]]` is row i's value."""
    codes: np.ndarray
    cats: list

    def __len__(self):
        return len(self.codes)

    def values(self):
        return np.asarray(self.cats, dtype=object)[self.codes]


# ----------------------------------------------------------------- schema

_I, _L = dt.INT32, dt.INT64
SCHEMAS = {
    "dates": [
        ("d_datekey", _I), ("d_date", dt.varchar(18)),
        ("d_dayofweek", dt.varchar(9)), ("d_month", dt.varchar(9)),
        ("d_year", _I), ("d_yearmonthnum", _I),
        ("d_yearmonth", dt.varchar(7)), ("d_daynuminweek", _I),
        ("d_daynuminmonth", _I), ("d_daynuminyear", _I),
        ("d_monthnuminyear", _I), ("d_weeknuminyear", _I),
        ("d_sellingseason", dt.varchar(12)), ("d_lastdayinweekfl", _I),
        ("d_lastdayinmonthfl", _I), ("d_holidayfl", _I),
        ("d_weekdayfl", _I)],
    "customer": [
        ("c_custkey", _I), ("c_name", dt.varchar(25)),
        ("c_address", dt.varchar(25)), ("c_city", dt.varchar(10)),
        ("c_nation", dt.varchar(15)), ("c_region", dt.varchar(12)),
        ("c_phone", dt.varchar(15)), ("c_mktsegment", dt.varchar(10))],
    "supplier": [
        ("s_suppkey", _I), ("s_name", dt.varchar(25)),
        ("s_address", dt.varchar(25)), ("s_city", dt.varchar(10)),
        ("s_nation", dt.varchar(15)), ("s_region", dt.varchar(12)),
        ("s_phone", dt.varchar(15))],
    "part": [
        ("p_partkey", _I), ("p_name", dt.varchar(22)),
        ("p_mfgr", dt.varchar(6)), ("p_category", dt.varchar(7)),
        ("p_brand1", dt.varchar(9)), ("p_color", dt.varchar(11)),
        ("p_type", dt.varchar(25)), ("p_size", _I),
        ("p_container", dt.varchar(10))],
    "lineorder": [
        ("lo_orderkey", _L), ("lo_linenumber", _I), ("lo_custkey", _I),
        ("lo_partkey", _I), ("lo_suppkey", _I), ("lo_orderdate", _I),
        ("lo_orderpriority", dt.varchar(15)), ("lo_shippriority", _I),
        ("lo_quantity", _L), ("lo_extendedprice", _L),
        ("lo_ordtotalprice", _L), ("lo_discount", _L), ("lo_revenue", _L),
        ("lo_supplycost", _L), ("lo_tax", _L), ("lo_commitdate", _I),
        ("lo_shipmode", dt.varchar(10))],
}
PRIMARY_KEYS = {"dates": ["d_datekey"], "customer": ["c_custkey"],
                "supplier": ["s_suppkey"], "part": ["p_partkey"],
                "lineorder": ["lo_orderkey", "lo_linenumber"]}

NATION_NAMES = [n for n, _ in NATIONS]
NATION_REGION = [REGIONS[r] for _, r in NATIONS]
#: the ten cities of a nation: its first nine characters, padded, + a digit
CITIES = [f"{n[:9]:<9}{d}" for n in NATION_NAMES for d in range(10)]
MFGRS = [f"MFGR#{m}" for m in range(1, 6)]
CATEGORIES = [f"MFGR#{m}{c}" for m in range(1, 6) for c in range(1, 6)]
BRANDS = [f"{c}{b}" for c in CATEGORIES for b in range(1, 41)]
MONTHS = list(calendar.month_name)[1:]
YEARS = range(1992, 1999)
YEARMONTHNUMS = [y * 100 + m for y in YEARS for m in range(1, 13)]
YEARMONTHS = [f"{MONTHS[m - 1][:3]}{y}" for y in YEARS
              for m in range(1, 13)]
#: the months a substitution parameter is drawn from: those with orders
#: (up to July 1998).  A month after the last order has filter factor 0,
#: not the paper's 1/84
ORDER_MONTHS = 79
#: orders are placed up to 1998-08-02 (as dbgen), so that a commit date
#: 30 to 90 days later still finds its row in the date dimension
_LAST_ORDER_DAY = (datetime.date(1998, 8, 2)
                   - datetime.date(1992, 1, 1)).days


# -------------------------------------------------------------- generator

def _gen_dates() -> dict:
    day0 = datetime.date(1992, 1, 1)
    days = [day0 + datetime.timedelta(d)
            for d in range((datetime.date(1999, 1, 1) - day0).days)]
    n = len(days)
    year = np.array([d.year for d in days], np.int32)
    month = np.array([d.month for d in days], np.int32)
    dom = np.array([d.day for d in days], np.int32)
    doy = np.array([d.timetuple().tm_yday for d in days], np.int32)
    dow = np.array([d.isoweekday() % 7 for d in days], np.int32)  # Sun=0
    last_dom = np.array([calendar.monthrange(d.year, d.month)[1]
                         for d in days], np.int32)
    season = np.select(
        [month == 12, month <= 3, month <= 5, month <= 8],
        [0, 1, 2, 3], 4).astype(np.int32)
    holiday = ((month == 12) & (dom == 25)) | ((month == 1) & (dom == 1)) \
        | ((month == 7) & (dom == 4))
    return {
        "d_datekey": year * 10000 + month * 100 + dom,
        "d_date": Coded(np.arange(n, dtype=np.int32),
                        [f"{MONTHS[d.month - 1]} {d.day}, {d.year}"
                         for d in days]),
        "d_dayofweek": Coded(dow, list(calendar.day_name)[-1:]
                             + list(calendar.day_name)[:-1]),
        "d_month": Coded(month - 1, MONTHS),
        "d_year": year,
        "d_yearmonthnum": year * 100 + month,
        "d_yearmonth": Coded((year - 1992) * 12 + month - 1, YEARMONTHS),
        "d_daynuminweek": dow + 1,
        "d_daynuminmonth": dom,
        "d_daynuminyear": doy,
        "d_monthnuminyear": month,
        "d_weeknuminyear": (doy - 1) // 7 + 1,
        "d_sellingseason": Coded(season, ["Christmas", "Winter", "Spring",
                                          "Summer", "Fall"]),
        "d_lastdayinweekfl": (dow == 6).astype(np.int32),
        "d_lastdayinmonthfl": (dom == last_dom).astype(np.int32),
        "d_holidayfl": holiday.astype(np.int32),
        "d_weekdayfl": ((dow >= 1) & (dow <= 5)).astype(np.int32),
    }


def _geo(rng, n, prefix) -> dict:
    """The city > nation > region hierarchy and a phone of the nation."""
    city = rng.integers(0, len(CITIES), n).astype(np.int32)
    nation = city // 10
    region_of = np.array([r for _, r in NATIONS], np.int32)
    local = rng.integers(0, 10 ** 10, n)
    phones = [f"{10 + k}-{v // 10 ** 7:03d}-{v // 10 ** 4 % 1000:03d}-"
              f"{v % 10 ** 4:04d}" for k, v in zip(nation.tolist(),
                                                   local.tolist())]
    return {f"{prefix}_city": Coded(city, CITIES),
            f"{prefix}_nation": Coded(nation, NATION_NAMES),
            f"{prefix}_region": Coded(region_of[nation], REGIONS),
            f"{prefix}_phone": Coded(np.arange(n, dtype=np.int32), phones)}


def _addresses(rng, n) -> Coded:
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOP"
                            b"QRSTUVWXYZ0123456789 ,", np.uint8)
    raw = letters[rng.integers(0, len(letters), (n, 25))]
    lens = rng.integers(10, 26, n)
    cats = [bytes(r[:k]).decode() for r, k in zip(raw, lens.tolist())]
    return Coded(np.arange(n, dtype=np.int32), cats)


def _retail_price(partkey):
    """TPC-H 4.2.3's p_retailprice, in cents (dbgen keeps it for SSB)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _lines_per_order(n_orders: int) -> np.ndarray:
    """1 to 7 lines an order, each as often as the others, FOUR an order
    in all whatever the seed permutes them to: whole cycles of 1..7 and,
    for the orders left over, the values nearest to 4 on both sides.  One
    row count for every seed (6,000,000 at SF1), so that programs keyed
    by a segment's length are the same programs in every run."""
    cycles, rest = divmod(n_orders, 7)
    mid = [4] if rest % 2 else []
    tail = mid + [4 - k for k in range(1, rest // 2 + 1)] \
        + [4 + k for k in range(1, rest // 2 + 1)]
    return np.concatenate([np.tile(np.arange(1, 8), cycles),
                           np.array(tail, np.int64)])


def table_sizes(scale_factor: float) -> dict:
    sf = float(scale_factor)
    return {"orders": max(int(1_500_000 * sf), 1),
            "customer": max(int(30_000 * sf), 10),
            "supplier": max(int(2_000 * sf), 10),
            "part": max(int(200_000 * int(1 + np.log2(max(sf, 1.0)))
                            * min(sf, 1.0)), 40)}


def gen_ssb(scale_factor: float = 1.0, seed: int = 0,
            sizes: Optional[dict] = None) -> Dict[str, Dict[str, object]]:
    """The five tables as host column arrays: numpy arrays, and `Coded`
    for the string columns.  The same seed gives the same tables.
    `sizes` overrides entries of `table_sizes` (a test's small fact table
    over dimensions large enough for every filter to find rows)."""
    rng = np.random.default_rng(seed)
    size = {**table_sizes(scale_factor), **(sizes or {})}
    dates = _gen_dates()

    n = size["customer"]
    key = np.arange(1, n + 1, dtype=np.int32)
    customer = {
        "c_custkey": key,
        "c_name": Coded(key - 1, [f"Customer#{k:09d}" for k in key.tolist()]),
        "c_address": _addresses(rng, n), **_geo(rng, n, "c"),
        "c_mktsegment": Coded(rng.integers(0, 5, n).astype(np.int32),
                              SEGMENTS)}

    n = size["supplier"]
    key = np.arange(1, n + 1, dtype=np.int32)
    supplier = {
        "s_suppkey": key,
        "s_name": Coded(key - 1, [f"Supplier#{k:09d}" for k in key.tolist()]),
        "s_address": _addresses(rng, n), **_geo(rng, n, "s")}

    n = size["part"]
    key = np.arange(1, n + 1, dtype=np.int32)
    brand = rng.integers(0, len(BRANDS), n).astype(np.int32)
    color = rng.integers(0, len(COLORS), n).astype(np.int32)
    types = [f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2
             for c in TYPE_S3]
    part = {
        "p_partkey": key,
        "p_name": Coded((color * len(COLORS)
                         + rng.integers(0, len(COLORS), n)).astype(np.int32),
                        [f"{a} {b}" for a in COLORS for b in COLORS]),
        "p_mfgr": Coded(brand // 200, MFGRS),
        "p_category": Coded(brand // 40, CATEGORIES),
        "p_brand1": Coded(brand, BRANDS),
        "p_color": Coded(color, COLORS),
        "p_type": Coded(rng.integers(0, len(types), n).astype(np.int32),
                        types),
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": Coded(
            rng.integers(0, 40, n).astype(np.int32),
            [f"{a} {b}" for a in CONT_S1 for b in CONT_S2])}

    # ---- lineorder: per order, then per line
    n_orders = size["orders"]
    lines = rng.permutation(_lines_per_order(n_orders))
    order = np.repeat(np.arange(n_orders), lines)           # line -> order
    n = len(order)
    first = np.cumsum(lines) - lines
    datekeys = dates["d_datekey"]
    o_day = rng.integers(0, _LAST_ORDER_DAY + 1, n_orders)
    o_cust = rng.integers(1, size["customer"] + 1, n_orders)
    o_prio = rng.integers(0, 5, n_orders).astype(np.int32)
    partkey = rng.integers(1, size["part"] + 1, n).astype(np.int32)
    quantity = rng.integers(1, 51, n).astype(np.int64)
    discount = rng.integers(0, 11, n).astype(np.int64)
    tax = rng.integers(0, 9, n).astype(np.int64)
    price = _retail_price(partkey.astype(np.int64))
    extended = quantity * price
    charged = extended * (100 - discount) // 100 * (100 + tax) // 100
    total = np.add.reduceat(charged, first)
    lineorder = {
        "lo_orderkey": (order + 1).astype(np.int64),
        "lo_linenumber": (np.arange(n) - first[order] + 1).astype(np.int32),
        "lo_custkey": o_cust[order].astype(np.int32),
        "lo_partkey": partkey,
        "lo_suppkey": rng.integers(1, size["supplier"] + 1,
                                   n).astype(np.int32),
        "lo_orderdate": datekeys[o_day][order],
        "lo_orderpriority": Coded(o_prio[order], PRIORITIES),
        "lo_shippriority": np.zeros(n, np.int32),
        "lo_quantity": quantity,
        "lo_extendedprice": extended,
        "lo_ordtotalprice": total[order],
        "lo_discount": discount,
        "lo_revenue": extended * (100 - discount) // 100,
        "lo_supplycost": 6 * price // 10,
        "lo_tax": tax,
        "lo_commitdate": datekeys[o_day[order] + rng.integers(30, 91, n)],
        "lo_shipmode": Coded(rng.integers(0, 7, n).astype(np.int32),
                             SHIPMODES)}
    return {"dates": dates, "customer": customer, "supplier": supplier,
            "part": part, "lineorder": lineorder}


def load_ssb(catalog, tables: Optional[dict] = None, commits: int = 1,
             scale_factor: float = 0.01, seed: int = 0) -> dict:
    """Create the five tables (primary keys declared) and bulk-insert
    `tables` (or a generated set), each table of at least `commits` rows
    in that many insert commits."""
    if tables is None:
        tables = gen_ssb(scale_factor, seed)
    for name, schema in SCHEMAS.items():
        arrays = tables[name]
        catalog.create_table(TableMeta(name, schema, PRIMARY_KEYS[name]),
                             if_not_exists=True)
        t = catalog.get_table(name)
        n = len(arrays[schema[0][0]])
        bounds = np.linspace(0, n, (commits if n >= commits else 1) + 1
                             ).astype(np.int64)
        for lo, hi in zip(bounds[:-1], bounds[1:]):
            part, strings = {}, {}
            for col, dtype in schema:
                a = arrays[col]
                if dtype.is_varlen:
                    strings[col] = (a.codes[lo:hi], a.cats)
                else:
                    part[col] = a[lo:hi]
            t.insert_numpy(part, strings=strings)
    return tables


# -------------------------------------------------------------- templates

_Q1 = ("select sum(lo_extendedprice * lo_discount) as revenue "
       "from lineorder, dates where lo_orderdate = d_datekey and ")
_Q2 = ("select sum(lo_revenue), d_year, p_brand1 "
       "from lineorder, dates, part, supplier "
       "where lo_orderdate = d_datekey and lo_partkey = p_partkey "
       "and lo_suppkey = s_suppkey and {} "
       "group by d_year, p_brand1 order by d_year, p_brand1")
_Q3 = ("select {0}, {1}, d_year, sum(lo_revenue) as revenue "
       "from customer, lineorder, supplier, dates "
       "where lo_custkey = c_custkey and lo_suppkey = s_suppkey "
       "and lo_orderdate = d_datekey and {2} "
       "group by {0}, {1}, d_year order by d_year asc, revenue desc")
_Q4 = ("select {0}, sum(lo_revenue - lo_supplycost) as profit "
       "from dates, customer, supplier, part, lineorder "
       "where lo_custkey = c_custkey and lo_suppkey = s_suppkey "
       "and lo_partkey = p_partkey and lo_orderdate = d_datekey and {1} "
       "group by {0} order by {0}")
_CITIES = ("(c_city = '{city_a}' or c_city = '{city_b}') and "
           "(s_city = '{city_a}' or s_city = '{city_b}')")
_MFGRS = "(p_mfgr = '{mfgr_a}' or p_mfgr = '{mfgr_b}')"
_YEARS2 = "(d_year = {year_a} or d_year = {year_b})"

#: the thirteen queries in the paper's order and text (comma joins, the
#: equalities in WHERE), each constant a `{placeholder}`
TEMPLATES = {
    "q1.1": _Q1 + "d_year = {year} and lo_discount between {discount_lo} "
                  "and {discount_hi} and lo_quantity < 25",
    "q1.2": _Q1 + "d_yearmonthnum = {yearmonthnum} and lo_discount between "
                  "{discount_lo} and {discount_hi} and lo_quantity between "
                  "{quantity_lo} and {quantity_hi}",
    "q1.3": _Q1 + "d_weeknuminyear = {week} and d_year = {week_year} and "
                  "lo_discount between {discount_lo} and {discount_hi} and "
                  "lo_quantity between {quantity_lo} and {quantity_hi}",
    "q2.1": _Q2.format("p_category = '{category}' and "
                       "s_region = '{region}'"),
    "q2.2": _Q2.format("p_brand1 between '{brand_lo}' and '{brand_hi}' and "
                       "s_region = '{region}'"),
    "q2.3": _Q2.format("p_brand1 = '{brand}' and s_region = '{region}'"),
    "q3.1": _Q3.format("c_nation", "s_nation",
                       "c_region = '{region}' and s_region = '{region}' "
                       "and d_year >= 1992 and d_year <= 1997"),
    "q3.2": _Q3.format("c_city", "s_city",
                       "c_nation = '{nation}' and s_nation = '{nation}' "
                       "and d_year >= 1992 and d_year <= 1997"),
    "q3.3": _Q3.format("c_city", "s_city", _CITIES +
                       " and d_year >= 1992 and d_year <= 1997"),
    "q3.4": _Q3.format("c_city", "s_city", _CITIES +
                       " and d_yearmonth = '{yearmonth}'"),
    "q4.1": _Q4.format("d_year, c_nation",
                       "c_region = '{region}' and s_region = '{region}' "
                       "and " + _MFGRS),
    "q4.2": _Q4.format("d_year, s_nation, p_category",
                       "c_region = '{region}' and s_region = '{region}' "
                       "and " + _YEARS2 + " and " + _MFGRS),
    "q4.3": _Q4.format("d_year, s_city, p_brand1",
                       "c_region = '{region_x}' and s_nation = '{nation_x}' "
                       "and " + _YEARS2 + " and p_category = '{category}'"),
}

#: the paper's own constants
PAPER_PARAMS = {
    "q1.1": {"year": 1993, "discount_lo": 1, "discount_hi": 3},
    "q1.2": {"yearmonthnum": 199401, "discount_lo": 4, "discount_hi": 6,
             "quantity_lo": 26, "quantity_hi": 35},
    "q1.3": {"week": 6, "week_year": 1994, "discount_lo": 5,
             "discount_hi": 7,
             "quantity_lo": 26, "quantity_hi": 35},
    "q2.1": {"category": "MFGR#12", "region": "AMERICA"},
    "q2.2": {"brand_lo": "MFGR#2221", "brand_hi": "MFGR#2228",
             "region": "ASIA"},
    "q2.3": {"brand": "MFGR#2239", "region": "EUROPE"},
    "q3.1": {"region": "ASIA"},
    "q3.2": {"nation": "UNITED STATES"},
    "q3.3": {"city_a": "UNITED KI1", "city_b": "UNITED KI5"},
    "q3.4": {"city_a": "UNITED KI1", "city_b": "UNITED KI5",
             "yearmonth": "Dec1997"},
    "q4.1": {"region": "AMERICA", "mfgr_a": "MFGR#1", "mfgr_b": "MFGR#2"},
    "q4.2": {"region": "AMERICA", "year_a": 1997, "year_b": 1998,
             "mfgr_a": "MFGR#1", "mfgr_b": "MFGR#2"},
    "q4.3": {"region_x": "AMERICA", "nation_x": "UNITED STATES",
             "year_a": 1997, "year_b": 1998, "category": "MFGR#14"},
}


def draw_world(rng, city_nations=None) -> dict:
    """One coherent draw of every substitution parameter, over each
    column's own domain and keeping each query's filter factor: a template
    takes the keys it names.  `rng` is a `random.Random`; `city_nations`
    narrows the nations (by number) the two cities are drawn from."""
    x = rng.randint(1, 9)
    q = rng.randint(1, 41)
    cat = rng.choice(CATEGORIES)
    tens, a = rng.randint(1, 3), rng.randint(0, 2)
    n1 = rng.choice(city_nations or range(len(NATION_NAMES)))
    c1, c2 = rng.sample(range(10), 2)
    m1, m2 = sorted(rng.sample(range(5), 2))
    ya = rng.randint(1992, 1997)
    n2 = rng.randrange(len(NATION_NAMES))
    return {
        "year": rng.randint(1992, 1998),
        "yearmonthnum": rng.choice(YEARMONTHNUMS[:ORDER_MONTHS]),
        "yearmonth": rng.choice(YEARMONTHS[:ORDER_MONTHS]),
        "week": rng.randint(1, 52), "week_year": rng.randint(1992, 1997),
        "discount_lo": x - 1, "discount_hi": x + 1,
        "quantity_lo": q, "quantity_hi": q + 9,
        "category": rng.choice(CATEGORIES), "region": rng.choice(REGIONS),
        "brand_lo": f"{cat}{tens}{a}", "brand_hi": f"{cat}{tens}{a + 7}",
        "brand": rng.choice(BRANDS), "nation": rng.choice(NATION_NAMES),
        "city_a": CITIES[n1 * 10 + c1], "city_b": CITIES[n1 * 10 + c2],
        "mfgr_a": MFGRS[m1], "mfgr_b": MFGRS[m2],
        "year_a": ya, "year_b": ya + 1,
        "region_x": NATION_REGION[n2], "nation_x": NATION_NAMES[n2]}


def render(template: str, params: dict) -> str:
    sql = TEMPLATES[template]
    for name, value in params.items():
        sql = sql.replace("{" + name + "}", str(value))
    return sql


# ------------------------------------------------------- plain reference
#
# Straightforward numpy over the generated arrays, independent of the
# engine: a boolean mask over each dimension, the join as an index by key
# (dimension keys are 1..N; the date key through a lookup), grouped sums
# by np.unique + np.add.at in int64, ORDER BY by a stable sort on the
# query's own keys.  The largest sum is lo_revenue over a region pair:
# under 6.0e6 rows x 1.05e7 (50 x the largest price) = 6.3e13 at SF1,
# below 2^53 = 9.0e15 and far below 2^63.

def _eq(col: Coded, value) -> np.ndarray:
    """Row mask of a string column equal to `value`."""
    return np.asarray([c == value for c in col.cats], bool)[col.codes]


def _between(col: Coded, lo, hi) -> np.ndarray:
    return np.asarray([lo <= c <= hi for c in col.cats], bool)[col.codes]


class Star:
    """The fact table's foreign keys resolved to dimension row numbers,
    once for all statements."""

    def __init__(self, tables, sum_dtype=np.int64):
        self.t = tables
        self.sum_dtype = sum_dtype
        lo, d = tables["lineorder"], tables["dates"]
        by_key = np.full(int(d["d_datekey"].max()) + 1, -1, np.int64)
        by_key[d["d_datekey"]] = np.arange(len(d["d_datekey"]))
        self.date_row = by_key[lo["lo_orderdate"]]
        self.row = {"customer": lo["lo_custkey"].astype(np.int64) - 1,
                    "supplier": lo["lo_suppkey"].astype(np.int64) - 1,
                    "part": lo["lo_partkey"].astype(np.int64) - 1,
                    "dates": self.date_row}
        # a deleted dimension row must drop its fact rows: hold the keys
        for name, key in (("customer", "c_custkey"), ("supplier",
                          "s_suppkey"), ("part", "p_partkey")):
            k = tables[name][key]
            if len(k) == 0 or k[0] != 1 or k[-1] != len(k):
                raise ValueError(f"{name}: keys are not 1..N")

    def grouped(self, fact_mask, keys, measure, order):
        """`keys` is [(table, column)], `measure` an int64 array over
        lineorder; -> rows (key values..., sum), ordered by `order`:
        indexes into the row, negative for descending."""
        rows = np.flatnonzero(fact_mask)
        cols = []
        for table, col in keys:
            c = self.t[table][col]
            dim = self.row[table][rows]
            cols.append(c.codes[dim] if isinstance(c, Coded) else c[dim])
        if len(rows) == 0:
            return []
        stacked = np.stack([np.asarray(c, np.int64) for c in cols], 1)
        uniq, inverse = np.unique(stacked, axis=0, return_inverse=True)
        sums = np.zeros(len(uniq), self.sum_dtype)
        np.add.at(sums, inverse.reshape(-1),
                  measure[rows].astype(self.sum_dtype))
        out = []
        for u, s in zip(uniq.tolist(), sums.tolist()):
            vals = [self.t[tb][c].cats[v] if isinstance(self.t[tb][c], Coded)
                    else v for (tb, c), v in zip(keys, u)]
            out.append(tuple(vals) + (int(round(s)),))
        for i in reversed(order):
            out.sort(key=lambda r, i=i: r[abs(i) - 1], reverse=i < 0)
        return out


def answer(star: Star, template: str, p: dict) -> list:
    """The exact answer of one statement: rows of (str | int)."""
    t, lo = star.t, star.t["lineorder"]
    d, c, s, pt = t["dates"], t["customer"], t["supplier"], t["part"]
    drow = star.row["dates"]
    flight = template[:2]
    if flight == "q1":
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
        m = (dm[drow] & qm & (lo["lo_discount"] >= p["discount_lo"])
             & (lo["lo_discount"] <= p["discount_hi"]))
        if not m.any():
            return [(None,)]
        prod = (lo["lo_extendedprice"][m] * lo["lo_discount"][m]
                ).astype(star.sum_dtype)
        return [(int(round(float(prod.sum()))),)] \
            if star.sum_dtype != np.int64 else [(int(prod.sum()),)]
    if flight == "q2":
        pm = {"q2.1": lambda: _eq(pt["p_category"], p["category"]),
              "q2.2": lambda: _between(pt["p_brand1"], p["brand_lo"],
                                       p["brand_hi"]),
              "q2.3": lambda: _eq(pt["p_brand1"], p["brand"])}[template]()
        m = pm[star.row["part"]] \
            & _eq(s["s_region"], p["region"])[star.row["supplier"]]
        rows = star.grouped(m, [("dates", "d_year"), ("part", "p_brand1")],
                            lo["lo_revenue"], [1, 2])
        return [(r[2], r[0], r[1]) for r in rows]
    if flight == "q3":
        years = (d["d_year"] >= 1992) & (d["d_year"] <= 1997)
        if template == "q3.1":
            cm, sm = (_eq(c["c_region"], p["region"]),
                      _eq(s["s_region"], p["region"]))
            keys = ("c_nation", "s_nation")
        elif template == "q3.2":
            cm, sm = (_eq(c["c_nation"], p["nation"]),
                      _eq(s["s_nation"], p["nation"]))
            keys = ("c_city", "s_city")
        else:
            cm = _eq(c["c_city"], p["city_a"]) | _eq(c["c_city"], p["city_b"])
            sm = _eq(s["s_city"], p["city_a"]) | _eq(s["s_city"], p["city_b"])
            keys = ("c_city", "s_city")
            if template == "q3.4":
                years = _eq(d["d_yearmonth"], p["yearmonth"])
        m = cm[star.row["customer"]] & sm[star.row["supplier"]] & years[drow]
        return star.grouped(m, [("customer", keys[0]), ("supplier", keys[1]),
                                ("dates", "d_year")], lo["lo_revenue"],
                            [3, -4])
    cm = _eq(c["c_region"], p["region_x" if template == "q4.3"
                               else "region"])
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
    m = (cm[star.row["customer"]] & sm[star.row["supplier"]]
         & pm[star.row["part"]] & dm[drow])
    return star.grouped(m, keys, lo["lo_revenue"] - lo["lo_supplycost"],
                        list(range(1, len(keys) + 1)))
