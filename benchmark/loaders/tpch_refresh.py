"""Loader of `tpch-sf1-refresh`: `tpch-sf1`'s tables (the repo's seeded
generator, the bulk load, the read-back: `loaders/tpch.py`) plus TPC-H's
refresh sets (spec 2.5-2.7), made from the seed in place of `dbgen -U`.

`data` is the eight tables and, under `refresh`, the sets as host arrays
(what the reference replays) and the pools (what the one generator sends).
A refresh function changes `refresh_orders` orders (SF x 1500, the spec's)
in one transaction.  Set r is round r of the traffic mix: its RF1 half is
that many new orders, keys past every key before them, with 1 to 7
lineitems each, every column drawn in the base tables' domains and
`o_totalprice` the sum over its lineitems (spec 3.3.2, as the generator
computes it); its RF2 half names as many of the oldest orders still
present, a run of consecutive keys (the generator's keys are dense), as
`lo` and `hi`.

The LAST set cancels itself: its RF2 deletes exactly the orders its RF1
inserted.  `run.py` warms a cell with the plan's last round, twice, so
after the warm-up every statement shape has run, the visible rows are the
loaded ones again, and the reference starts from `data`.

A pool is indexed by the statement's number (`traffic.py`), so each holds
an entry a statement, empty where the statement's template does not use
it."""

import datetime

import numpy as np

from loaders import tpch

EPOCH = datetime.date(1970, 1, 1)
ROUND = ("begin", "rf1_orders", "rf1_lineitem", "commit", "orders_check",
         "q1", "q6", "begin", "rf2_lineitem", "rf2_orders", "commit",
         "orders_check", "q1", "q6")
POOLS = ("rf1_orders_rows", "rf1_lineitem_rows", "rf2_lo", "rf2_hi")


def _draw_set(rng, tables, first_key, n_orders):
    """One RF1 half: (orders, lineitem) as column arrays in the units of
    `gen_tpch` (money in cents, dates in days), keys first_key .. +n."""
    from matrixone_tpu.utils import tpch_full as T
    part, base_orders = tables["part"], tables["orders"]
    n_part, n_supp = len(part["p_partkey"]), len(tables["supplier"]["s_suppkey"])
    o_key = np.arange(first_key, first_key + n_orders, dtype=np.int64)
    o_date = rng.integers(T._days(1992, 1, 1), T._days(1998, 8, 3),
                          n_orders).astype(np.int32)
    lines = rng.integers(1, 8, n_orders)
    l_order = np.repeat(o_key, lines)
    n_li = len(l_order)
    l_part = rng.integers(1, n_part + 1, n_li).astype(np.int64)
    pick4 = rng.integers(0, 4, n_li)
    l_supp = ((l_part - 1 + pick4 * (n_supp // 4) + (l_part - 1) // n_supp)
              % n_supp) + 1
    qty = rng.integers(1, 51, n_li).astype(np.int64)
    ext = qty * part["p_retailprice"][l_part - 1]
    disc = rng.integers(0, 11, n_li).astype(np.int64)
    tax = rng.integers(0, 9, n_li).astype(np.int64)
    date_of_line = np.repeat(o_date, lines)
    ship = date_of_line + rng.integers(1, 122, n_li).astype(np.int32)
    commit = date_of_line + rng.integers(30, 91, n_li).astype(np.int32)
    receipt = ship + rng.integers(1, 31, n_li).astype(np.int32)
    today = T._days(1995, 6, 17)
    rf = np.where(receipt <= today,
                  np.where(rng.random(n_li) < 0.5, "R", "A"), "N")
    ls = np.where(ship > today, "O", "F")
    lineitem = {
        "l_orderkey": l_order, "l_partkey": l_part, "l_suppkey": l_supp,
        "l_linenumber": np.concatenate(
            [np.arange(1, k + 1) for k in lines]).astype(np.int64),
        "l_quantity": qty * 100, "l_extendedprice": ext,
        "l_discount": disc, "l_tax": tax,
        "l_returnflag": rf.astype(object), "l_linestatus": ls.astype(object),
        "l_shipdate": ship, "l_commitdate": commit, "l_receiptdate": receipt,
        "l_shipinstruct": np.array(T.INSTRUCTS, dtype=object)[
            rng.integers(0, 4, n_li)],
        "l_shipmode": np.array(T.SHIPMODES, dtype=object)[
            rng.integers(0, 7, n_li)],
        "l_comment": T._comments(rng, n_li),
    }
    owner = np.repeat(np.arange(n_orders), lines)
    total = np.zeros(n_orders, np.int64)
    np.add.at(total, owner, (ext * (100 - disc) * (100 + tax)) // 10000)
    all_f = np.ones(n_orders, bool)
    any_f = np.zeros(n_orders, bool)
    np.logical_and.at(all_f, owner, ls == "F")
    np.logical_or.at(any_f, owner, ls == "F")
    orders = {
        "o_orderkey": o_key,
        # a customer that has placed orders: the base table's own domain
        "o_custkey": base_orders["o_custkey"][
            rng.integers(0, len(base_orders["o_custkey"]), n_orders)],
        "o_orderstatus": np.where(all_f, "F", np.where(any_f, "P", "O")
                                  ).astype(object),
        "o_totalprice": total, "o_orderdate": o_date,
        "o_orderpriority": np.array(T.PRIORITIES, dtype=object)[
            rng.integers(0, 5, n_orders)],
        "o_clerk": base_orders["o_clerk"][
            rng.integers(0, len(base_orders["o_clerk"]), n_orders)],
        "o_shippriority": np.zeros(n_orders, np.int64),
        "o_comment": T._comments(rng, n_orders, 0.02, "special requests"),
    }
    return orders, lineitem


def _cents(a):
    return [f"{v // 100}.{v % 100:02d}" for v in a.tolist()]


def _dates(a):
    return [f"'{EPOCH + datetime.timedelta(days=d)}'" for d in a.tolist()]


def _quoted(a):
    return [f"'{s}'" for s in a.tolist()]


def _ints(a):
    return [str(v) for v in a.tolist()]


_RENDER = {
    "orders": (("o_orderkey", _ints), ("o_custkey", _ints),
               ("o_orderstatus", _quoted), ("o_totalprice", _cents),
               ("o_orderdate", _dates), ("o_orderpriority", _quoted),
               ("o_clerk", _quoted), ("o_shippriority", _ints),
               ("o_comment", _quoted)),
    "lineitem": (("l_orderkey", _ints), ("l_partkey", _ints),
                 ("l_suppkey", _ints), ("l_linenumber", _ints),
                 ("l_quantity", _cents), ("l_extendedprice", _cents),
                 ("l_discount", _cents), ("l_tax", _cents),
                 ("l_returnflag", _quoted), ("l_linestatus", _quoted),
                 ("l_shipdate", _dates), ("l_commitdate", _dates),
                 ("l_receiptdate", _dates), ("l_shipinstruct", _quoted),
                 ("l_shipmode", _quoted), ("l_comment", _quoted)),
}


def values_text(table, cols):
    """`(v, ...), (v, ...)`: the rows of a multi-row INSERT in the
    schema's column order, as a client would write them."""
    rendered = [fn(cols[c]) for c, fn in _RENDER[table]]
    return ", ".join("(" + ", ".join(row) + ")" for row in zip(*rendered))


def generate(cfg, seed):
    data = tpch.generate(cfg, seed)
    rng = np.random.default_rng([seed, 0x5246])
    n_sets = int(cfg["refresh_rounds"])
    per = int(cfg["refresh_orders"])
    n_loaded = len(data["orders"]["o_orderkey"])
    if n_sets * per > n_loaded:
        raise ValueError("more orders retired than were loaded")
    sets, lo, hi = [], [], []
    for r in range(n_sets):
        first = n_loaded + 1 + r * per
        sets.append(_draw_set(rng, data, first, per))
        # the oldest orders still present; the last set retires its own
        old = first if r == n_sets - 1 else 1 + r * per
        lo.append(old)
        hi.append(old + per - 1)
    pools = {name: [""] * (n_sets * len(ROUND)) for name in POOLS}
    for r, (orders, lineitem) in enumerate(sets):
        def at(template):
            return r * len(ROUND) + ROUND.index(template)
        pools["rf1_orders_rows"][at("rf1_orders")] = values_text(
            "orders", orders)
        pools["rf1_lineitem_rows"][at("rf1_lineitem")] = values_text(
            "lineitem", lineitem)
        for t in ("rf2_lineitem", "rf2_orders"):
            pools["rf2_lo"][at(t)] = str(lo[r])
            pools["rf2_hi"][at(t)] = str(hi[r])
    data["refresh"] = {"sets": sets, "lo": lo, "hi": hi, "pools": pools}
    return data


def _tables(data):
    return {t: cols for t, cols in data.items() if t != "refresh"}


def load(cfg, data, engine):
    tpch.load(cfg, _tables(data), engine)


def pools(cfg, data):
    return data["refresh"]["pools"]


def rows(cfg, data):
    return tpch.rows(cfg, _tables(data))


def prepare(cfg, data, conn):
    """-> numbers compared, each [value, limit]: the read-back of every
    table.  Then what the harness's warm-up cannot touch: its round
    deletes its own fresh rows, so a DELETE whose zonemaps leave it a
    FLUSHED chunk would first run in the window.  Round 0's RF2, rolled
    back."""
    numbers = tpch.prepare(cfg, _tables(data), conn)
    lo, hi = data["refresh"]["lo"][0], data["refresh"]["hi"][0]
    for sql in ("begin",
                f"delete from lineitem where l_orderkey between {lo} and {hi}",
                f"delete from orders where o_orderkey between {lo} and {hi}",
                "rollback"):
        conn.query(sql)
    return numbers
