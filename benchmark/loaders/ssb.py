"""Loader of the Star Schema Benchmark configurations: the repo's seeded
generator (`assumed` in the configuration file, in place of dbgen), a bulk
load of the five tables in several commits, the pools of correlated
substitution parameters, the read-back of every table's count(*) from the
re-opened engine, and EXPLAIN of every template: each join is the fused
build + probe pair with `lineorder` on the probe side.

`data` is {"tables": the five tables as host column arrays, "pools":
{placeholder: [literal of statement j]}}.  A pool holds what no `int` or
`choice` parameter expresses, a tuple of constants that depend on one
another: the discount and quantity ranges, eight consecutive brands, two
cities of one nation, two manufacturers, two consecutive years, a nation of
a region.  Every pool is drawn from the seed, one coherent draw a
statement, so that entry j of each belongs together."""

import random


def generate(cfg, seed):
    from matrixone_tpu.utils import ssb
    tables = ssb.gen_ssb(cfg["scale_factor"], seed)
    rng = random.Random(seed)
    # the one generator (traffic.py) collapses every run of whitespace in
    # a statement, inside a literal too: a city whose nation is shorter
    # than eight letters ('CHINA    5') cannot be sent, so the two cities
    # are drawn among those it can send (70 of the 250; `assumed`)
    sendable = [i for i, n in enumerate(ssb.NATION_NAMES)
                if "  " not in f"{n[:9]:<9}0"]
    draws = [ssb.draw_world(rng, city_nations=sendable)
             for _ in range(int(cfg["parameter_draws"]))]
    pools = {name: [str(d[name]) for d in draws] for name in cfg["pools"]}
    return {"tables": tables, "pools": pools}


def load(cfg, data, engine):
    from matrixone_tpu.utils import ssb
    ssb.load_ssb(engine, data["tables"], commits=cfg["commits_per_table"])


def pools(cfg, data):
    return data["pools"]


def rows(cfg, data):
    return {t: len(next(iter(cols.values())))
            for t, cols in data["tables"].items()}


def joins_not_fused_with_lineorder_probing(plan):
    """Joins of one EXPLAIN text that are not the fused build + probe pair
    with the `lineorder` scan under their first (probe) child and not
    under their second (build) child.  A plan with no join counts one."""
    lines = [(len(ln) - len(ln.lstrip()), ln.strip())
             for ln in plan.splitlines() if ln.strip()]
    bad = joins = 0
    for i, (depth, text) in enumerate(lines):
        if not text.startswith("Join"):
            continue
        joins += 1
        kids = []                        # [first line, last line) of each
        for j in range(i + 1, len(lines) + 1):
            if j == len(lines) or lines[j][0] <= depth:
                if kids:
                    kids[-1][1] = j
                break
            if lines[j][0] == depth + 2:
                if kids:
                    kids[-1][1] = j
                kids.append([j, None])
        has = [any("Scan table=lineorder " in lines[k][1]
                   for k in range(a, b)) for a, b in kids]
        if "join=build+probe" not in text or has != [True, False]:
            bad += 1
    return bad if joins else 1


def _dimension_column_sets(templates, schemas):
    """{dimension: the distinct sets of its columns a template names}."""
    import re
    out = {}
    for sql in templates.values():
        named = set(re.findall(r"\b[a-z]{1,2}_[a-z0-9]+\b", sql))
        for table, schema in schemas.items():
            cols = tuple(c for c, _ in schema if c in named)
            if cols and table != "lineorder" \
                    and cols not in out.setdefault(table, []):
                out[table].append(cols)
    return out


def prepare(cfg, data, conn):
    """-> numbers compared, each [value, limit].  Also warms what the
    cell's warm-up cannot promise to touch: a template's constants pick
    one or two of a dimension's chunks, and a chunk of another length
    (the four segments of 2,557 dates differ by a row) pads and summarizes
    through programs of its own, so every dimension is scanned whole once
    with each set of columns a template reads from it."""
    from matrixone_tpu.utils import ssb
    missing = 0
    for table, n in rows(cfg, data).items():
        got = int(conn.query(f"select count(*) from {table}")[0][0])
        missing += abs(got - n)
    for table, sets in _dimension_column_sets(ssb.TEMPLATES,
                                              ssb.SCHEMAS).items():
        ints = dict(ssb.SCHEMAS[table])
        for cols in sets:
            where = " and ".join(f"{c} >= 0" for c in cols
                                 if not ints[c].is_varlen)
            conn.query(f"select {', '.join(f'count({c})' for c in cols)} "
                       f"from {table} where {where}")
    bad = 0
    for name, params in ssb.PAPER_PARAMS.items():
        plan = "\n".join(r[0] for r in conn.query(
            "explain " + ssb.render(name, params)))
        bad += joins_not_fused_with_lineorder_probing(plan)
    return {"rows_not_read_back": [missing, 0],
            "ssb_join_not_in_plan": [bad, 0]}
