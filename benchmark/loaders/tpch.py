"""Loader of the TPC-H configurations: the repo's seeded generator
(`assumed` in the configuration file, in place of dbgen), a bulk load in
several commits, and the read-back of every table's count(*) over the
wire after the engine was re-opened from its flushed files."""


def generate(cfg, seed):
    """All eight tables as host column arrays (money in cents, dates in
    days since 1970-01-01)."""
    from matrixone_tpu.utils import tpch_full as T
    return T.gen_tpch(cfg["scale_factor"], seed,
                      lineitem_rows=cfg["lineitem_rows"])


def load(cfg, data, engine):
    from matrixone_tpu.utils import tpch_full as T
    T.load_tpch(engine, tables=data, commits=cfg["commits_per_table"])


def pools(cfg, data):
    return {}


def rows(cfg, data):
    return {t: len(next(iter(cols.values()))) for t, cols in data.items()}


def prepare(cfg, data, conn):
    """Every acknowledged row is read back from the re-opened engine.
    -> numbers compared, each [value, limit]."""
    missing = 0
    for table, n in rows(cfg, data).items():
        got = int(conn.query(f"select count(*) from {table}")[0][0])
        missing += abs(got - n)
    return {"rows_not_read_back": [missing, 0]}
