"""Loader of the vector configurations: seeded clustered vectors
(`assumed` in the configuration file: chip_smoke.py's recipe), loaded into
`docs (id bigint primary key, v vecf32(dim))` in several commits; after
the re-open the IVF-Flat index is built through SQL and EXPLAIN has to
name it."""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def generate(cfg, seed):
    """`centres` centres ~ N(0, 1) per dimension; each row is its centre
    plus N(0, sigma^2) noise; queries are fresh points drawn the same way.
    Generated in blocks whose seeds, not the thread schedule, fix the
    data."""
    n, dim, centres = cfg["vectors"], cfg["dim"], cfg["centres"]
    sigma, nq = cfg["sigma"], cfg["query_pool"]
    root = np.random.default_rng(seed)
    cent = root.standard_normal((centres, dim), dtype=np.float32)
    labels = root.integers(0, centres, n)
    x = np.empty((n, dim), np.float32)
    step = 1 << 16

    def fill(lo):
        hi = min(n, lo + step)
        noise = np.random.default_rng([seed, lo]).standard_normal(
            (hi - lo, dim), dtype=np.float32)
        x[lo:hi] = cent[labels[lo:hi]] + sigma * noise

    with ThreadPoolExecutor(os.cpu_count() or 4) as pool:
        list(pool.map(fill, range(0, n, step)))
    q = (cent[root.integers(0, centres, nq)] + sigma
         * root.standard_normal((nq, dim), dtype=np.float32))
    return {"x": x, "queries": q.astype(np.float32)}


def load(cfg, data, engine):
    from matrixone_tpu.frontend import Session
    x = data["x"]
    Session(catalog=engine).execute(
        f"create table docs (id bigint primary key, v vecf32({x.shape[1]}))")
    t = engine.get_table("docs")
    bounds = np.linspace(0, len(x), cfg["commits"] + 1).astype(np.int64)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        t.insert_numpy({"id": np.arange(lo, hi, dtype=np.int64),
                        "v": x[lo:hi]})


def literal(v):
    return "[" + ",".join(repr(float(f)) for f in v) + "]"


def pools(cfg, data):
    return {"queries": [literal(v) for v in data["queries"]]}


def rows(cfg, data):
    return {"docs": len(data["x"])}


def prepare(cfg, data, conn):
    got = int(conn.query("select count(*) from docs")[0][0])
    conn.query(f"create index docs_v using ivfflat on docs (v) "
               f"lists = {cfg['lists']} op_type = 'vector_l2_ops'")
    conn.query(f"set ivf_nprobe = {cfg['nprobe']}")
    plan = "\n".join(r[0] for r in conn.query(
        f"explain select id from docs order by l2_distance(v, "
        f"'{literal(data['queries'][0])}') limit {cfg['k']}"))
    return {"rows_not_read_back": [abs(got - len(data["x"])), 0],
            "index_not_in_plan":
                [int("VectorTopK" not in plan or "docs_v" not in plan), 0]}
