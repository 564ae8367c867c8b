"""Loader of `vec-wiki-1m-ivf`: the data, the load, the query pool and the
row counts are those of `loaders/vecsql.py`, unchanged.  `prepare` adds a
guard: after the index is built and EXPLAIN names it, two searches are
sent; the second, which compiles nothing, runs on a connection of its own
with a timeout of the configuration's `guard_seconds`.  A deployment whose
single top-k search outlasts that is down: timing its window would spend
the run's limit, so the run ends here, non-zero, with no result line."""

import socket

import loadgen
from loaders import vecsql
from loaders.vecsql import generate, load, pools, rows  # noqa: F401


class SearchTooSlow(RuntimeError):
    pass


def prepare(cfg, data, conn):
    numbers = vecsql.prepare(cfg, data, conn)
    queries = data["queries"]
    searches = [f"select id from docs order by l2_distance(v, "
                f"'{vecsql.literal(queries[j % len(queries)])}') "
                f"limit {cfg['k']}" for j in (0, 1)]
    conn.query(searches[0])              # compiles what a search needs
    limit = float(cfg["guard_seconds"])
    own = loadgen.Connection(conn.sock.getpeername()[1])
    try:
        own.query(f"set ivf_nprobe = {cfg['nprobe']}")
        own.sock.settimeout(limit)
        own.query(searches[1])
    except socket.timeout:
        raise SearchTooSlow(
            f"guard: one warmed top-{cfg['k']} search over "
            f"{len(data['x'])} x {data['x'].shape[1]} did not answer "
            f"within {limit:g} s; the deployment is down, the window is "
            f"not timed") from None
    finally:
        own.sock.close()
    return numbers
