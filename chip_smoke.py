"""chip_smoke.py: the served SQL and vector path, once, on the TPU.

One process starts `MOServer` on a local port and talks to it only
through `matrixone_tpu.client.connect` over the MySQL wire.  Every answer
is compared with a plain reference computed here, in numpy / pandas /
`Decimal`, from the same seeded arrays.  Phases, one JSON line each:

  device      jax.devices(); anything but the TPUs asked for ends the run
  f64         what a float64 round trip through this chip returns
  sql         TPC-H SF1, the spec's types: bulk load in several commits on
              a LocalFS directory, checkpoint, Engine.open, then Q1, Q6,
              Q3 cold and warm
  write_read  INSERT/UPDATE/DELETE in one transaction; every acknowledged
              row read back by a second connection after COMMIT, after
              checkpoint, and after the engine is reopened from disk
  vector      1M x 768 loaded in 4 commits, checkpoint, Engine.open; then
              IVF-Flat built and searched through SQL by the re-opened
              engine, against numpy
  kernels     every Pallas kernel those phases traced is one that
              `ops/kernels.py` can choose, compiled for the chip, not
              interpreted; no fused program holds a `tpu_custom_call`

`--chips 4` runs only what exists across chips, each against one device:
the sharded IVF search (`SET ivf_shards = 4`) and Q1 under
`SET query_shards = 4` over a `PARTITION BY HASH ... SHARDS 4` lineitem.

Every phase holds the off-ramps shut (a fused fragment that fell back
to eager execution, a sharded fragment that degraded to one device) and
any failure ends the run with a non-zero exit.  The last line is
`{"ok": true, "device": {...}}` and is printed only on a TPU.  `--tiny`
rehearses the same code on the CPU at toy size; it never prints that
line and always exits 3.
"""

import argparse
import contextlib
import datetime
import json
import os
import shutil
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor
from decimal import Decimal
from fractions import Fraction

import numpy as np

K = 20
NPROBE = 8
UPSTREAM_RECALL = 0.86      # BASELINE.md: IVF-Flat, 1M x 768, nprobe 8
# Vectors: 1,024 centres ~ N(0, 1) per dimension, each row its centre plus
# N(0, SIGMA^2) noise; queries are fresh points drawn the same way.  At
# SIGMA 2.0 every query's true top-20 lie in its own generating cluster
# (numpy, 1M rows), so a neighbour that is missed was lost by the index
# (k-means lists that are not the clusters), never by the data.
SIGMA = 2.0
# Equality with the numpy search of the same lists is the correctness
# gate.  Recall is the index's quality, printed beside the upstream figure;
# its floor is where a loss of quality cannot be sampling noise: the
# sandbox CPU rehearsal of this recipe gave 0.89, and 64 queries that hit
# or miss a list together have a standard error near 0.035, so the floor
# is 0.89 - 4 x 0.035.
RECALL_FLOOR = 0.75
EPOCH = datetime.date(1970, 1, 1)

SIZES = {
    "full": dict(sf=1.0, lineitem_rows=6_001_215, commits=4, vectors=1_000_000,
                 dim=768, lists=1024, queries=64, vectors4=250_000,
                 lists4=256, sf4=1.0, lineitem_rows4=6_001_215),
    "tiny": dict(sf=0.01, lineitem_rows=60_012, commits=4, vectors=16_384,
                 dim=768, lists=16, queries=16, vectors4=16_384,
                 lists4=16, sf4=0.02,
                 lineitem_rows4=120_024),   # above dist_min_rows
}


def emit(**fields):
    print(json.dumps(fields), flush=True)


# ------------------------------------------------------------------ meters

class Meters:
    """Compile events (jax.monitoring), the off-ramp counters and device
    memory, read as deltas around a phase."""

    def __init__(self, jax):
        self.jax = jax
        self.compiles = []           # (seconds, fun_name) incl. cache hits
        self.cache_hits = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((seconds, kw.get("fun_name", "?")))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def n_compiled(self):
        """Programs the backend really compiled (persistent-cache hits
        pass through the same event and are taken off)."""
        return len(self.compiles) - self.cache_hits

    @staticmethod
    def offramps():
        from matrixone_tpu.utils import metrics as M
        return {
            'fusion_compile{outcome="trace_fail"}':
                M.fusion_compile.get(outcome="trace_fail"),
            'fusion_dispatch{kind="eager"}':
                M.fusion_dispatch.get(kind="eager"),
            "exchange_degrade": M.exchange_degrade.get(),
        }

    def memory(self):
        stats = [d.memory_stats() or {} for d in self.jax.devices()]
        return {"bytes_in_use": [s.get("bytes_in_use") for s in stats],
                "peak_bytes_in_use": [s.get("peak_bytes_in_use")
                                      for s in stats]}


@contextlib.contextmanager
def phase(meters, name):
    """Time a phase, count its compiles, hold its off-ramps shut and print
    its line.  The body fills the dict it is given; an exception in the
    body is not caught."""
    out = {}
    t0 = time.perf_counter()
    c0, h0, n0 = len(meters.compiles), meters.cache_hits, meters.n_compiled()
    ramps0 = meters.offramps()
    yield out
    ramps = {k: v - ramps0[k] for k, v in meters.offramps().items()}
    assert not any(ramps.values()), f"{name}: an off-ramp opened: {ramps}"
    emit(phase=name, seconds=time.perf_counter() - t0,
         compiles=meters.n_compiled() - n0,
         compile_cache_hits=meters.cache_hits - h0,
         compile_seconds=sum(s for s, _ in meters.compiles[c0:]),
         offramps=ramps, **out, **meters.memory())


# ------------------------------------------------------------------ device

def phase_device(jax, want, tiny):
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if tiny:
        if dev["platform"] != "cpu":
            sys.exit("--tiny is the CPU rehearsal; run it with "
                     "JAX_PLATFORMS=cpu")
    elif dev["platform"] != "tpu" or dev["count"] != want:
        sys.exit(f"chip_smoke.py needs {want} TPU device(s); jax reports "
                 f"{dev}")
    from importlib import metadata
    from matrixone_tpu import native
    from matrixone_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    emit(phase="device", **dev, jax=jax.__version__, libtpu=libtpu,
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         native_library_loaded=native.get_lib() is not None)
    return dev


# --------------------------------------------------------------------- f64

def phase_f64(meters, jax, seed):
    """A float64 array goes host -> device -> host; what comes back?"""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    mant = rng.uniform(1.0, 2.0, 4096)

    def trip(exp_lo, exp_hi):
        x = mant * np.exp2(rng.integers(exp_lo, exp_hi, 4096))
        y = np.asarray(jax.device_put(x))
        ok = np.isfinite(y)
        rel = np.abs(y[ok] - x[ok]) / np.abs(x[ok])
        return {"bit_exact_share": float(np.mean(
                    y.view(np.uint64) == x.view(np.uint64))),
                "max_rel_err": float(rel.max()) if ok.any() else None,
                "non_finite": int((~ok).sum())}, x

    with phase(meters, "f64") as out:
        out["f32_exponent_range"], x = trip(-100, 100)
        out["full_exponent_range"], big = trip(-1000, 1000)
        dsum = float(jnp.sum(jax.device_put(big)))
        out["device_sum_of_full_range"] = repr(dsum)
        out["host_sum_of_full_range"] = repr(float(big.sum()))
        i64 = rng.integers(-2**62, 2**62, 4096)
        out["int64_round_trip_exact"] = bool(
            (np.asarray(jax.device_put(i64)) == i64).all())
        out["device_dtype"] = str(jax.device_put(x).dtype)


# --------------------------------------------------------------------- sql

def _money(cents, scale=2):
    return Decimal(int(cents)).scaleb(-scale)


def _date(days):
    return (EPOCH + datetime.timedelta(days=int(days))).isoformat()


def _days(y, m, d):
    return (datetime.date(y, m, d) - EPOCH).days


def ref_q1(li):
    """TPC-H Q1 in int64: money is cents, so x*(1-d) carries scale 4 and
    x*(1-d)*(1+t) scale 6."""
    import pandas as pd
    m = li["l_shipdate"] <= _days(1998, 12, 1) - 90
    ext, disc, tax = (li[c][m].astype(np.int64) for c in
                      ("l_extendedprice", "l_discount", "l_tax"))
    df = pd.DataFrame({
        "rf": li["l_returnflag"][m], "ls": li["l_linestatus"][m],
        "qty": li["l_quantity"][m], "ext": ext, "disc": disc,
        "dp": ext * (100 - disc), "ch": ext * (100 - disc) * (100 + tax)})
    g = df.groupby(["rf", "ls"], sort=True).agg(
        qty=("qty", "sum"), ext=("ext", "sum"), dp=("dp", "sum"),
        ch=("ch", "sum"), disc=("disc", "sum"), n=("qty", "size"))
    return [(rf, ls, _money(r.qty), _money(r.ext), _money(r.dp, 4),
             _money(r.ch, 6), Fraction(int(r.qty), 100 * int(r.n)),
             Fraction(int(r.ext), 100 * int(r.n)),
             Fraction(int(r.disc), 100 * int(r.n)), int(r.n))
            for (rf, ls), r in g.iterrows()]


def check_q1(rows, want):
    """Keys, the four DECIMAL sums and the count must be equal.  AVG of a
    DECIMAL is a DOUBLE in this engine, so it is held to the exact
    quotient within 1e-9; the largest error seen is returned."""
    assert len(rows) == len(want), (len(rows), len(want))
    worst = 0.0
    for got, ref in zip(rows, want):
        assert got[:2] == ref[:2], (got, ref)
        assert [Decimal(v) for v in got[2:6]] == list(ref[2:6]), (got, ref)
        assert int(got[9]) == ref[9], (got, ref)
        for v, exact in zip(got[6:9], ref[6:9]):
            err = abs(Fraction(v) - exact) / exact
            assert err < Fraction(1, 10**9), (got, ref)
            worst = max(worst, float(err))
    return worst


def ref_q6(li):
    qty, disc = li["l_quantity"], li["l_discount"]
    m = ((li["l_shipdate"] >= _days(1994, 1, 1))
         & (li["l_shipdate"] < _days(1995, 1, 1))
         & (disc >= 5) & (disc <= 7) & (qty < 2400))
    rev = int((li["l_extendedprice"][m].astype(np.int64)
               * disc[m].astype(np.int64)).sum())
    return [(_money(rev, 4),)]


def ref_q3(tables):
    cu, od, li = tables["customer"], tables["orders"], tables["lineitem"]
    cut = _days(1995, 3, 15)
    building = np.zeros(len(cu["c_custkey"]) + 1, bool)
    building[cu["c_custkey"][cu["c_mktsegment"] == "BUILDING"]] = True
    n_ord = len(od["o_orderkey"])            # o_orderkey is 1..n_ord
    order_ok = np.zeros(n_ord + 1, bool)
    order_ok[od["o_orderkey"][(od["o_orderdate"] < cut)
                              & building[od["o_custkey"]]]] = True
    m = (li["l_shipdate"] > cut) & order_ok[li["l_orderkey"]]
    rev = np.zeros(n_ord + 1, np.int64)
    np.add.at(rev, li["l_orderkey"][m],
              li["l_extendedprice"][m].astype(np.int64)
              * (100 - li["l_discount"][m].astype(np.int64)))
    keys = np.flatnonzero(np.bincount(li["l_orderkey"][m],
                                      minlength=n_ord + 1))
    odate = od["o_orderdate"][keys - 1]
    top = np.lexsort((odate, -rev[keys]))[:10]
    return [(int(k), _money(rev[k], 4), _date(od["o_orderdate"][k - 1]),
             int(od["o_shippriority"][k - 1])) for k in keys[top]]


def check_rows(rows, want, types):
    got = [tuple(t(v) for t, v in zip(types, r)) for r in rows]
    assert got == want, (got, want)


def timed_query(meters, conn, sql):
    n0, t0 = meters.n_compiled(), time.perf_counter()
    _, rows = conn.query(sql)
    return rows, time.perf_counter() - t0, meters.n_compiled() - n0


def run_query(meters, conn, sql, check):
    """Cold then warm; both answers checked."""
    cold, t_cold, n_cold = timed_query(meters, conn, sql)
    extra = check(cold)
    warm, t_warm, n_warm = timed_query(meters, conn, sql)
    check(warm)
    out = {"rows": len(cold), "exact": True, "cold_seconds": t_cold,
           "warm_seconds": t_warm, "cold_compiles": n_cold,
           "warm_compiles": n_warm}
    if extra is not None:
        out["avg_columns_max_rel_err_vs_exact_quotient"] = extra
    return out


def device_tier():
    from matrixone_tpu.storage import blockcache
    st = blockcache.CACHE.stats()["device_tier"]
    return {k: st[k] for k in ("used_bytes", "peak_bytes", "budget_bytes",
                               "evictions", "hits", "misses",
                               "uploaded_bytes")}


def phase_sql(meters, state, size, seed):
    """Leaves the reopened engine and its server in `state`."""
    from matrixone_tpu import client
    from matrixone_tpu.frontend.server import MOServer
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import LocalFS
    from matrixone_tpu.utils import tpch_full as T
    with phase(meters, "sql") as out:
        t0 = time.perf_counter()
        tables = T.gen_tpch(size["sf"], seed,
                            lineitem_rows=size["lineitem_rows"])
        out["generate_seconds"] = time.perf_counter() - t0
        out["tables_generated"] = "all eight, every column"
        out["rows"] = {t: len(next(iter(cols.values())))
                       for t, cols in tables.items()}
        eng = Engine(LocalFS(state["workdir"]))
        t0 = time.perf_counter()
        T.load_tpch(eng, tables=tables, commits=size["commits"])
        out["load_seconds"] = time.perf_counter() - t0
        out["commits_per_table"] = size["commits"]
        t0 = time.perf_counter()
        eng.checkpoint()
        eng.close()
        eng = state["eng"] = Engine.open(LocalFS(state["workdir"]))
        out["checkpoint_and_reopen_seconds"] = time.perf_counter() - t0
        srv = state["srv"] = MOServer(engine=eng, port=0).start()
        conn = client.connect(port=srv.port, timeout=3600.0)
        _, n = conn.query("select count(*) from lineitem")
        assert int(n[0][0]) == out["rows"]["lineitem"], n
        want1, want6, want3 = (ref_q1(tables["lineitem"]),
                               ref_q6(tables["lineitem"]), ref_q3(tables))
        out["q1"] = run_query(meters, conn, T.QUERIES[1],
                              lambda rows: check_q1(rows, want1))
        out["q6"] = run_query(meters, conn, T.QUERIES[6],
                              lambda rows: check_rows(rows, want6,
                                                      (Decimal,)))
        out["q3"] = run_query(meters, conn, T.QUERIES[3],
                              lambda rows: check_rows(
                                  rows, want3, (int, Decimal, str, int)))
        conn.close()
        out["device_tier"] = device_tier()
        from matrixone_tpu.utils import metrics as M
        out["fusion_exec"] = {m: M.fusion_exec.get(mode=m) for m in
                              ("fused", "eager", "fallback", "degraded")}
        out["fusion_dispatch_step"] = M.fusion_dispatch.get(kind="step")


# -------------------------------------------------------------- write_read

def phase_write_read(meters, state):
    """The guarantee: a write acknowledged by COMMIT is read back, by
    another connection, now, after a checkpoint and after a restart."""
    from matrixone_tpu import client
    from matrixone_tpu.frontend.server import MOServer
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import LocalFS
    want = {i: (i * 7, f"row{i}") for i in range(100)}
    for i in range(30):
        want[i] = (want[i][0] + 1000, "updated")
    for i in range(90, 100):
        del want[i]
    want = [(i, v, s) for i, (v, s) in sorted(want.items())]

    def read_back(port):
        c = client.connect(port=port, timeout=600.0)
        _, rows = c.query("select id, v, s from wr order by id")
        c.close()
        got = [(int(i), int(v), s) for i, v, s in rows]
        assert got == want, (got[:5], want[:5], len(got), len(want))
        return True

    with phase(meters, "write_read") as out:
        srv = state["srv"]
        w = client.connect(port=srv.port, timeout=600.0)
        w.execute("create table wr (id bigint primary key, v bigint,"
                  " s varchar(16))")
        w.execute("begin")
        n_ins = w.execute("insert into wr values " + ", ".join(
            f"({i}, {i * 7}, 'row{i}')" for i in range(100)))
        n_upd = w.execute("update wr set v = v + 1000, s = 'updated'"
                          " where id < 30")
        n_del = w.execute("delete from wr where id >= 90")
        w.execute("commit")
        out["acknowledged"] = {"inserted": n_ins, "updated": n_upd,
                               "deleted": n_del}
        assert (n_ins, n_upd, n_del) == (100, 30, 10), out["acknowledged"]
        out["read_back_after_commit"] = read_back(srv.port)
        w.query("select mo_ctl('checkpoint')")
        w.close()
        out["read_back_after_checkpoint"] = read_back(srv.port)
        srv.stop()
        state["eng"].close()
        state["eng"] = Engine.open(LocalFS(state["workdir"]))
        state["srv"] = MOServer(engine=state["eng"], port=0).start()
        out["read_back_after_reopen"] = read_back(state["srv"].port)
        out["rows"] = len(want)


# ------------------------------------------------------------------ vector

def make_vectors(seed, n, dim, centres, n_queries):
    """Seeded clustered vectors (see SIGMA), generated in parallel blocks
    so that the block seeds, not the thread schedule, fix the data."""
    root = np.random.default_rng(seed)
    cent = root.standard_normal((centres, dim), dtype=np.float32)
    labels = root.integers(0, centres, n)
    x = np.empty((n, dim), np.float32)
    step = 1 << 16

    def fill(lo):
        hi = min(n, lo + step)
        noise = np.random.default_rng([seed, lo]).standard_normal(
            (hi - lo, dim), dtype=np.float32)
        x[lo:hi] = cent[labels[lo:hi]] + SIGMA * noise

    with ThreadPoolExecutor(os.cpu_count() or 4) as pool:
        list(pool.map(fill, range(0, n, step)))
    q = (cent[root.integers(0, centres, n_queries)] + SIGMA
         * root.standard_normal((n_queries, dim), dtype=np.float32))
    return x, q


def brute_force_topk(x, q):
    """Exact top-K ids per query: a float32 matmul picks 4K candidates,
    which are then ranked by float64 distances."""
    d32 = np.einsum("nd,nd->n", x, x)[None, :] - 2.0 * (q @ x.T)
    cand = np.argpartition(d32, 4 * K, axis=1)[:, :4 * K]
    out = []
    for qv, c in zip(q.astype(np.float64), cand):
        d = ((x[c].astype(np.float64) - qv) ** 2).sum(1)
        out.append(c[np.argsort(d, kind="stable")[:K]])
    return np.stack(out)


def vec_literal(v):
    return "[" + ",".join(repr(float(f)) for f in v) + "]"


def load_vectors(conn, eng, table, x, commits):
    conn.execute(f"create table {table} (id bigint primary key,"
                 f" v vecf32({x.shape[1]}))")
    t = eng.get_table(table)
    bounds = np.linspace(0, len(x), commits + 1).astype(np.int64)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        t.insert_numpy({"id": np.arange(lo, hi, dtype=np.int64),
                        "v": x[lo:hi]})


def search_sql(conn, table, queries):
    """ids per query through SQL, and the seconds each took."""
    ids, secs = [], []
    for qv in queries:
        t0 = time.perf_counter()
        _, rows = conn.query(
            f"select id from {table} order by "
            f"l2_distance(v, '{vec_literal(qv)}') limit {K}")
        secs.append(time.perf_counter() - t0)
        ids.append([int(r[0]) for r in rows])
    return ids, secs


def same_lists_reference(index, x, queries):
    """numpy search of the lists the index probes: the NPROBE lists whose
    centroids are nearest, every member scored exactly.  -> per query
    (ids of the top K, distance of the K-th, {id: distance})."""
    cents = np.asarray(index.centroids, np.float64)
    offs = np.asarray(index.offsets)
    members = np.asarray(index.ids)        # row position == id, see load
    out = []
    for qv in queries:
        cd = ((cents - qv.astype(np.float64)) ** 2).sum(1)
        probed = np.argsort(cd, kind="stable")[:NPROBE]
        cand = np.concatenate([members[offs[c]:offs[c + 1]]
                               for c in probed])
        d = ((x[cand].astype(np.float64) - qv.astype(np.float64)) ** 2
             ).sum(1)
        order = np.argsort(d, kind="stable")[:K]
        out.append((cand[order].tolist(), float(d[order[-1]]),
                    dict(zip(cand.tolist(), d.tolist()))))
    return out


def check_same_lists(got_ids, ref):
    """Equal id sets; where they differ the swapped ids must be distance
    ties at the K-th place (within float32 resolution of the distance the
    server ranks by).  -> number of queries that needed the tie rule."""
    tie_queries = 0
    for ids, (want, kth, dist) in zip(got_ids, ref):
        assert len(ids) == len(want) == K, (len(ids), len(want))
        if set(ids) == set(want):
            continue
        tie_queries += 1
        for i in set(ids) ^ set(want):
            assert i in dist, f"id {i} is in none of the probed lists"
            assert abs(dist[i] - kth) <= 1e-5 * kth, (i, dist[i], kth)
    return tie_queries


def phase_vector(meters, state, size, seed):
    from matrixone_tpu import client
    from matrixone_tpu.frontend.server import MOServer
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import LocalFS
    srv, eng = state["srv"], state["eng"]
    with phase(meters, "vector") as out:
        n, dim, lists = size["vectors"], size["dim"], size["lists"]
        t0 = time.perf_counter()
        x, queries = make_vectors(seed, n, dim, lists, size["queries"])
        truth = brute_force_topk(x, queries)
        out["generate_and_brute_force_seconds"] = time.perf_counter() - t0
        out.update(vectors=n, dim=dim, lists=lists, nprobe=NPROBE, k=K,
                   queries=len(queries), sigma=SIGMA)
        conn = client.connect(port=srv.port, timeout=3600.0)
        t0 = time.perf_counter()
        load_vectors(conn, eng, "docs", x, commits=4)
        out["load_seconds"] = time.perf_counter() - t0
        # the state every deployment is in after its first restart: the
        # index is built, and every search answered, by a re-opened engine
        t0 = time.perf_counter()
        conn.query("select mo_ctl('checkpoint')")
        conn.close()
        srv.stop()
        eng.close()
        eng = state["eng"] = Engine.open(LocalFS(state["workdir"]))
        srv = state["srv"] = MOServer(engine=eng, port=0).start()
        out["checkpoint_reopen_seconds"] = time.perf_counter() - t0
        conn = client.connect(port=srv.port, timeout=3600.0)
        _, rows = conn.query("select count(*) from docs")
        assert int(rows[0][0]) == n, (rows, n)
        out["rows_read_back_after_reopen"] = n
        t0 = time.perf_counter()
        conn.execute(f"create index docs_v using ivfflat on docs (v) "
                     f"lists = {lists} op_type = 'vector_l2_ops'")
        conn.execute(f"set ivf_nprobe = {NPROBE}")
        _, plan = conn.query(
            f"explain select id from docs order by "
            f"l2_distance(v, '{vec_literal(queries[0])}') limit {K}")
        plan = "\n".join(r[0] for r in plan)
        assert "VectorTopK" in plan and "docs_v" in plan, plan
        out["explain_shows_index_scan"] = True
        ids, secs = search_sql(conn, "docs", queries)
        out["build_and_first_query_seconds"] = (time.perf_counter() - t0
                                                - sum(secs[1:]))
        ids, secs = search_sql(conn, "docs", queries)     # warm
        conn.close()
        out["median_query_seconds"] = float(np.median(secs))
        index = eng.indexes["docs_v"].index_obj
        out["index_lists"] = int(index.nlist)
        out["queries_needing_tie_rule"] = check_same_lists(
            ids, same_lists_reference(index, x, queries))
        out["ids_equal_numpy_search_of_same_lists"] = True
        recall = float(np.mean([len(set(a) & set(b.tolist())) / K
                                for a, b in zip(ids, truth)]))
        out.update(recall_at_20=recall, recall_floor=RECALL_FLOOR,
                   upstream_recall_at_20=UPSTREAM_RECALL)
        assert recall >= RECALL_FLOOR, (recall, RECALL_FLOOR)


# ----------------------------------------------------------------- kernels

def phase_kernels(meters, tiny):
    """The Pallas kernels that sql and vector traced into their programs:
    none but those `ops/kernels.py` can choose, compiled, not interpreted;
    and no fused program holds one (nothing a fused fragment calls has a
    kernel)."""
    from matrixone_tpu.ops import kernels as HK
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.vm import fusion as FF
    with phase(meters, "kernels") as out:
        traced = [v["labels"] for v in M.pallas_traces.snapshot()["values"]]
        out["traced_by_sql_and_vector"] = traced
        out["interpret"] = HK.interpret()
        assert {t["kernel"] for t in traced} <= {"adc_score_pallas"}, traced
        if not tiny:
            assert not HK.interpret()
            assert all(t["interpret"] == "False" for t in traced), traced
        texts = [c.as_text() for e in FF.CACHE._lru.snapshot()
                 for c in e["compiled"].values()]
        out["fused_programs"] = len(texts)
        out["fused_programs_with_tpu_custom_call"] = sum(
            "tpu_custom_call" in t for t in texts)
        assert texts and not out["fused_programs_with_tpu_custom_call"]


# --------------------------------------------------------------- four chips

LINEITEM_DDL = """create table lineitem (
  l_orderkey bigint, l_partkey bigint, l_suppkey bigint, l_linenumber bigint,
  l_quantity decimal(15,2), l_extendedprice decimal(15,2),
  l_discount decimal(15,2), l_tax decimal(15,2),
  l_returnflag varchar(117), l_linestatus varchar(117),
  l_shipdate date, l_commitdate date, l_receiptdate date,
  l_shipinstruct varchar(117), l_shipmode varchar(117),
  l_comment varchar(117), primary key (l_orderkey, l_linenumber))
  partition by hash(l_orderkey) shards 4"""


def phase_sharded_vector(meters, jax, state, size, seed):
    from matrixone_tpu import client
    srv, eng = state["srv"], state["eng"]
    with phase(meters, "sharded_vector") as out:
        n, dim, lists = size["vectors4"], size["dim"], size["lists4"]
        x, queries = make_vectors(seed, n, dim, lists, size["queries"])
        out.update(vectors=n, dim=dim, lists=lists, nprobe=NPROBE, k=K,
                   queries=len(queries))
        conn = client.connect(port=srv.port, timeout=3600.0)
        load_vectors(conn, eng, "docs", x, commits=4)
        conn.execute(f"create index docs_v using ivfflat on docs (v) "
                     f"lists = {lists} op_type = 'vector_l2_ops'")
        conn.execute(f"set ivf_nprobe = {NPROBE}")
        one, _ = search_sql(conn, "docs", queries)
        out["bytes_in_use_after_one_device_search"] = \
            meters.memory()["bytes_in_use"]
        conn.execute("set ivf_shards = 4")
        four, _ = search_sql(conn, "docs", queries)
        four, secs = search_sql(conn, "docs", queries)
        conn.close()
        ix = eng.indexes["docs_v"]
        assert ix.options["_sharded"][1] == 4, "the search did not shard"
        sidx = ix.options["_sharded"][2]
        by_dev = {}
        for arr in jax.tree.leaves(sidx):
            for sh in arr.addressable_shards:
                by_dev[sh.device.id] = (by_dev.get(sh.device.id, 0)
                                        + sh.data.nbytes)
        out["sharded_index_bytes_by_device"] = [
            by_dev[d.id] for d in jax.devices()]
        assert four == one, "sharded ids differ from the one-device search"
        out["ids_equal_one_device_search"] = True
        out["median_query_seconds_sharded"] = float(np.median(secs))


def phase_sharded_sql(meters, state, size, seed):
    from matrixone_tpu import client
    from matrixone_tpu.parallel import merge_exec
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.utils import tpch_full as T
    srv, eng = state["srv"], state["eng"]
    with phase(meters, "sharded_sql") as out:
        tables = T.gen_tpch(size["sf4"], seed,
                            lineitem_rows=size["lineitem_rows4"])
        li = tables["lineitem"]
        conn = client.connect(port=srv.port, timeout=3600.0)
        conn.execute(LINEITEM_DDL)
        T.load_tpch(eng, tables={"lineitem": li}, commits=8)
        out["lineitem_rows"] = len(li["l_orderkey"])
        want = ref_q1(li)
        single = run_query(meters, conn, T.QUERIES[1],
                           lambda rows: check_q1(rows, want))
        one_rows = conn.query(T.QUERIES[1])[1]
        conn.execute("set query_shards = 4")
        _, plan = conn.query("explain " + T.QUERIES[1])
        out["explain_sharded"] = [r[0] for r in plan][:4]
        merges0 = merge_exec._MERGE_CALLS["count"]
        partial0 = sum(v["value"] for v in
                       M.exchange_partial_merge.snapshot()["values"])
        sharded = run_query(meters, conn, T.QUERIES[1],
                            lambda rows: check_q1(rows, want))
        four_rows = conn.query(T.QUERIES[1])[1]
        conn.close()
        partials = sum(v["value"] for v in
                       M.exchange_partial_merge.snapshot()["values"]
                       ) - partial0
        assert partials == 3, f"{partials} sharded executions of 3 queries"
        out["merge_dispatches_per_query"] = (
            merge_exec._MERGE_CALLS["count"] - merges0) / 3
        assert out["merge_dispatches_per_query"] == 1, out
        assert four_rows == one_rows, (four_rows, one_rows)
        out.update(one_device=single, four_shards=sharded,
                   rows_equal=True, degraded=False,
                   q3="not run on four chips: left to ROADMAP B6")


# -------------------------------------------------------------------- main

def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy size; always exits 3")
    args = ap.parse_args()
    import jax
    import matrixone_tpu  # noqa: F401  (enables x64)
    t_start = time.perf_counter()
    dev = phase_device(jax, args.chips, args.tiny)
    meters = Meters(jax)
    size = SIZES["tiny" if args.tiny else "full"]
    state = {"workdir": tempfile.mkdtemp(prefix="mo_chip_smoke_")}
    try:
        if args.chips == 4:
            from matrixone_tpu.frontend.server import MOServer
            from matrixone_tpu.storage.engine import Engine
            from matrixone_tpu.storage.fileservice import LocalFS
            state["eng"] = Engine(LocalFS(state["workdir"]))
            state["srv"] = MOServer(engine=state["eng"], port=0).start()
            phase_sharded_vector(meters, jax, state, size, args.seed)
            phase_sharded_sql(meters, state, size, args.seed)
        else:
            phase_f64(meters, jax, args.seed)
            phase_sql(meters, state, size, args.seed)
            phase_write_read(meters, state)
            phase_vector(meters, state, size, args.seed)
            phase_kernels(meters, args.tiny)
    finally:
        if "srv" in state:
            state["srv"].stop()
        shutil.rmtree(state["workdir"], ignore_errors=True)
    emit(phase="total", seconds=time.perf_counter() - t_start,
         compiles=meters.n_compiled(), compile_cache_hits=meters.cache_hits,
         compile_seconds=sum(s for s, _ in meters.compiles),
         slowest_compiles=[[round(s, 1), f] for s, f in
                           sorted(meters.compiles, reverse=True)[:8]],
         **meters.memory())
    if args.tiny:
        emit(ok=False, rehearsal="cpu, toy size: not a chip run", device=dev)
        sys.exit(3)
    emit(ok=True, device=dev)


if __name__ == "__main__":
    main()
