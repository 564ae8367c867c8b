"""Cross-CN hash-repartition (shuffle) joins — VERDICT r4 Next #4.

Reference analogue: plan/shuffle.go determineShuffleMethod +
colexec/shuffle + colexec/dispatch: when BOTH join sides are big, the
rows of each side are hash-partitioned by join key across the peers
(direct peer-to-peer pushes, not through the coordinator), each peer
joins its bucket locally, and the coordinator concatenates — no side is
ever broadcast or fully replicated in any single executor's working set.
"""

import numpy as np
import pytest

from matrixone_tpu.cluster.cn import FragmentServer
from matrixone_tpu.frontend import Session
from matrixone_tpu.storage.engine import Engine


@pytest.fixture(scope="module")
def rig():
    eng = Engine()
    s = Session(catalog=eng)
    s.execute("create table fact (id bigint primary key, k bigint,"
              " v bigint)")
    s.execute("create table dim (k bigint primary key, nm varchar(8),"
              " w bigint)")
    rng = np.random.default_rng(17)
    vals = ",".join(f"({i},{rng.integers(0, 800)},{rng.integers(0, 50)})"
                    for i in range(6000))
    s.execute("insert into fact values " + vals)
    vals = ",".join(f"({k},'n{k % 37}',{k % 11})" for k in range(800))
    s.execute("insert into dim values " + vals)
    f1 = FragmentServer(eng).start()
    f2 = FragmentServer(eng).start()
    f3 = FragmentServer(eng).start()
    eng.dist_peers = [f"127.0.0.1:{f.port}" for f in (f1, f2, f3)]
    sd = Session(catalog=eng)
    sd.variables["dist_min_rows"] = 0
    sd.variables["dist_batch_rows"] = 1024
    yield eng, s, sd, (f1, f2, f3)
    for f in (f1, f2, f3):
        f.stop()


def _both(rig, sql):
    eng, s, sd, frags = rig
    local = s.execute(sql).rows()
    before = sum(f.frags_run for f in frags)
    dist = sd.execute(sql).rows()
    ran = sum(f.frags_run for f in frags) - before
    return local, dist, ran


def test_shuffle_join_exact_vs_local(rig):
    # no ORDER BY LIMIT / GROUP BY above the join: the shuffle-join
    # fragment kind is the only distribution that applies
    sql = ("select f.id, f.v, d.nm, d.w from fact f join dim d"
           " on f.k = d.k")
    local, dist, ran = _both(rig, sql)
    assert sorted(dist) == sorted(local)
    # 2n shuffle_scan fragments + n shuffle_join fragments
    assert ran == 9, f"expected full shuffle (frags_run delta {ran})"


def test_shuffle_join_with_filters(rig):
    sql = ("select f.id, d.nm from fact f join dim d on f.k = d.k"
           " where f.v >= 25 and d.w <= 5")
    local, dist, ran = _both(rig, sql)
    assert sorted(dist) == sorted(local)
    assert ran == 9


def test_shuffle_join_under_aggregate(rig):
    sql = ("select d.nm, count(*), sum(f.v) from fact f join dim d"
           " on f.k = d.k group by d.nm order by d.nm")
    local, dist, _ = _both(rig, sql)
    assert dist == local


def test_small_tables_stay_local(rig):
    eng, s, sd, frags = rig
    sd.variables["dist_min_rows"] = 10_000_000
    try:
        sql = "select f.id from fact f join dim d on f.k = d.k"
        before = sum(f.frags_run for f in frags)
        assert sorted(sd.execute(sql).rows()) == \
            sorted(s.execute(sql).rows())
        assert sum(f.frags_run for f in frags) == before
    finally:
        sd.variables["dist_min_rows"] = 0


# ---------------------------------------------------------------- process
def test_shuffle_join_across_cn_processes(tmp_path):
    """The VERDICT r4 acceptance drill: two tables joined across 2 REAL
    CN processes. The CNs bootstrap from the TN checkpoint, so their
    segments are object-backed views (metadata + block cache) — no CN
    holds a full replica of either table in RAM; the join repartitions
    both sides peer-to-peer by key hash."""
    import os
    import socket
    import subprocess
    import sys

    from matrixone_tpu import client
    from matrixone_tpu.cluster import RemoteCatalog, TNService
    from matrixone_tpu.frontend import Session

    shared = str(tmp_path / "store")
    tn = TNService(data_dir=shared).start()
    cat = RemoteCatalog(("127.0.0.1", tn.port), data_dir=shared)
    s = Session(catalog=cat)
    s.execute("create table fa (id bigint primary key, k bigint,"
              " v bigint)")
    s.execute("create table di (k bigint primary key, w bigint)")
    rng = np.random.default_rng(3)
    s.execute("insert into fa values " + ",".join(
        f"({i},{rng.integers(0, 200)},{rng.integers(0, 9)})"
        for i in range(3000)))
    s.execute("insert into di values " + ",".join(
        f"({k},{k % 13})" for k in range(200)))
    oracle = s.execute("select f.id, f.v, d.w from fa f join di d"
                       " on f.k = d.k").rows()
    # checkpoint through the TN so CNs bootstrap object-backed
    cat.merge_table("fa", min_segments=1)
    cat.merge_table("di", min_segments=1)

    def free_port():
        sk = socket.socket()
        sk.bind(("127.0.0.1", 0))
        p = sk.getsockname()[1]
        sk.close()
        return p

    fps = [free_port(), free_port()]
    peers = ",".join(f"127.0.0.1:{p}" for p in fps)
    cns = []
    try:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"        # not the (possibly wedged)
        for fp in fps:
            p = subprocess.Popen(
                [sys.executable, "-m", "matrixone_tpu.cluster.cn",
                 "--tn", f"127.0.0.1:{tn.port}", "--dir", shared,
                 "--frag-port", str(fp), "--peers", peers],
                stdout=subprocess.PIPE, env=env, text=True)
            port = int(p.stdout.readline().split()[1])
            p.stdout.readline()          # FRAGPORT line
            cns.append((p, port))
        # generous timeout: each cold CN jit-compiles its first scans
        c = client.connect(port=cns[0][1], timeout=300.0)
        c.execute("set dist_min_rows = 100")
        _, rows = c.query("select f.id, f.v, d.w from fa f join di d"
                          " on f.k = d.k")
        got = sorted((int(a), int(b), int(cc)) for a, b, cc in rows)
        assert got == sorted((int(a), int(b), int(cc))
                             for a, b, cc in oracle)
        c.close()
    finally:
        for p, _ in cns:
            p.kill()
        cat.close()
        tn.stop()


def test_mixed_width_keys_and_negative(rig):
    """code-review r5: int32-vs-int64 key columns must hash to the same
    buckets (pandas hash_array is width-sensitive; keys normalize to
    int64 first). Negative keys included."""
    eng, s, sd, frags = rig
    s.execute("create table l32 (id bigint primary key, k int)")
    s.execute("create table r64 (k bigint primary key, w bigint)")
    s.execute("insert into l32 values " + ",".join(
        f"({i},{(i % 40) - 20})" for i in range(1200)))
    s.execute("insert into r64 values " + ",".join(
        f"({k},{k * 7})" for k in range(-20, 20)))
    sql = "select l.id, r.w from l32 l join r64 r on l.k = r.k"
    local = sorted(s.execute(sql).rows())
    before = sum(f.frags_run for f in frags)
    dist = sorted(sd.execute(sql).rows())
    ran = sum(f.frags_run for f in frags) - before
    assert ran == 9, f"not distributed ({ran})"
    assert dist == local and len(dist) == 1200
