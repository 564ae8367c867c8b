"""CN/TN split (VERDICT r2 #2): TN owns storage+commit, stateless CNs
apply the logtail push stream and serve snapshot reads locally.

Reference analogue: disttae/logtail_consumer.go:296 (PushClient apply
loop), tae/logtail/service/server.go:192 (push server), tae/rpc/
handle.go:547 (CN commits over RPC). Covered here:

  * in-process: snapshot isolation across 2 CNs, read path never RPCs,
    TN-allocated auto_increment, cross-CN conflict/duplicate errors,
    merge resync;
  * process-level: TN process + 2 CN processes serving the MySQL wire —
    INSERT via CN1 visible via CN2; TN kill -9 + restart on the same
    port replays the WAL and both CNs resubscribe and continue.
"""

import os
import subprocess
import sys
import tempfile
import time

import pytest

from matrixone_tpu import client
from matrixone_tpu.cluster import RemoteCatalog, TNService
from matrixone_tpu.frontend import Session
from matrixone_tpu.storage.engine import ConflictError, DuplicateKeyError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------- in-process
@pytest.fixture
def tn_pair():
    d = tempfile.mkdtemp(prefix="mo_cntn_")
    tn = TNService(data_dir=d).start()
    cat1 = RemoteCatalog(("127.0.0.1", tn.port), data_dir=d)
    cat2 = RemoteCatalog(("127.0.0.1", tn.port), data_dir=d)
    yield tn, cat1, cat2
    cat1.close()
    cat2.close()
    tn.stop()


def _sync(*cats):
    ts = max(c.committed_ts for c in cats)
    for c in cats:
        c.consumer.wait_ts(ts)


def test_replica_ddl_gen_tracks_catalog_shape_ops(tn_pair):
    """Stage/publication/source/dynamic/snapshot DDL must advance the
    REPLICA's ddl_gen through the logtail apply path, not only the
    TN's — a CN plan/result cache pinned to a stale gen would keep
    resolving the pre-DDL stage URL / publication set (the replica-side
    hole molint's cache-invalidation rule flagged)."""
    tn, cat1, cat2 = tn_pair
    s1 = Session(catalog=cat1)
    s1.execute("create table pt (id bigint primary key)")
    _sync(cat1, cat2)
    for ddl in ("create stage st9 url='file:///tmp/st9'",
                "drop stage st9",
                "create publication p9 table pt",
                "drop publication p9",
                "create snapshot sn9"):
        g2 = cat2.ddl_gen
        s1.execute(ddl)
        # _ddl blocks until CN1's replica applied; CN2 may lag behind
        cat2.consumer.wait_ts(cat1.consumer.applied_ts)
        assert cat2.ddl_gen > g2, \
            f"replica ddl_gen did not advance on {ddl!r}"


def test_cross_cn_visibility_and_snapshots(tn_pair):
    tn, cat1, cat2 = tn_pair
    s1, s2 = Session(catalog=cat1), Session(catalog=cat2)
    s1.execute("create table t (id bigint primary key, x bigint)")
    s1.execute("insert into t values (1,10),(2,20)")
    _sync(cat1, cat2)

    # open txn on CN2 pins its snapshot: a later CN1 commit is invisible
    s2.execute("begin")
    assert len(s2.execute("select * from t").rows()) == 2
    s1.execute("insert into t values (3,30)")
    assert len(s2.execute("select * from t").rows()) == 2
    s2.execute("commit")
    _sync(cat1, cat2)
    assert len(s2.execute("select * from t").rows()) == 3


def test_cn_read_path_never_rpcs(tn_pair):
    tn, cat1, cat2 = tn_pair
    s1, s2 = Session(catalog=cat1), Session(catalog=cat2)
    s1.execute("create table t (id bigint primary key, v varchar(8))")
    s1.execute("insert into t values (1,'a'),(2,'b')")
    _sync(cat1, cat2)
    # count TN round-trips during reads on CN2 (the subscribe stream is a
    # different socket — _TNClient.call is the only request/response path)
    calls = {"n": 0}
    orig = cat2._client.call

    def counted(header, blob=b""):
        calls["n"] += 1
        return orig(header, blob)
    cat2._client.call = counted
    rows = s2.execute("select id, v from t order by id").rows()
    assert [(int(a), b) for a, b in rows] == [(1, "a"), (2, "b")]
    s2.execute("select count(*) from t where id > 0")
    assert calls["n"] == 0, "CN read path must not touch the TN"


def test_cross_cn_auto_increment_and_conflicts(tn_pair):
    tn, cat1, cat2 = tn_pair
    s1, s2 = Session(catalog=cat1), Session(catalog=cat2)
    s1.execute("create table a (id bigint primary key auto_increment,"
               " v bigint)")
    for i in range(4):
        s1.execute(f"insert into a (v) values ({i})")
        s2.execute(f"insert into a (v) values ({100 + i})")
    _sync(cat1, cat2)
    ids = sorted(int(r[0]) for r in
                 s1.execute("select id from a").rows())
    assert len(ids) == len(set(ids)) == 8, ids

    s1.execute("create table t (id bigint primary key, x bigint)")
    s1.execute("insert into t values (1,1),(2,2),(3,3)")
    _sync(cat1, cat2)
    s1.execute("begin")
    s2.execute("begin")
    s1.execute("delete from t where id = 3")
    s2.execute("delete from t where id = 3")
    s1.execute("commit")
    with pytest.raises(ConflictError):
        s2.execute("commit")
    with pytest.raises(DuplicateKeyError):
        s2.execute("insert into t values (1, 999)")


def test_merge_resync_rewrites_gids(tn_pair):
    tn, cat1, cat2 = tn_pair
    s1, s2 = Session(catalog=cat1), Session(catalog=cat2)
    s1.execute("create table t (id bigint primary key, x bigint)")
    s1.execute("insert into t values (1,1)")
    s1.execute("insert into t values (2,2)")
    s1.execute("insert into t values (3,3)")
    s1.execute("delete from t where id = 2")
    kept = cat1.merge_table("t")
    assert kept == 2
    deadline = time.time() + 10
    while time.time() < deadline:
        r2 = sorted(int(r[0]) for r in
                    s2.execute("select id from t").rows())
        if r2 == [1, 3]:
            break
        time.sleep(0.05)
    assert r2 == [1, 3]
    # deletes against post-merge gids must land on both replicas
    s2.execute("delete from t where id = 3")
    _sync(cat1, cat2)
    assert [int(r[0]) for r in
            s1.execute("select id from t").rows()] == [1]


def test_resubscribe_across_truncation_gap(tn_pair):
    """A CN whose subscription lapsed across a TN checkpoint (WAL
    truncated) must rebuild from the manifest, not silently serve a
    hole (reviewer finding: subscribe had no from_ts < ckpt_ts guard)."""
    tn, cat1, cat2 = tn_pair
    s1, s2 = Session(catalog=cat1), Session(catalog=cat2)
    s1.execute("create table g (id bigint primary key, v varchar(8))")
    s1.execute("insert into g values (1,'a')")
    _sync(cat1, cat2)
    # CN2 goes dark
    cat2.consumer.stop()
    time.sleep(1.2)          # let the consumer thread exit its loop
    # CN1 commits and the TN checkpoints: the gap records are truncated
    s1.execute("insert into g values (2,'b'), (3,'c')")
    s1.execute("delete from g where id = 1")
    cat1.checkpoint()
    # CN2 resubscribes from its stale applied_ts -> must full-resync
    from matrixone_tpu.cluster.cn import LogtailConsumer
    cat2.consumer = LogtailConsumer(cat2._replica,
                                    ("127.0.0.1", tn.port)).start()
    deadline = time.time() + 15
    while time.time() < deadline:
        rows = sorted(int(r[0]) for r in
                      s2.execute("select id from g").rows())
        if rows == [2, 3]:
            break
        time.sleep(0.1)
    assert rows == [2, 3], rows
    # and stays live after the resync
    s1.execute("insert into g values (4,'d')")
    _sync(cat1, cat2)
    assert sorted(int(r[0]) for r in
                  s2.execute("select id from g").rows()) == [2, 3, 4]


# ------------------------------------------------------- process-level
def _spawn(mod_args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen([sys.executable, "-m"] + mod_args,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.DEVNULL, env=env, text=True)
    port = None
    deadline = time.time() + 120
    while time.time() < deadline:
        line = p.stdout.readline()
        if line.startswith("PORT "):
            port = int(line.split()[1])
            break
    assert port, "subprocess did not report a port"
    return p, port


@pytest.fixture(scope="module")
def cluster_procs():
    d = tempfile.mkdtemp(prefix="mo_cluster_")
    tn, tn_port = _spawn(["matrixone_tpu.cluster.tn", "--dir", d,
                          "--port", "0"])
    cns = [_spawn(["matrixone_tpu.cluster.cn", "--tn",
                   f"127.0.0.1:{tn_port}", "--dir", d, "--port", "0"])
           for _ in range(2)]
    yield d, (tn, tn_port), cns
    for p, _ in cns + [(tn, tn_port)]:
        if p.poll() is None:
            p.kill()


def _poll_rows(conn, sql, want_n, timeout=20):
    deadline = time.time() + timeout
    while time.time() < deadline:
        _cols, rows = conn.query(sql)
        if len(rows) >= want_n:
            return rows
        time.sleep(0.1)
    raise AssertionError(f"never saw {want_n} rows for {sql!r}")


def test_two_cn_processes_over_mysql_wire(cluster_procs):
    d, (tn, tn_port), cns = cluster_procs
    c1 = client.connect(port=cns[0][1])
    c2 = client.connect(port=cns[1][1])
    c1.execute("create table w (id bigint primary key, v varchar(16))")
    c1.execute("insert into w values (1,'from-cn1'), (2,'x')")
    rows = _poll_rows(c2, "select id, v from w order by id", 2)
    assert [(int(a), b) for a, b in rows] == [(1, "from-cn1"), (2, "x")]
    # and the reverse direction
    c2.execute("insert into w values (3,'from-cn2')")
    rows = _poll_rows(c1, "select id from w order by id", 3)
    assert [int(r[0]) for r in rows] == [1, 2, 3]


def test_proxy_routes_sessions_to_cn_processes(cluster_procs):
    """Client -> proxy -> some CN -> TN commit -> logtail -> every CN:
    the reference deployment path (proxy + stateless CNs) end to end."""
    from matrixone_tpu.frontend.proxy import MOProxy
    d, (tn, tn_port), cns = cluster_procs
    proxy = MOProxy([("127.0.0.1", cns[0][1]),
                     ("127.0.0.1", cns[1][1])]).start()
    try:
        pa = client.connect(port=proxy.port)
        pb = client.connect(port=proxy.port)
        pa.execute("create table px (id bigint primary key, v bigint)")
        pa.execute("insert into px values (1, 1)")
        _poll_rows(pb, "select id from px", 1)
        pb.execute("insert into px values (2, 2)")
        rows = _poll_rows(pa, "select id from px order by id", 2)
        assert [int(r[0]) for r in rows] == [1, 2]
    finally:
        proxy.stop()


def test_tn_restart_replay_and_cn_resubscribe(cluster_procs):
    d, (tn, tn_port), cns = cluster_procs
    c1 = client.connect(port=cns[0][1])
    c2 = client.connect(port=cns[1][1])
    c1.execute("create table r (id bigint primary key, v bigint)")
    c1.execute("insert into r values (1, 10)")
    _poll_rows(c2, "select * from r", 1)

    tn.kill()
    tn.wait()
    # the WAL is durable before commit acks, so a kill -9 TN restart
    # replays everything acked; the port may linger in TIME_WAIT briefly
    tn2 = None
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            tn2, _ = _spawn(["matrixone_tpu.cluster.tn", "--dir", d,
                             "--port", str(tn_port)])
            break
        except AssertionError:
            time.sleep(0.5)
    assert tn2 is not None

    # both CNs must resubscribe and serve new writes end-to-end
    c1b = client.connect(port=cns[0][1])
    deadline = time.time() + 60
    ok = False
    while time.time() < deadline:
        try:
            c1b.execute("insert into r values (2, 20)")
            ok = True
            break
        except Exception:
            time.sleep(0.5)
    assert ok, "CN1 could not commit after TN restart"
    rows = _poll_rows(c2, "select id from r order by id", 2, timeout=30)
    assert [int(r[0]) for r in rows] == [1, 2]
    tn2.kill()
