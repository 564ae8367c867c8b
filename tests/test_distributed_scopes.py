"""General distributed executor (VERDICT r3 directive 1): plan fragments
shipped to peer CN fragment servers — distributed hash join (replicated
build + sharded probe), distributed group-by, distributed top-k.

Reference analogue: compile/remoterun.go:86 encodeScope +
proto/pipeline.proto:529 (operator subtrees shipped to peer CNs);
acceptance: TPC-H Q3 and Q18 across 2 CN processes, bit-identical to
the local plan.
"""

import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from matrixone_tpu.cluster.cn import FragmentServer
from matrixone_tpu.frontend import Session
from matrixone_tpu.storage.engine import Engine
from matrixone_tpu.utils import tpch_full as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ in-process
@pytest.fixture(scope="module")
def dist_rig():
    """One engine, two fragment servers over it, a local session and a
    distribution-enabled session — every dist answer is checked against
    the identical local plan."""
    eng = Engine()
    s = Session(catalog=eng)
    s.execute("create table t (id bigint primary key, g varchar(8),"
              " v bigint, d double)")
    for lo in range(0, 4000, 800):
        vals = ",".join(
            f"({i},'g{i % 7}',{i % 100},{(i % 13) * 0.5})"
            for i in range(lo, lo + 800))
        s.execute(f"insert into t values {vals}")
    f1 = FragmentServer(eng).start()
    f2 = FragmentServer(eng).start()
    eng.dist_peers = [f"127.0.0.1:{f1.port}", f"127.0.0.1:{f2.port}"]
    sd = Session(catalog=eng)
    sd.variables["dist_min_rows"] = 0
    sd.variables["dist_batch_rows"] = 512
    yield eng, s, sd, (f1, f2)
    f1.stop()
    f2.stop()


def _both(rig, sql):
    eng, s, sd, frags = rig
    before = sum(f.frags_run for f in frags)
    local = s.execute(sql).rows()
    dist = sd.execute(sql).rows()
    after = sum(f.frags_run for f in frags)
    return local, dist, after - before


def test_dist_group_by_with_varchar_keys(dist_rig):
    local, dist, nfrags = _both(
        dist_rig, "select g, sum(v), count(*), avg(v), min(v), max(v)"
                  " from t group by g order by g")
    assert dist == local
    assert nfrags == 2, "both peers must have executed a fragment"


def test_dist_scalar_aggregate(dist_rig):
    local, dist, nfrags = _both(
        dist_rig, "select sum(v), count(*), avg(d), min(id), max(id)"
                  " from t where v < 80")
    assert dist == local
    assert nfrags == 2


def test_dist_topk(dist_rig):
    local, dist, nfrags = _both(
        dist_rig, "select id, v from t order by v desc, id limit 9")
    assert dist == local
    assert nfrags == 2


def test_dist_topk_with_offset(dist_rig):
    local, dist, _ = _both(
        dist_rig,
        "select id, v from t order by v desc, id limit 5 offset 3")
    assert dist == local


def test_dist_join_group_by(dist_rig):
    eng, s, sd, frags = dist_rig
    s.execute("create table dim (k bigint primary key, tag varchar(8))")
    vals = ",".join(f"({i},'d{i % 3}')" for i in range(100))
    s.execute(f"insert into dim values {vals}")
    sql = ("select dim.tag, sum(t.v), count(*) from t"
           " join dim on t.v = dim.k where dim.k < 60"
           " group by dim.tag order by dim.tag")
    local, dist, nfrags = _both(dist_rig, sql)
    assert dist == local
    assert nfrags == 2


def test_dist_falls_back_inside_txn(dist_rig):
    """An open txn's workspace is invisible to peers: dist must bail and
    the local plan must see the uncommitted rows."""
    eng, s, sd, frags = dist_rig
    sd.execute("begin")
    before = sum(f.frags_run for f in frags)
    sd.execute("insert into t values (999001, 'gx', 1, 0.0)")
    rows = sd.execute("select count(*) from t where id = 999001").rows()
    assert int(rows[0][0]) == 1
    assert sum(f.frags_run for f in frags) == before
    sd.execute("rollback")


def test_dist_unsupported_shapes_fall_back(dist_rig):
    """DISTINCT aggregates and window functions are not distributable;
    the planner must return the local plan, not a wrong answer."""
    eng, s, sd, frags = dist_rig
    for sql in (
            "select g, count(distinct v) from t group by g order by g",
            "select id, row_number() over (partition by g order by id)"
            " from t order by id limit 5"):
        local = s.execute(sql).rows()
        dist = sd.execute(sql).rows()
        assert dist == local


# -------------------------------------------------------------- TPC-H
@pytest.fixture(scope="module")
def tpch_rig():
    eng = Engine()
    tables = T.load_tpch(eng, sf=0.004, seed=1)
    conn = T.to_sqlite(tables)
    f1 = FragmentServer(eng).start()
    f2 = FragmentServer(eng).start()
    eng.dist_peers = [f"127.0.0.1:{f1.port}", f"127.0.0.1:{f2.port}"]
    s = Session(catalog=eng)
    sd = Session(catalog=eng)
    sd.variables["dist_min_rows"] = 0
    sd.variables["dist_batch_rows"] = 4096
    yield eng, s, sd, conn, (f1, f2)
    conn.close()
    f1.stop()
    f2.stop()


@pytest.mark.parametrize("qnum", [1, 3, 6, 10, 18])
def test_tpch_distributed_matches_local_and_oracle(tpch_rig, qnum):
    """The directive's acceptance shape: distributed TPC-H = local TPC-H
    bit-for-bit, and both = the sqlite oracle."""
    eng, s, sd, conn, frags = tpch_rig
    sql = T.QUERIES[qnum]
    local = s.execute(sql).rows()
    before = sum(f.frags_run for f in frags)
    dist = sd.execute(sql).rows()
    ran = sum(f.frags_run for f in frags) - before
    assert dist == local, f"Q{qnum} distributed != local"
    T.run_compare(sd, conn, qnum)
    if qnum in (1, 3, 6, 18):
        # Q18's inlined HAVING subquery distributes too -> 4 fragments
        assert ran >= 2 and ran % 2 == 0, \
            f"Q{qnum} did not distribute (frags={ran})"


# ------------------------------------------------------- process-level
def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


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
def dist_cluster():
    from matrixone_tpu.cluster import RemoteCatalog
    d = tempfile.mkdtemp(prefix="mo_dist_cluster_")
    tn, tn_port = _spawn(["matrixone_tpu.cluster.tn", "--dir", d,
                          "--port", "0"])
    fp1, fp2 = _free_port(), _free_port()
    peers = f"127.0.0.1:{fp1},127.0.0.1:{fp2}"
    cns = [_spawn(["matrixone_tpu.cluster.cn", "--tn",
                   f"127.0.0.1:{tn_port}", "--dir", d, "--port", "0",
                   "--frag-port", str(fp), "--peers", peers])
           for fp in (fp1, fp2)]
    # load the corpus through the TN commit path (a third CN-side catalog)
    loader = RemoteCatalog(("127.0.0.1", tn_port), data_dir=d)
    tables = T.load_tpch(loader, sf=0.004, seed=1)
    ts = loader.committed_ts
    loader.close()
    yield d, tn_port, cns, (fp1, fp2), tables, ts
    for p, _ in cns + [(tn, tn_port)]:
        if p.poll() is None:
            p.kill()


def _frag_stats(port):
    from matrixone_tpu.cluster.rpc import RpcClient
    c = RpcClient(("127.0.0.1", port))
    resp, _ = c.call({"op": "stats"})
    c.close()
    return resp["frags_run"]


@pytest.mark.parametrize("qnum", [3, 18])
def test_tpch_q3_q18_across_two_cn_processes(dist_cluster, qnum):
    """The directive verbatim: Q3 and Q18 across 2 CN processes,
    bit-identical to local — same CN, same wire, dist off vs on."""
    from matrixone_tpu import client
    d, tn_port, cns, frag_ports, tables, ts = dist_cluster
    # generous timeout: a cold CN process jit-compiles every fragment
    # shape on its first distributed query
    c = client.connect(port=cns[0][1], timeout=300)
    sql = " ".join(T.QUERIES[qnum].split())
    if qnum == 18:
        # the canonical 300-quantity threshold is empty at sf=0.004 —
        # lower it so the comparison is non-vacuous
        sql = sql.replace("> 300", "> 60")
    c.execute("set dist = 0")
    _cols, local = c.query(sql)
    c.execute("set dist = 1")
    c.execute("set dist_min_rows = 0")
    c.execute("set dist_batch_rows = 4096")
    before = sum(_frag_stats(p) for p in frag_ports)
    _cols, dist = c.query(sql)
    ran = sum(_frag_stats(p) for p in frag_ports) - before
    assert dist == local, f"Q{qnum}: distributed != local over the wire"
    if ran < 2:
        # a cold peer under machine load can time one fragment out and
        # fall back to local (by design); the warm retry must fan out
        before = sum(_frag_stats(p) for p in frag_ports)
        _cols, dist = c.query(sql)
        ran = sum(_frag_stats(p) for p in frag_ports) - before
        assert dist == local, f"Q{qnum}: warm retry != local"
    assert ran >= 2, f"Q{qnum} did not fan out across CN processes"
    assert len(local) > 0, f"Q{qnum} returned no rows (weak corpus)"
