"""Multi-PROCESS distribution slice (VERDICT r1 #2):

  * replicated WAL: engine commits against 3 log-replica processes,
    survives killing one replica, and a fresh engine recovers from the
    surviving majority (reference: pkg/logservice Raft WAL);
  * remote pipeline scopes: TPC-H Q1 split across 2 worker processes via
    serialized stage descriptors, bit-identical to the local run
    (reference: compile/remoterun.go encodeScope over morpc).
"""

import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from matrixone_tpu.logservice.replicated import ReplicatedLog
from matrixone_tpu.storage.engine import Engine, live_rows
from matrixone_tpu.storage.fileservice import MemoryFS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(mod_args, needs_port=True):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.Popen([sys.executable, "-m"] + mod_args,
                         stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                         env=env, text=True)
    port = None
    if needs_port:
        deadline = time.time() + 120
        while time.time() < deadline:
            line = p.stdout.readline()
            if line.startswith("PORT "):
                port = int(line.split()[1])
                break
        assert port, "subprocess did not report a port"
    return p, port


@pytest.fixture
def log_replicas():
    procs, addrs, dirs = [], [], []
    for i in range(3):
        d = tempfile.mkdtemp(prefix=f"mo_logrep{i}_")
        dirs.append(d)
        p, port = _spawn(["matrixone_tpu.logservice.replicated",
                          "--dir", d, "--port", "0"])
        procs.append(p)
        addrs.append(("127.0.0.1", port))
    yield procs, addrs, dirs
    for p in procs:
        if p.poll() is None:
            p.kill()


def test_replicated_wal_survives_replica_loss(log_replicas):
    procs, addrs, _dirs = log_replicas
    log = ReplicatedLog(addrs)
    eng = Engine(MemoryFS(), wal=log)
    from matrixone_tpu.frontend.session import Session
    s = Session(catalog=eng)
    s.execute("create table r (id bigint primary key, v varchar(16))")
    s.execute("insert into r values (1, 'one'), (2, 'two')")

    # kill one replica: quorum 2/3 still commits
    procs[0].kill()
    procs[0].wait()
    s.execute("insert into r values (3, 'three')")

    # fresh engine recovers the full committed log from the majority
    log2 = ReplicatedLog(addrs)
    eng2 = Engine.open(MemoryFS(), wal=log2)
    s2 = Session(catalog=eng2)
    rows = s2.execute("select id, v from r order by id").rows()
    assert [(int(a), b) for a, b in rows] == [
        (1, "one"), (2, "two"), (3, "three")]

    # losing a SECOND replica must refuse appends (no silent minority ack)
    procs[1].kill()
    procs[1].wait()
    with pytest.raises(Exception, match="quorum|reachable"):
        s2.execute("insert into r values (4, 'four')")


def test_replica_epoch_fences_stale_writer(log_replicas):
    procs, addrs, _dirs = log_replicas
    old = ReplicatedLog(addrs)
    old.append({"op": "commit", "ts": 1})
    new = ReplicatedLog(addrs)            # epoch := old.epoch + 1
    with pytest.raises(ConnectionError, match="quorum"):
        old.append({"op": "commit", "ts": 2})   # fenced
    new.append({"op": "commit", "ts": 3})       # new writer fine
    seqs = [h["ts"] for h, _ in new.replay()]
    assert 2 not in seqs and 1 in seqs and 3 in seqs


@pytest.fixture(scope="module")
def workers():
    procs, addrs = [], []
    for _ in range(2):
        p, port = _spawn(["matrixone_tpu.worker", "--port", "0"])
        procs.append(p)
        addrs.append(f"127.0.0.1:{port}")
    yield addrs
    for p in procs:
        p.send_signal(signal.SIGINT)
    for p in procs:
        if p.poll() is None:
            p.kill()


def test_remote_scope_q1_two_worker_processes(workers):
    """Q1 as a remote scope over 2 worker processes == local execution,
    exactly (int64 cent partial sums are order-independent)."""
    from matrixone_tpu.container import dtypes as dt
    from matrixone_tpu.frontend.session import Session
    from matrixone_tpu.parallel.remote_exec import RemoteScopeCoordinator
    from matrixone_tpu.sql.expr import AggCall, BoundCol, BoundFunc, \
        BoundLiteral
    from matrixone_tpu.utils import tpch

    s = Session()
    tpch.load_lineitem(s.catalog, 60_000)
    local = {}
    for row in s.execute(tpch.Q1_SQL).rows():
        local[(row[0], row[1])] = tuple(row[2:])

    t = s.catalog.get_table("lineitem")
    cols = ["l_returnflag", "l_linestatus", "l_quantity",
            "l_extendedprice", "l_discount", "l_tax", "l_shipdate"]
    schema = {c: (dt.INT32 if d.is_varlen else d)
              for c, d in t.meta.schema if c in cols}
    d152 = dt.decimal64(15, 2)

    def col(c):
        return BoundCol(c, schema[c])

    one = BoundLiteral(100, d152)          # 1.00 in cents
    disc_price = BoundFunc("mul", [col("l_extendedprice"),
                                   BoundFunc("sub", [one, col("l_discount")],
                                             d152)], dt.decimal64(15, 4))
    charge = BoundFunc("mul", [disc_price,
                               BoundFunc("add", [one, col("l_tax")], d152)],
                       dt.decimal64(15, 6))
    aggs = [AggCall("sum", col("l_quantity"), False, d152, "sum_qty"),
            AggCall("sum", col("l_extendedprice"), False, d152, "sum_base"),
            AggCall("sum", disc_price, False, dt.decimal64(15, 4),
                    "sum_disc_price"),
            AggCall("sum", charge, False, dt.decimal64(15, 6), "sum_charge"),
            AggCall("count", None, False, dt.INT64, "cnt")]
    out_dtypes = [d152, d152, dt.decimal64(15, 4), dt.decimal64(15, 6),
                  dt.INT64]
    cutoff = (np.datetime64("1998-09-02") - np.datetime64("1970-01-01")
              ).astype(int)
    filters = [BoundFunc("le", [col("l_shipdate"),
                                BoundLiteral(int(cutoff), dt.DATE)],
                         dt.BOOL)]

    coord = RemoteScopeCoordinator(workers)
    chunks = [({c: arrays[c] for c in cols},
               {c: validity[c] for c in cols})
              for arrays, validity, _dicts, _n in map(
                  live_rows, t.iter_chunks(cols, batch_rows=16384))]
    assert len(chunks) >= 2, "need multiple chunks to exercise fan-out"
    keys, kvalids, vals, ng = coord.group_aggregate(
        chunks, schema,
        group_keys=[col("l_returnflag"), col("l_linestatus")],
        aggs=aggs, filters=filters, out_dtypes=out_dtypes)
    coord.close()

    assert ng == len(local)
    rf_dict = t.dicts["l_returnflag"]
    ls_dict = t.dicts["l_linestatus"]
    for i in range(ng):
        k = (rf_dict[int(keys[0][i])], ls_dict[int(keys[1][i])])
        want = local[k]
        got = (vals[0][i] / 100, vals[1][i] / 100, vals[2][i] / 10**4,
               vals[3][i] / 10**6, vals[4][i])
        for a, b in zip(got, (float(want[0]), float(want[1]),
                              float(want[2]), float(want[3]),
                              float(want[7]))):
            assert abs(float(a) - b) < 1e-6, (k, got, want)
