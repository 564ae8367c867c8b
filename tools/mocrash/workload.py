"""mocrash seeded workloads: run a realistic write history on
recording file services and log which operations were ACKNOWLEDGED at
which journal position — the ground truth the recovery invariants are
checked against (tools/mocrash/invariants.py).

Two scenarios:

  * engine — one TN engine (commits, DDL, snapshots, a materialized
    view maintained from deltas, checkpoint, merge, a multi-table
    atomic txn) plus a CDC mirror engine on its own file service with
    a durably persisted watermark; both journals share ONE CrashJournal
    so a crash point is a consistent cut across source and mirror;
  * quorum — three log-replica cores driven by a majority-ack writer
    (the ReplicatedLog append rule), with a mid-stream checkpoint
    truncation.

Determinism: the SHAPE of the workload (row counts, values, strings,
delete choices) is seeded; timestamps are wall-clock HLC and don't
matter to any invariant.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from matrixone_tpu.cdc import CdcTask, FileWatermark
from matrixone_tpu.container.dtypes import DType, TypeOid
from matrixone_tpu.logservice.replicated import ReplicaCore
from matrixone_tpu.storage.engine import (ROWID, Engine, TableMeta,
                                          live_rows)
from matrixone_tpu.storage.fileservice import (MemoryFS,
                                               RecordingFileService)
from matrixone_tpu.utils.crash import CrashJournal

INT64 = DType(TypeOid.INT64)
VARCHAR = DType(TypeOid.VARCHAR, width=64)

#: plant flag (tools/mocrash/plants.py): persist the CDC watermark
#: BEFORE delivering to the mirror — the "watermark advanced before its
#: backing commit is durable" violation the sweep must catch
WM_EARLY = False

_STRINGS = ["ash", "birch", "cedar", "fir", "oak", "pine", "teak"]


@dataclasses.dataclass
class Ack:
    """One acknowledged operation: everything it did is journaled at
    indices < event_hi (recorded AFTER the call returned)."""
    op: str                 # insert|delete|txn2|ddl|snapshot|snapdrop|
    #                         mview|checkpoint|merge|gc|cdc_sync|
    #                         qappend|qtruncate
    event_lo: int           # journal position just before the op started
    event_hi: int           # journal position right after it returned
    table: str = ""
    ids: Tuple[int, ...] = ()
    rows: Dict[int, tuple] = dataclasses.field(default_factory=dict)
    pair_ids: Tuple[int, ...] = ()
    seq: int = 0            # quorum scenario
    payload: bytes = b""
    upto: int = 0
    ts: int = 0             # snapshot acks: the pinned timestamp


@dataclasses.dataclass
class EngineWorld:
    journal: CrashJournal
    acks: List[Ack]
    seed: int
    mirror_wm_path: str = "cdc/t_main.wm"

    # ---------------- expected-state folding (the checker's oracle)
    def fold(self, k: int):
        """State implied by the acks visible at crash point k:
        (expected t_main id->row, expected t_pair id set, ddl set,
        in-flight Ack or None).  Ops after the in-flight one never
        started — the workload is single-threaded."""
        main: Dict[int, tuple] = {}
        pair: set = set()
        ddl: set = set()
        inflight: Optional[Ack] = None
        for a in self.acks:
            if a.event_hi > k:
                inflight = a
                break
            if a.op == "insert":
                main.update(a.rows)
            elif a.op == "delete":
                for i in a.ids:
                    main.pop(i, None)
            elif a.op == "txn2":
                main.update(a.rows)
                pair.update(a.pair_ids)
            elif a.op in ("ddl", "snapshot", "mview"):
                ddl.add(a.table)
            elif a.op == "snapdrop":
                ddl.discard(a.table)
        return main, pair, ddl, inflight


@dataclasses.dataclass
class QuorumWorld:
    journal: CrashJournal
    acks: List[Ack]
    seed: int
    n_replicas: int = 3


class EngineSink:
    """CDC sink applying full DML to a second engine with PK upsert
    semantics — delete-then-insert in ONE commit, so a replayed event
    (at-least-once delivery) converges instead of duplicating."""

    def __init__(self, eng: Engine, table: str):
        self.eng = eng
        self.table = table

    def _gids_for(self, ids: List[int]) -> np.ndarray:
        t = self.eng.get_table(self.table)
        want = set(int(i) for i in ids)
        gids = []
        for arrays, _v, _d, n in map(
                live_rows, t.iter_chunks(["id", ROWID], 1 << 20)):
            for i in range(n):
                if int(arrays["id"][i]) in want:
                    gids.append(int(arrays[ROWID][i]))
        return np.asarray(gids, np.int64)

    def on_insert(self, table, rows, pk_cols=None):
        if not rows:
            return
        t = self.eng.get_table(self.table)
        n = len(rows)
        arrays = {
            "id": np.asarray([r["id"] for r in rows], np.int64),
            "batch": np.asarray([r["batch"] or 0 for r in rows],
                                np.int64),
            "v": np.asarray([r["v"] or 0 for r in rows], np.int64),
            "s": t.encode_strings_list("s", [r["s"] for r in rows]),
        }
        validity = {
            "id": np.ones(n, np.bool_),
            "batch": np.asarray([r["batch"] is not None for r in rows]),
            "v": np.asarray([r["v"] is not None for r in rows]),
            "s": np.asarray([r["s"] is not None for r in rows]),
        }
        gids = self._gids_for([r["id"] for r in rows])
        self.eng.commit_txn(
            None, {self.table: [(arrays, validity)]},
            {self.table: gids} if len(gids) else {})

    def on_delete(self, table, pk_rows):
        if not pk_rows:
            return
        gids = self._gids_for([r["id"] for r in pk_rows])
        if len(gids):
            self.eng.commit_txn(None, {}, {self.table: gids})


def _clear_table(eng: Engine, name: str) -> None:
    """Tombstone every visible row (one commit) — the mirror re-seed."""
    t = eng.get_table(name)
    gids: List[int] = []
    for arrays, _v, _d, n in map(live_rows,
                                 t.iter_chunks([ROWID], 1 << 20)):
        gids.extend(int(g) for g in arrays[ROWID])
    if gids:
        eng.commit_txn(None, {}, {name: np.asarray(gids, np.int64)})


def _main_meta() -> TableMeta:
    return TableMeta("t_main",
                     [("id", INT64), ("batch", INT64),
                      ("v", INT64), ("s", VARCHAR)],
                     ["id"])


def mirror_engine(fs) -> Engine:
    """A fresh (or reopened) mirror engine holding the t_main clone."""
    if fs.exists("meta/manifest.json") or fs.exists("wal/wal.log"):
        eng = Engine.open(fs)
    else:
        eng = Engine(fs)
    if "t_main" not in eng.tables:
        eng.create_table(_main_meta())
    return eng


def run_engine_workload(seed: int = 2026) -> EngineWorld:
    """Execute the seeded engine scenario; returns the shared journal +
    the ack log."""
    from matrixone_tpu.frontend import Session
    rng = np.random.default_rng(seed)
    journal = CrashJournal()
    fs = RecordingFileService(MemoryFS(), journal, "tn")
    mfs = RecordingFileService(MemoryFS(), journal, "mirror")
    eng = Engine(fs)
    sess = Session(catalog=eng)
    meng = mirror_engine(mfs)
    wm = FileWatermark(mfs, "cdc/t_main.wm")
    acks: List[Ack] = []
    next_id = [0]
    batch_no = [0]
    live: Dict[int, tuple] = {}

    def ack(op: str, lo: int, **kw) -> Ack:
        a = Ack(op=op, event_lo=lo, event_hi=journal.position(), **kw)
        acks.append(a)
        return a

    def insert_batch(n: int):
        batch_no[0] += 1
        b = batch_no[0]
        ids = list(range(next_id[0], next_id[0] + n))
        next_id[0] += n
        rows = {}
        vals = []
        for i in ids:
            v = int(rng.integers(0, 1000))
            s = (None if rng.random() < 0.15
                 else _STRINGS[int(rng.integers(len(_STRINGS)))])
            rows[i] = (b, v, s)
            vals.append(f"({i}, {b}, {v}, "
                        + ("null" if s is None else f"'{s}'") + ")")
        lo = journal.position()
        sess.execute("insert into t_main (id, batch, v, s) values "
                     + ", ".join(vals))
        live.update(rows)
        ack("insert", lo, table="t_main", ids=tuple(ids), rows=rows)

    def delete_some(k: int):
        if not live:
            return
        ids = sorted(live)
        pick = tuple(int(ids[j]) for j in
                     sorted(rng.choice(len(ids), size=min(k, len(ids)),
                                       replace=False)))
        lo = journal.position()
        sess.execute("delete from t_main where id in ("
                     + ", ".join(str(i) for i in pick) + ")")
        for i in pick:
            live.pop(i, None)
        ack("delete", lo, table="t_main", ids=pick)

    def cdc_sync():
        """Deliver everything past the durable watermark to the mirror,
        then persist the new watermark — AFTER the deliveries are
        durable (the plant flips the order)."""
        lo = journal.position()
        task = CdcTask(eng, "t_main", EngineSink(meng, "t_main"),
                       from_ts=wm.load())
        if WM_EARLY:
            # PLANTED VIOLATION: claim everything up to the current
            # frontier is delivered before delivering any of it
            wm.store(eng.committed_ts)
        try:
            task.backfill(from_ts=task.watermark)
        except ValueError:
            # a merge compacted deltas below the watermark: the
            # documented recovery — re-seed the mirror from scratch
            _clear_table(meng, "t_main")
            task.watermark = 0
            task.backfill(from_ts=0)
        if not WM_EARLY:
            wm.store(task.watermark)
        ack("cdc_sync", lo)

    # ---- the script
    lo = journal.position()
    sess.execute("create table t_main (id bigint primary key, "
                 "batch bigint, v bigint, s varchar(64))")
    ack("ddl", lo, table="t_main")
    lo = journal.position()
    sess.execute("create table t_pair (id bigint primary key, "
                 "src bigint)")
    ack("ddl", lo, table="t_pair")

    insert_batch(int(rng.integers(4, 9)))
    insert_batch(int(rng.integers(4, 9)))

    lo = journal.position()
    sess.execute("create materialized view mv1 as select s, sum(v) sv, "
                 "count(*) c from t_main group by s")
    ack("mview", lo, table="mv1")

    insert_batch(int(rng.integers(3, 7)))
    delete_some(2)
    cdc_sync()

    lo = journal.position()
    eng.create_snapshot("snap_wk")
    ack("snapshot", lo, table="snap_wk")

    lo = journal.position()
    sess.execute("select mo_ctl('checkpoint')")
    ack("checkpoint", lo)

    insert_batch(int(rng.integers(3, 7)))

    # multi-table atomic txn straight through the commit pipeline: both
    # tables' rows or neither (the commit frame is the atom)
    b = batch_no[0] = batch_no[0] + 1
    ids = list(range(next_id[0], next_id[0] + 3))
    next_id[0] += 3
    rows = {i: (b, i * 7, "teak") for i in ids}
    t_main = eng.get_table("t_main")
    arrays = {"id": np.asarray(ids, np.int64),
              "batch": np.full(3, b, np.int64),
              "v": np.asarray([i * 7 for i in ids], np.int64),
              "s": t_main.encode_strings_list("s", ["teak"] * 3)}
    ones = np.ones(3, np.bool_)
    validity = {c: ones.copy() for c in ("id", "batch", "v", "s")}
    pair = {"id": np.asarray(ids, np.int64),
            "src": np.asarray(ids, np.int64)}
    pvalid = {c: ones.copy() for c in ("id", "src")}
    lo = journal.position()
    eng.commit_txn(None, {"t_main": [(arrays, validity)],
                          "t_pair": [(pair, pvalid)]}, {})
    live.update(rows)
    ack("txn2", lo, table="t_main", ids=tuple(ids), rows=rows,
        pair_ids=tuple(ids))

    delete_some(1)
    cdc_sync()

    lo = journal.position()
    sess.execute("select mo_ctl('merge', 't_main')")
    ack("merge", lo)

    insert_batch(int(rng.integers(3, 6)))
    cdc_sync()

    sess.close()
    return EngineWorld(journal=journal, acks=acks, seed=seed)


def run_merge_workload(seed: int = 2026) -> EngineWorld:
    """Merge-under-traffic scenario: MergeScheduler cycles (candidate
    pick -> off-lock rewrite -> catalog swap -> fence GC -> checkpoint)
    interleave with foreground commits, a pinned named snapshot, and
    CDC fenced resumes — so the sweep crashes at every scheduler
    decision point and checks acked data survives, AS OF reads stay
    exact across the swap, deltas replay exactly-once, and no object is
    GC'd while a snapshot or fence can still reach it."""
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.storage.merge_sched import MergeScheduler
    rng = np.random.default_rng(seed)
    journal = CrashJournal()
    fs = RecordingFileService(MemoryFS(), journal, "tn")
    mfs = RecordingFileService(MemoryFS(), journal, "mirror")
    eng = Engine(fs)
    sess = Session(catalog=eng)
    meng = mirror_engine(mfs)
    wm = FileWatermark(mfs, "cdc/t_main.wm")
    sched = MergeScheduler(eng)
    sched.min_segments = 2           # small history: compact eagerly
    acks: List[Ack] = []
    next_id = [0]
    batch_no = [0]
    live: Dict[int, tuple] = {}

    def ack(op: str, lo: int, **kw) -> Ack:
        a = Ack(op=op, event_lo=lo, event_hi=journal.position(), **kw)
        acks.append(a)
        return a

    def insert_batch(n: int):
        batch_no[0] += 1
        b = batch_no[0]
        ids = list(range(next_id[0], next_id[0] + n))
        next_id[0] += n
        rows = {}
        vals = []
        for i in ids:
            v = int(rng.integers(0, 1000))
            s = (None if rng.random() < 0.15
                 else _STRINGS[int(rng.integers(len(_STRINGS)))])
            rows[i] = (b, v, s)
            vals.append(f"({i}, {b}, {v}, "
                        + ("null" if s is None else f"'{s}'") + ")")
        lo = journal.position()
        sess.execute("insert into t_main (id, batch, v, s) values "
                     + ", ".join(vals))
        live.update(rows)
        ack("insert", lo, table="t_main", ids=tuple(ids), rows=rows)

    def delete_some(k: int):
        if not live:
            return
        ids = sorted(live)
        pick = tuple(int(ids[j]) for j in
                     sorted(rng.choice(len(ids), size=min(k, len(ids)),
                                       replace=False)))
        lo = journal.position()
        sess.execute("delete from t_main where id in ("
                     + ", ".join(str(i) for i in pick) + ")")
        for i in pick:
            live.pop(i, None)
        ack("delete", lo, table="t_main", ids=pick)

    def cdc_sync():
        """Resume the mirror from its durable watermark.  Below a held
        fence this is the exactly-once fenced catch-up; only when the
        fence was GC'd (floor above the watermark) does the documented
        degrade rung re-seed from scratch."""
        lo = journal.position()
        task = CdcTask(eng, "t_main", EngineSink(meng, "t_main"),
                       from_ts=wm.load())
        try:
            task.backfill(from_ts=task.watermark)
        except ValueError:
            _clear_table(meng, "t_main")
            task.watermark = 0
            task.backfill(from_ts=0)
        wm.store(task.watermark)
        ack("cdc_sync", lo)

    def merge_cycle(op: str):
        lo = journal.position()
        sched.run_cycle()       # merge + fence GC + checkpoint cadence
        ack(op, lo)

    # ---- the script
    lo = journal.position()
    sess.execute("create table t_main (id bigint primary key, "
                 "batch bigint, v bigint, s varchar(64))")
    ack("ddl", lo, table="t_main")

    insert_batch(int(rng.integers(4, 8)))
    lo = journal.position()
    sess.execute("create materialized view mv1 as select s, sum(v) sv, "
                 "count(*) c from t_main group by s")
    ack("mview", lo, table="mv1")
    insert_batch(int(rng.integers(3, 7)))
    delete_some(2)
    cdc_sync()
    insert_batch(int(rng.integers(3, 6)))

    # pin the pre-merge history with a named snapshot, remembering
    # exactly what an AS OF read of it must return forever after
    lo = journal.position()
    snap_ts = eng.create_snapshot("snap_mg")
    ack("snapshot", lo, table="snap_mg", rows=dict(live), ts=snap_ts)

    lo = journal.position()
    sess.execute("select mo_ctl('checkpoint')")
    ack("checkpoint", lo)      # pre-merge segments now object-backed

    insert_batch(int(rng.integers(3, 6)))
    delete_some(2)

    # scheduler cycle 1: compacts below BOTH the snapshot and the CDC
    # watermark — the fence pins the pre-merge view, GC must hold
    merge_cycle("merge")

    insert_batch(int(rng.integers(3, 6)))
    cdc_sync()                 # fenced resume: watermark < merge_ts
    delete_some(1)
    insert_batch(int(rng.integers(2, 5)))

    # scheduler cycle 2: a second merge stacks a second fence
    merge_cycle("merge")
    cdc_sync()

    # release: drop the pin — the next cycle's gc_fences releases the
    # fences (manifest durable FIRST) and deletes the pre-merge objects
    lo = journal.position()
    eng.drop_snapshot("snap_mg")
    ack("snapdrop", lo, table="snap_mg")
    merge_cycle("gc")

    insert_batch(int(rng.integers(2, 5)))
    cdc_sync()
    sess.close()
    return EngineWorld(journal=journal, acks=acks, seed=seed)


def run_quorum_workload(seed: int = 2026,
                        n_entries: int = 10) -> QuorumWorld:
    """Majority-ack append stream over three recorded replica cores,
    with one mid-stream checkpoint truncation — the ReplicatedLog
    durability contract at disk granularity."""
    rng = np.random.default_rng(seed)
    journal = CrashJournal()
    cores = [ReplicaCore(RecordingFileService(MemoryFS(), journal,
                                              f"rep{i}"))
             for i in range(3)]
    acks: List[Ack] = []
    epoch = 1
    for seq in range(1, n_entries + 1):
        payload = (f"entry-{seq}-".encode()
                   * int(1 + rng.integers(1, 4)))
        # one replica is occasionally "down" — a majority still acks
        skip = int(rng.integers(0, 3)) if rng.random() < 0.3 else -1
        lo = journal.position()
        ok = 0
        for i, c in enumerate(cores):
            if i == skip:
                continue
            if c.append(epoch, seq, payload).get("ok"):
                ok += 1
        if ok >= 2:
            acks.append(Ack(op="qappend", event_lo=lo,
                            event_hi=journal.position(), seq=seq,
                            payload=payload))
        if seq == n_entries // 2:
            upto = seq - 1
            lo = journal.position()
            for c in cores:
                c.truncate(epoch, upto)
            acks.append(Ack(op="qtruncate", event_lo=lo,
                            event_hi=journal.position(), upto=upto))
    return QuorumWorld(journal=journal, acks=acks, seed=seed)
