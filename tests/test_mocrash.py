"""mocrash gate: deterministic crash-point recovery sweep
(tools/mocrash + utils/crash + storage/fileservice RecordingFileService).

Tier-1 contract (ISSUE 15): the quick seeded sweep over EVERY
enumerated durability boundary (all crash points x torn-write variants,
engine + quorum scenarios) reports zero invariant violations, and all
three planted violations are caught with the point-of-crash and the
violated invariant named in the finding.
"""

import numpy as np
import pytest

from matrixone_tpu.container.dtypes import DType, TypeOid
from matrixone_tpu.storage.engine import Engine, TableMeta, live_rows
from matrixone_tpu.storage.fileservice import (LocalFS, MemoryFS,
                                               RecordingFileService)
from matrixone_tpu.storage import wal as walmod
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.utils.crash import CrashJournal

from tools import mocrash
from tools.mocrash import invariants, workload

INT64 = DType(TypeOid.INT64)


def _small_journal():
    """A recorded engine history: two commits around a checkpoint."""
    j = CrashJournal()
    fs = RecordingFileService(MemoryFS(), j, "tn")
    eng = Engine(fs)
    eng.create_table(TableMeta("t", [("id", INT64), ("v", INT64)],
                               ["id"]))
    ones = np.ones(5, np.bool_)
    eng.commit_write("t", {"id": np.arange(5), "v": np.arange(5) * 10},
                     {"id": ones, "v": ones.copy()})
    eng.checkpoint()
    ones4 = np.ones(4, np.bool_)
    eng.commit_write("t", {"id": np.arange(5, 9),
                           "v": np.arange(5, 9) * 10},
                     {"id": ones4, "v": ones4.copy()})
    return j


# ================================================= journal/materializer
def test_materializer_torn_append_variants():
    j = CrashJournal()
    fs = RecordingFileService(MemoryFS(), j, "x")
    fs.append("wal/w.log", b"AAAA")
    fs.append("wal/w.log", b"BBBB")
    evs = j.events()
    k = max(i for i, e in enumerate(evs) if e.op == "append")
    for torn, want in ((0.0, b"AAAA"), (0.5, b"AAAABB"),
                       (1.0, b"AAAABBBB")):
        u = j.materialize(k, torn=torn)
        assert u["x"].read("wal/w.log") == want
    # lossy at the fsync of the second append: un-fsynced bytes drop
    u = j.materialize(k + 1, torn=0.0, lossy=True)
    assert u["x"].read("wal/w.log") == b"AAAA"


def test_materializer_write_is_atomic_and_orphans_surface():
    j = CrashJournal()
    fs = RecordingFileService(MemoryFS(), j, "x")
    fs.write("meta/m.json", b"OLD")
    fs.write("meta/m.json", b"NEWLONGER")
    evs = j.events()
    k2 = max(i for i, e in enumerate(evs) if e.op == "write_tmp")
    # crash mid-tmp-write: dst untouched, torn tmp is an orphan
    u = j.materialize(k2, torn=0.5)
    assert u["x"].read("meta/m.json") == b"OLD"
    assert u["x"].orphans() == ["meta/m.json.tmp"]
    assert "meta/m.json.tmp" not in u["x"].list("meta/")
    # crash with the replace in flight (not applied): old content
    u = j.materialize(k2 + 2, torn=0.0)
    assert u["x"].read("meta/m.json") == b"OLD"
    # replace applied but dirent never fsynced + lossy: rename rolls
    # back to the previous durable content
    u = j.materialize(k2 + 3, torn=0.0, lossy=True)
    assert u["x"].read("meta/m.json") == b"OLD"
    # fully issued: new content, no orphan
    u = j.materialize(len(j))
    assert u["x"].read("meta/m.json") == b"NEWLONGER"
    assert u["x"].orphans() == []


def test_journal_byte_budget_overflow():
    j = CrashJournal(max_bytes=100)
    fs = RecordingFileService(MemoryFS(), j, "x")
    fs.append("a", b"x" * 200)      # first payload lands, budget spent
    pos = j.position()
    fs.append("a", b"y")            # over budget: recording stops
    assert j.overflow and j.position() == pos
    with pytest.raises(RuntimeError):
        j.materialize(0)            # incomplete journal refuses


def test_diskcache_gcs_orphan_tmp_on_init(tmp_path):
    from matrixone_tpu.storage.s3 import DiskCacheFS
    d = tmp_path / "cache"
    d.mkdir()
    (d / "deadbeef.tmp").write_bytes(b"torn")
    fs = DiskCacheFS(MemoryFS(), str(d))
    assert fs.orphans() == []
    assert not (d / "deadbeef.tmp").exists()


def test_wal_replay_stats_report_torn_tail():
    fs = MemoryFS()
    w = walmod.WalWriter(fs)
    w.append({"op": "commit", "ts": 1})
    w.append({"op": "commit", "ts": 2})
    blob = fs.read("wal/wal.log")
    fs.write("wal/wal.log", blob[:-7])      # tear the tail
    stats = {}
    frames = list(walmod.replay(fs, stats=stats))
    assert [h["ts"] for h, _b in frames] == [1]
    assert stats["frames"] == 1
    assert stats["torn_bytes"] > 0


# ====================================================== recovery summary
def test_recovery_summary_metrics_and_span():
    from matrixone_tpu.utils import motrace
    j = _small_journal()
    evs = j.events()
    k = max(i for i, e in enumerate(evs) if e.op == "append")
    u = j.materialize(k, torn=0.5)
    f0 = M.recovery_frames.get()
    t0 = M.recovery_torn_bytes.get()
    was = motrace.TRACER.armed
    motrace.TRACER.arm(sample=1.0)
    motrace.TRACER.clear()
    try:
        eng = Engine.open(u["tn"])
        tids = motrace.TRACER.trace_ids()
        spans = [sp for tid in tids
                 for sp in motrace.TRACER.spans_of(tid)
                 if sp["name"] == "engine.recover"]
    finally:
        if not was:
            motrace.TRACER.disarm()
    rs = eng.recovery_summary
    assert rs is not None
    assert rs["frames_replayed"] >= 1
    assert rs["torn_bytes"] > 0
    assert rs["ckpt_ts"] > 0
    assert eng.get_table("t").n_rows == 5    # torn commit not visible
    assert M.recovery_frames.get() > f0
    assert M.recovery_torn_bytes.get() > t0
    assert spans, "Engine.open must emit an engine.recover span"
    assert spans[0]["attrs"]["torn_bytes"] == rs["torn_bytes"]


def test_orphan_tmp_files_gcd_at_open(tmp_path):
    # real LocalFS: a leftover tmp from a crashed writer is swept
    fs = LocalFS(str(tmp_path))
    eng = Engine(fs)
    eng.create_table(TableMeta("t", [("id", INT64)], []))
    ones = np.ones(3, np.bool_)
    eng.commit_write("t", {"id": np.arange(3)}, {"id": ones})
    eng.checkpoint()
    (tmp_path / "meta" / "manifest.json.tmp").write_bytes(b"torn")
    assert fs.orphans() == ["meta/manifest.json.tmp"]
    g0 = M.recovery_orphans.get()
    eng2 = Engine.open(fs)
    assert eng2.recovery_summary["orphans_gcd"] == 1
    assert fs.orphans() == []
    assert M.recovery_orphans.get() == g0 + 1
    assert eng2.get_table("t").n_rows == 3


# ========================================================= THE quick gate
def test_quick_sweep_every_boundary_is_clean():
    """Zero findings across all crash points x torn variants of the
    seeded engine + quorum workloads — the tier-1 durability gate.
    (The merge scenario sweeps in its own capped gate below; the
    uncapped all-scenario matrix lives under the slow marker.)"""
    findings, events, points, recoveries = [], 0, 0, 0
    for scenario in ("engine", "quorum"):
        rep = mocrash.run_sweep(seed=mocrash.sweep_seed(),
                                scenario=scenario)
        findings += rep["findings_formatted"]
        events += rep["events"]
        points += rep["points"]
        recoveries += rep["recoveries"]
    assert events > 200
    assert points >= 3 * events * 0.9
    assert recoveries > 50
    assert findings == [], "\n".join(findings)


# ===================================================== planted violations
def test_planted_truncate_before_checkpoint_caught():
    rep = mocrash.run_sweep(seed=mocrash.sweep_seed(),
                            scenario="engine", plant="truncate-early")
    assert rep["findings"]
    invs = {f["invariant"] for f in rep["findings"]}
    assert "acked-commit-lost" in invs
    line = rep["findings_formatted"][0]
    assert "point=" in line and "invariant=" in line and "event=" in line


def test_planted_fsync_skip_before_rename_caught():
    rep = mocrash.run_sweep(seed=mocrash.sweep_seed(),
                            scenario="engine", plant="fsync-skip")
    assert rep["findings"]
    invs = {f["invariant"] for f in rep["findings"]}
    assert invs & {"recovery-opens", "acked-commit-lost"}
    assert all("point=" in ln and "invariant=" in ln
               for ln in rep["findings_formatted"])


def test_planted_watermark_before_commit_caught():
    rep = mocrash.run_sweep(seed=mocrash.sweep_seed(),
                            scenario="engine", plant="watermark-early")
    assert rep["findings"]
    assert {f["invariant"] for f in rep["findings"]} == {
        "cdc-exactly-once"}
    assert "point=" in rep["findings_formatted"][0]


# ============================================== merge-under-traffic sweep
def test_merge_under_traffic_sweep_is_clean():
    """Crash at every MergeScheduler decision point (candidate pick /
    off-lock rewrite / catalog swap / fence GC / checkpoint truncate)
    under foreground traffic: acked data survives, AS OF reads stay
    exact across the swap, deltas replay exactly-once, and no object is
    GC'd while a snapshot or fence can reach it."""
    world = mocrash.workload.run_merge_workload(mocrash.sweep_seed())
    assert len(world.journal) > 250
    ops = {a.op for a in world.acks}
    assert {"merge", "gc", "snapshot", "snapdrop", "cdc_sync"} <= ops
    findings, counts = [], {"points": 0, "recoveries": 0,
                            "memo_hits": 0, "events": 0}
    pts = mocrash._pick_points(len(world.journal), 30)
    mocrash._sweep_world(world, mocrash.invariants.check_engine,
                         mocrash.VARIANTS_QUICK, pts, findings, counts)
    assert counts["recoveries"] > 20
    assert findings == [], "\n".join(f.format() for f in findings)


def test_planted_gc_before_fence_release_caught():
    """Re-introduce object-GC-before-fence-release-durable: the sweep
    must catch a manifest whose held fences reference deleted files,
    naming the point of crash and the invariant."""
    rep = mocrash.run_sweep(seed=mocrash.sweep_seed(),
                            scenario="merge", plant="gc-early")
    assert rep["findings"]
    invs = {f["invariant"] for f in rep["findings"]}
    assert "gc-reachable-object-deleted" in invs
    line = rep["findings_formatted"][0]
    assert "point=" in line and "invariant=" in line and "event=" in line


@pytest.mark.slow
def test_planted_swap_before_rewrite_durable_caught():
    """Re-introduce merge-swap-before-rewrite-durable (merged object
    written without fsync): under fsync-loss the durable manifest
    references an object the disk never held — acked rows unreadable.
    (Slow tier: gc-early is the tier-1 planted merge drill; this one
    sweeps a 40-event window per merge on the 1-core box.)"""
    rep = mocrash.run_sweep(seed=mocrash.sweep_seed(),
                            scenario="merge", plant="swap-early")
    assert rep["findings"]
    invs = {f["invariant"] for f in rep["findings"]}
    assert invs & {"acked-commit-lost", "gc-reachable-object-deleted",
                   "recovery-opens"}
    assert "point=" in rep["findings_formatted"][0]


# ================================================ checkpoint-truncate window
def test_checkpoint_truncate_window_drill():
    """Chaos drill for the checkpoint protocol ordering: a crash at ANY
    point between the manifest becoming durable and the WAL truncate
    completing must replay cleanly (old-manifest + full-WAL and
    new-manifest + full-WAL are both legal; the tail is never lost).
    The planted `truncate-early` run proves the sweep would catch the
    reversed ordering."""
    j = _small_journal()
    evs = j.events()
    # the window: from the manifest's write_tmp to the WAL truncate's
    # directory fsync
    lo = next(i for i, e in enumerate(evs)
              if e.op == "write_tmp" and "manifest" in e.path)
    hi = max(i for i, e in enumerate(evs)
             if e.op == "fsync_dir" and e.path == "wal")
    for k in range(lo, hi + 2):
        for torn, lossy in ((1.0, False), (0.0, True)):
            u = j.materialize(k, torn=torn, lossy=lossy)
            eng = Engine.open(u["tn"])
            assert eng.get_table("t").n_rows in (5, 9), \
                f"point {k} ({evs[k].label()}) torn={torn} " \
                f"lossy={lossy} lost acked rows"
            # rows 0..4 were acked BEFORE the checkpoint began: they
            # must survive every point of the window
            ids = set()
            t = eng.get_table("t")
            for arrays, _v, _d, n in map(
                    live_rows, t.iter_chunks(["id"], 1 << 20)):
                ids.update(int(x) for x in arrays["id"])
            assert set(range(5)) <= ids


# ============================================ delta-economy crash windows
def _window_points(world, op):
    """Every crash point inside the acks of kind `op`."""
    pts = []
    for a in world.acks:
        if a.op == op:
            pts.extend(range(a.event_lo, a.event_hi + 1))
    return pts


def test_mview_backing_commit_crash_window():
    """Kill at every event between a source commit and its maintenance
    backing commit/watermark advance: after reopen + the first commit,
    the view equals a recompute — no gap, no double-apply."""
    world = workload.run_engine_workload(seed=7)
    pts = _window_points(world, "insert") + _window_points(world,
                                                           "delete")
    findings = []
    for k in pts[:: max(1, len(pts) // 40)]:
        findings += [f for f in invariants.check_engine(
            world, k, 0.5, False)
            if f.invariant == "mview-exactly-once"]
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cdc_watermark_crash_window():
    """Kill at every event between mirror sink delivery and the
    watermark persist: the reopen catches up exactly-once from
    cdc.delta_events (upsert dedups redelivery, nothing is skipped)."""
    world = workload.run_engine_workload(seed=11)
    pts = _window_points(world, "cdc_sync")
    findings = []
    for k in pts[:: max(1, len(pts) // 40)]:
        findings += [f for f in invariants.check_engine(
            world, k, 1.0, False)
            if f.invariant == "cdc-exactly-once"]
    assert findings == [], "\n".join(f.format() for f in findings)


# ================================================================ quorum
def test_replica_core_reloads_from_torn_state():
    from matrixone_tpu.logservice.replicated import ReplicaCore
    j = CrashJournal()
    fs = RecordingFileService(MemoryFS(), j, "r")
    core = ReplicaCore(fs)
    core.append(1, 1, b"one")
    core.append(1, 2, b"two-two")
    evs = j.events()
    k = max(i for i, e in enumerate(evs) if e.op == "append")
    u = j.materialize(k, torn=0.5)
    re = ReplicaCore(u["r"])
    assert dict(re.entries) == {1: (1, b"one")}    # torn tail dropped
    assert re.torn_bytes > 0
    assert re.epoch == 1                           # meta write atomic


# ============================================================ ops surface
def test_mo_ctl_crash_surface():
    from matrixone_tpu.frontend import Session
    s = Session(catalog=Engine())
    try:
        import json
        st = json.loads(
            s.execute("select mo_ctl('crash', 'status')").rows()[0][0])
        assert "plants" in st and "journal_events" in st
        out = json.loads(
            s.execute("select mo_ctl('crash', 'run:3')").rows()[0][0])
        assert out["findings"] == 0 and out["recoveries"] > 0
        s.execute("select mo_ctl('crash', 'clear')")
        with pytest.raises(Exception):
            s.execute("select mo_ctl('crash', 'bogus')")
    finally:
        s.close()


def test_mo_crash_record_env_wraps(monkeypatch):
    from matrixone_tpu.storage.fileservice import maybe_record
    base = MemoryFS()
    assert maybe_record(base) is base
    monkeypatch.setenv("MO_CRASH_RECORD", "1")
    wrapped = maybe_record(base, tag="t")
    assert isinstance(wrapped, RecordingFileService)
    pos0 = wrapped.journal.position()
    wrapped.write("a/b", b"x")
    assert wrapped.journal.position() > pos0
    assert base.read("a/b") == b"x"


# ============================================================= full sweep
@pytest.mark.slow
@pytest.mark.chaos
def test_full_sweep_all_variants():
    """The heavyweight net: full torn x lossy variant matrix, two
    seeds, every scenario (engine + merge + quorum)."""
    for seed in (2026, 31):
        rep = mocrash.run_sweep(seed=seed, scenario="all",
                                variants="full")
        assert rep["findings"] == [], "\n".join(
            rep["findings_formatted"])
