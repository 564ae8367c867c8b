"""mocrash recovery invariants: reopen the system from one materialized
crash state and verify the durability contract.

Engine scenario (per crash point x torn/lossy variant):

  * recovery-opens        — Engine.open must succeed from ANY
                            crash-consistent state (torn WAL tails and
                            half-replaced manifests are normal crash
                            debris, never fatal);
  * acked-commit-lost     — every commit acknowledged before the crash
                            point is visible after reopen;
  * partial-commit-visible / phantom-rows — the one in-flight commit is
                            all-or-nothing; nothing else appears;
  * txn-atomicity         — a multi-table txn lands in both tables or
                            neither;
  * ddl-lost              — acked DDL (tables, snapshots, view defs)
                            survives;
  * orphan-gc             — Engine.open sweeps `*.tmp` crash leftovers;
  * recovery-summary      — the reopen reports its recovery summary;
  * mview-exactly-once    — after the first post-restart commit the
                            materialized view equals a recompute of its
                            defining query over the recovered base
                            table (no gap, no double-apply);
  * cdc-exactly-once      — resuming the mirror from its durable
                            watermark via cdc.delta_events converges
                            the mirror to the source exactly once
                            (re-seeding from 0 only when the delta
                            floor passed the watermark — a fence still
                            covering the resume must serve it);
  * asof-read             — an acked named snapshot reads bit-identical
                            to the view pinned at its creation, across
                            background merges (the merge fence serves
                            the pre-merge history);
  * gc-reachable-object-deleted — every object file referenced by a
                            live segment or a held merge fence exists:
                            fence GC goes manifest-durable-first, so a
                            crash leaks unreferenced files but never
                            deletes reachable ones.

Quorum scenario:

  * quorum-acked-lost     — every majority-acked entry (not yet
                            checkpoint-truncated) is present with an
                            intact payload in the union of EVERY
                            majority subset of replicas;
  * quorum-replica-load   — a replica reopens cleanly from any torn
                            state (tails drop, epochs never corrupt).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from matrixone_tpu.cdc import CdcTask, FileWatermark
from matrixone_tpu.logservice.replicated import ReplicaCore, merge_majority
from matrixone_tpu.storage.engine import ROWID, Engine, live_rows
from matrixone_tpu.storage.fileservice import MemoryFS

from tools.mocrash import workload as W


@dataclasses.dataclass
class Finding:
    point: int
    event: str
    variant: str
    invariant: str
    detail: str

    def format(self) -> str:
        return (f"mocrash: point={self.point} event={self.event} "
                f"variant={self.variant} "
                f"invariant={self.invariant}: {self.detail}")


def variant_name(torn: float, lossy: bool) -> str:
    return f"torn{int(torn * 100)}" + ("+lossy" if lossy else "")


def _read_main(eng: Engine, table: str = "t_main",
               snapshot_ts: Optional[int] = None) -> Dict[int, tuple]:
    """id -> (batch, v, s) of the visible rows (or the AS OF view)."""
    t = eng.get_table(table)
    out: Dict[int, tuple] = {}
    for arrays, validity, dicts, n in map(live_rows, t.iter_chunks(
            ["id", "batch", "v", "s"], 1 << 20, snapshot_ts=snapshot_ts)):
        for i in range(n):
            s = (dicts["s"][int(arrays["s"][i])]
                 if validity["s"][i] else None)
            out[int(arrays["id"][i])] = (
                int(arrays["batch"][i]) if validity["batch"][i] else None,
                int(arrays["v"][i]) if validity["v"][i] else None, s)
    return out


def _read_pair(eng: Engine) -> set:
    t = eng.get_table("t_pair")
    out = set()
    for arrays, _v, _d, n in map(live_rows, t.iter_chunks(["id"], 1 << 20)):
        for i in range(n):
            out.add(int(arrays["id"][i]))
    return out


def _read_mview(eng: Engine) -> Dict[Optional[str], tuple]:
    t = eng.get_table("mv1")
    cols = [c for c, _ in t.meta.schema]          # s, sv, c
    out: Dict[Optional[str], tuple] = {}
    for arrays, validity, dicts, n in map(
            live_rows, t.iter_chunks(cols, 1 << 20)):
        for i in range(n):
            key = (dicts[cols[0]][int(arrays[cols[0]][i])]
                   if validity[cols[0]][i] else None)
            out[key] = (int(arrays[cols[1]][i]),
                        int(arrays[cols[2]][i]))
    return out


def _mview_oracle(main: Dict[int, tuple]
                  ) -> Dict[Optional[str], tuple]:
    groups: Dict[Optional[str], List[tuple]] = {}
    for _id, (_b, v, s) in main.items():
        groups.setdefault(s, []).append((v,))
    return {s: (sum(v for (v,) in rows if v is not None), len(rows))
            for s, rows in groups.items()}


def check_engine(world: "W.EngineWorld", k: int, torn: float,
                 lossy: bool, u: Optional[dict] = None
                 ) -> List[Finding]:
    evs = world.journal.events()
    label = evs[k].label() if k < len(evs) else "end"
    var = variant_name(torn, lossy)

    def F(inv: str, detail: str) -> Finding:
        return Finding(k, label, var, inv, detail)

    if u is None:
        u = world.journal.materialize(k, torn, lossy)
    tn_fs = u.get("tn") or MemoryFS()
    try:
        eng = Engine.open(tn_fs)
    except Exception as e:   # noqa: BLE001 — a recovery that cannot
        # open from a disciplined crash state IS the finding
        return [F("recovery-opens",
                  f"Engine.open raised {type(e).__name__}: {e}")]
    findings: List[Finding] = []
    if eng.recovery_summary is None:
        findings.append(F("recovery-summary",
                          "Engine.open emitted no recovery summary"))
    left = tn_fs.orphans()
    if left:
        findings.append(F("orphan-gc",
                          f"orphan tmp files survived open: {left}"))

    expected, pair_exp, ddl, inflight = world.fold(k)

    # ---- acked DDL survives
    for name in sorted(ddl):
        if inflight is not None and inflight.op == "snapdrop" \
                and inflight.table == name:
            continue       # the in-flight drop may have applied
        if name.startswith("snap"):
            if name not in eng.snapshots:
                findings.append(F("ddl-lost",
                                  f"acked snapshot {name} missing"))
        elif name not in eng.tables:
            findings.append(F("ddl-lost", f"acked {name!r} missing"))
    if "t_main" not in ddl or "t_main" not in eng.tables:
        return findings          # nothing further can be checked

    # ---- every object a live segment or a held merge fence references
    # must still exist: fence GC must go manifest-durable-first, so a
    # crash can only leak unreferenced files, never delete reachable ones
    missing = sorted({
        s.obj_path for t2 in eng.tables.values()
        for s in list(t2.segments) + [fs_ for f2 in
                                      getattr(t2, "fences", [])
                                      for fs_ in f2.segments]
        if s.obj_path is not None and not tn_fs.exists(s.obj_path)})
    if missing:
        findings.append(F(
            "gc-reachable-object-deleted",
            f"{len(missing)} reachable object file(s) gone: "
            f"{missing[:4]}"))
        return findings     # reads below would just raise on them

    # ---- acked commits visible, in-flight commit all-or-nothing
    try:
        actual = _read_main(eng)
        actual_pair = (_read_pair(eng) if "t_pair" in eng.tables
                       else set())
    except Exception as e:   # noqa: BLE001 — an unreadable recovered
        # table (torn object bytes behind a durable manifest) IS the
        # durability finding, not a sweep error
        findings.append(F("acked-commit-lost",
                          f"recovered table unreadable: "
                          f"{type(e).__name__}: {e}"))
        return findings

    # ---- AS OF reads through a surviving snapshot stay bit-exact
    # across background merges (the fence serves the pre-merge view)
    for a in world.acks:
        if a.op != "snapshot" or a.event_hi > k or not a.rows \
                or a.table not in eng.snapshots:
            continue
        try:
            got = _read_main(eng, snapshot_ts=eng.snapshots[a.table])
        except Exception as e:   # noqa: BLE001 — same rung as above
            findings.append(F("asof-read",
                              f"AS OF {a.table} raised "
                              f"{type(e).__name__}: {e}"))
            continue
        if got != a.rows:
            miss = sorted(set(a.rows) - set(got))[:6]
            extra = sorted(set(got) - set(a.rows))[:6]
            findings.append(F(
                "asof-read",
                f"AS OF {a.table} diverged from its pinned view "
                f"(missing ids {miss}, extra {extra})"))
    candidates: List[Tuple[Dict[int, tuple], set]] = [
        (expected, pair_exp)]
    if inflight is not None:
        if inflight.op in ("insert", "txn2"):
            alt = dict(expected)
            alt.update(inflight.rows)
            candidates.append((alt, pair_exp
                               | set(inflight.pair_ids)))
        elif inflight.op == "delete":
            alt = {i: r for i, r in expected.items()
                   if i not in inflight.ids}
            candidates.append((alt, pair_exp))
    if (actual, actual_pair) not in [tuple(c) for c in candidates]:
        findings.append(_classify(F, actual, actual_pair, expected,
                                  pair_exp, inflight))
        return findings     # downstream comparisons would double-report

    # ---- the delta economy reconverges exactly once
    if "mv1" in ddl:
        try:
            eng.commit_txn(None, {}, {})    # first post-restart commit
            #                                 drives the mview rebuild
            mv = _read_mview(eng)
            oracle = _mview_oracle(_read_main(eng))
            if mv != oracle:
                findings.append(F(
                    "mview-exactly-once",
                    f"view {mv} != recompute {oracle}"))
        except Exception as e:   # noqa: BLE001 — see recovery-opens
            findings.append(F("mview-exactly-once",
                              f"catch-up raised "
                              f"{type(e).__name__}: {e}"))
    findings.extend(_check_cdc(world, F, u, eng))
    return findings


def _check_cdc(world, F, u, eng) -> List[Finding]:
    mirror_fs = u.get("mirror") or MemoryFS()
    try:
        meng = W.mirror_engine(mirror_fs)
        wm = FileWatermark(mirror_fs, world.mirror_wm_path)
        task = CdcTask(eng, "t_main",
                       W.EngineSink(meng, "t_main"),
                       from_ts=wm.load())
        try:
            task.backfill(from_ts=task.watermark)
        except ValueError as e:
            # only a GC'd fence may refuse: below-or-at the delta floor
            # the re-seed is the documented degrade rung; a refusal
            # ABOVE the floor means the fence failed to serve a resume
            # it still covers — that's the finding, not a fallback
            floor = getattr(eng.get_table("t_main"), "delta_floor", 0)
            if task.watermark > floor:
                return [F("cdc-exactly-once",
                          f"fenced resume refused above the delta "
                          f"floor ({task.watermark} > {floor}): {e}")]
            W._clear_table(meng, "t_main")
            task.watermark = 0
            task.backfill(from_ts=0)
        wm.store(task.watermark)
        got = _read_main(meng)
        src = _read_main(eng)
        if got != src:
            missing = sorted(set(src) - set(got))[:6]
            extra = sorted(set(got) - set(src))[:6]
            return [F("cdc-exactly-once",
                      f"mirror diverged after watermark resume "
                      f"(missing ids {missing}, extra {extra})")]
    except Exception as e:   # noqa: BLE001 — see recovery-opens
        return [F("cdc-exactly-once",
                  f"mirror resume raised {type(e).__name__}: {e}")]
    return []


def _classify(F, actual, actual_pair, expected, pair_exp,
              inflight) -> Finding:
    lost = [i for i in expected if i not in actual
            or actual[i] != expected[i]]
    if lost:
        return F("acked-commit-lost",
                 f"{len(lost)} acked row(s) missing/changed, ids "
                 f"{sorted(lost)[:6]}")
    if inflight is not None and inflight.op == "txn2":
        got_main = all(i in actual for i in inflight.ids)
        got_pair = set(inflight.pair_ids) <= actual_pair
        if got_main != got_pair:
            return F("txn-atomicity",
                     f"multi-table txn half-applied (t_main={got_main}"
                     f", t_pair={got_pair})")
    if inflight is not None and inflight.op in ("insert", "txn2"):
        got = [i for i in inflight.ids if i in actual]
        if 0 < len(got) < len(inflight.ids):
            return F("partial-commit-visible",
                     f"in-flight insert partially visible: "
                     f"{len(got)}/{len(inflight.ids)} rows")
    extra = [i for i in actual if i not in expected
             and (inflight is None or i not in inflight.ids)]
    if extra:
        return F("phantom-rows",
                 f"rows never acked nor in flight: {sorted(extra)[:6]}")
    if actual_pair != pair_exp and (
            inflight is None
            or actual_pair != pair_exp | set(inflight.pair_ids)):
        return F("acked-commit-lost",
                 f"t_pair diverged: {sorted(actual_pair)} vs "
                 f"{sorted(pair_exp)}")
    return F("state-divergence",
             "recovered state matches no legal ack prefix")


# ------------------------------------------------------------- quorum

def check_quorum(world: "W.QuorumWorld", k: int, torn: float,
                 lossy: bool, u: Optional[dict] = None
                 ) -> List[Finding]:
    evs = world.journal.events()
    label = evs[k].label() if k < len(evs) else "end"
    var = variant_name(torn, lossy)
    if u is None:
        u = world.journal.materialize(k, torn, lossy)
    cores = []
    for i in range(world.n_replicas):
        try:
            cores.append(ReplicaCore(u.get(f"rep{i}") or MemoryFS()))
        except Exception as e:   # noqa: BLE001 — a replica that cannot
            # reload from its own crash state IS the finding
            return [Finding(k, label, var, "quorum-replica-load",
                            f"rep{i} reload raised "
                            f"{type(e).__name__}: {e}")]
    trunc_upto = 0
    for a in world.acks:
        # exemption starts the moment the truncate STARTED: a partially
        # propagated truncation may legitimately have dropped entries
        if a.op == "qtruncate" and a.event_lo <= k:
            trunc_upto = max(trunc_upto, a.upto)
    acked = [a for a in world.acks
             if a.op == "qappend" and a.event_hi <= k
             and a.seq > trunc_upto]
    findings: List[Finding] = []
    n = world.n_replicas
    for pair in [(i, j) for i in range(n) for j in range(i + 1, n)]:
        reads = [(cores[i].truncated_upto,
                  {s: p for s, (_e, p) in cores[i].entries.items()})
                 for i in pair]
        upto, merged = merge_majority(reads)
        for a in acked:
            if a.seq <= upto:
                continue
            if merged.get(a.seq) != a.payload:
                findings.append(Finding(
                    k, label, var, "quorum-acked-lost",
                    f"seq {a.seq} acked by a majority but absent/"
                    f"corrupt in the union of replicas {pair}"))
    return findings
