"""Bench regression guard: fail when the latest round's headline metrics
regress >20% against the best earlier round.

The r05 postmortem was a scoreboard that silently stopped trending; the
serving PR adds caches that could just as silently eat the scan-path
wins of PRs 1/3.  This tool reads every BENCH_*.json in the repo (the
driver's per-round records: {"n": round, "tail": "...last stdout..."}),
extracts the one-line JSON metric contract (top-level + extra_metrics),
and compares the LATEST round against the best PRIOR value of the same
metric family on the same backend.  Shape suffixes are normalized away
(ivfflat_search_qps_200000x256_top20_nprobe8 -> ivfflat_search_qps) so
rounds at different scales still guard the family; only higher-is-better
units (qps, rows/s) are guarded.

Usage: python tools/bench_guard.py [--dir REPO] [--tolerance 0.2]
Exit 0 = no regression, 1 = regression (or latest round unreadable).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

_GUARDED_UNITS = {"qps", "rows/s"}


def family(metric: str) -> str:
    """Strip shape/config suffixes: everything from the first numeric
    segment on (ivfflat_search_qps_200000x256_top20_nprobe8 and
    tpch_q1_rows_per_sec_6001215 both reduce to their family)."""
    parts = metric.split("_")
    out = []
    for p in parts:
        if re.fullmatch(r"\d+(x\d+)?(dev)?|top\d+|nprobe\d+(x\d+dev)?", p):
            break
        out.append(p)
    return "_".join(out) or metric


def _entries_of(path: str):
    """Every metric entry (top-level + extra_metrics) of one round
    record, or None if unreadable."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None
    lines = [ln for ln in str(rec.get("tail", "")).splitlines()
             if ln.startswith("{")]
    if not lines:
        return None
    try:
        top = json.loads(lines[-1])
    except ValueError:
        return None
    return int(rec.get("n", 0)), [top] + list(top.get("extra_metrics")
                                              or [])


def dispatch_counts_of(path: str) -> dict:
    """{(family, backend): fused_dispatches} for one round record —
    the per-family device-dispatch counts the budget check guards
    (LOWER is better: a fusion regression shows up as more dispatches
    long before wall-clock moves on a noisy box)."""
    got = _entries_of(path)
    out: dict = {}
    if got is None:
        return out
    for m in got[1]:
        cnt = m.get("fused_dispatches")
        if not isinstance(cnt, (int, float)) or cnt <= 0:
            continue
        key = (family(str(m.get("metric", ""))),
               str(m.get("backend", "")))
        out[key] = max(out.get(key, 0.0), float(cnt))
    return out


def metrics_of(path: str):
    """-> (round_n, {(family, backend): value}) or None if unreadable."""
    got = _entries_of(path)
    if got is None:
        return None
    rec_n, entries = got
    out = {}
    for m in entries:
        unit = m.get("unit")
        val = m.get("value")
        if unit not in _GUARDED_UNITS or not isinstance(val, (int, float)) \
                or val <= 0:
            continue
        key = (family(str(m.get("metric", ""))),
               str(m.get("backend", "")))
        out[key] = max(out.get(key, 0.0), float(val))
    return rec_n, out


def check(bench_dir: str, tolerance: float = 0.2):
    """-> (ok, report_lines)."""
    rounds = []
    unreadable = []
    # natural order so BENCH_r100 sorts after BENCH_r99 (lexicographic
    # order would break the newest-round detection at two-digit rounds);
    # BENCH_FLOORS.json is the floors sidecar, not a round record
    paths = sorted(
        (p for p in glob.glob(os.path.join(bench_dir, "BENCH_*.json"))
         if os.path.basename(p) != "BENCH_FLOORS.json"),
        key=lambda p: [int(t) if t.isdigit() else t for t in
                       re.split(r"(\d+)", os.path.basename(p))])
    for path in paths:
        got = metrics_of(path)
        if got is None:
            unreadable.append(os.path.basename(path))
        else:
            rounds.append((got[0], os.path.basename(path), got[1]))
    rounds.sort()
    report = []
    # the newest record being unreadable IS the failure this guard
    # exists for: a bench crash would otherwise drop the round and the
    # comparison would silently fall back to the previous one
    if paths and os.path.basename(paths[-1]) in unreadable:
        report.append(f"FAIL latest bench record "
                      f"{os.path.basename(paths[-1])} is unreadable — "
                      f"the newest round cannot be verified")
        return False, report
    for name in unreadable:
        report.append(f"WARN unreadable bench record {name} (skipped)")
    # explicit absolute floors override history — the escape hatch for a
    # deliberate methodology change (e.g. r05 rerouted Q1 through the
    # object store: honest numbers dropped, history would mis-flag it)
    floors = {}
    budgets = {}
    floors_path = os.path.join(bench_dir, "BENCH_FLOORS.json")
    if os.path.exists(floors_path):
        try:
            with open(floors_path) as f:
                raw = json.load(f)
            # "_"-prefixed keys are sidecar sections, not floor
            # families: _comment, and _dispatch_budgets — the
            # per-family device-dispatch ceilings (LOWER is better;
            # a broken fusion shows up as dispatch count long before
            # wall-clock moves on a share-throttled box)
            floors = {(fam, be): float(v)
                      for fam, per_be in raw.items()
                      if isinstance(per_be, dict)
                      and not fam.startswith("_")
                      for be, v in per_be.items()}
            budgets = {(fam, be): float(v)
                       for fam, per_be in
                       (raw.get("_dispatch_budgets") or {}).items()
                       if isinstance(per_be, dict)
                       for be, v in per_be.items()}
        except (OSError, ValueError, TypeError) as e:
            report.append(f"WARN unreadable {floors_path}: {e}")
    if not rounds or (len(rounds) < 2 and not floors):
        # no round record (none has been taken on a chip yet) or a
        # single one without floors: nothing to hold anything to
        report.append(f"bench_guard: only {len(rounds)} readable round(s)"
                      f" in {bench_dir}; nothing to compare")
        return True, report
    latest_n, latest_name, latest = rounds[-1]
    best: dict = {}
    for n, name, ms in rounds[:-1]:
        for key, v in ms.items():
            if v > best.get(key, (0.0, ""))[0]:
                best[key] = (v, name)
    ok = True
    for key in sorted(set(best) | set(floors)):
        fam, backend = key
        cur = latest.get(key)
        if key in floors:
            floor_v, src = floors[key], "BENCH_FLOORS.json"
            floor = floor_v                  # absolute, pre-tolerated
        elif key in best:
            floor_v, src = best[key]
            floor = floor_v * (1.0 - tolerance)
        else:
            continue
        if cur is None:
            report.append(f"WARN {fam} [{backend}]: absent from "
                          f"{latest_name} (floor {floor_v:g} per {src})"
                          f" — config drift or a dropped trend line")
            continue
        if cur < floor:
            ok = False
            report.append(
                f"FAIL {fam} [{backend}]: {cur:g} in {latest_name} is "
                f"below floor {floor:g} (from {floor_v:g} per {src})")
        else:
            report.append(f"ok   {fam} [{backend}]: {cur:g} vs floor "
                          f"{floor:g} ({src})")
    # dispatch-count budgets (inverted guard: latest must stay AT OR
    # UNDER the ceiling) — only the latest round is judged; a family
    # absent from it is a WARN like the floor case above
    if budgets:
        counts = dispatch_counts_of(
            os.path.join(bench_dir, latest_name))
        for key in sorted(budgets):
            fam, backend = key
            cap = budgets[key]
            cur = counts.get(key)
            if cur is None:
                report.append(
                    f"WARN dispatch budget {fam} [{backend}]: no "
                    f"fused_dispatches in {latest_name} (budget "
                    f"{cap:g})")
                continue
            if cur > cap:
                ok = False
                report.append(
                    f"FAIL dispatch budget {fam} [{backend}]: "
                    f"{cur:g} dispatches in {latest_name} exceeds "
                    f"budget {cap:g} (fusion regression)")
            else:
                report.append(
                    f"ok   dispatch budget {fam} [{backend}]: "
                    f"{cur:g} <= {cap:g}")
    return ok, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--tolerance", type=float, default=0.2)
    args = ap.parse_args(argv)
    ok, report = check(args.dir, args.tolerance)
    for line in report:
        print(line)
    print("bench_guard:", "PASS" if ok else "REGRESSION")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
