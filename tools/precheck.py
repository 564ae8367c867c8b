"""`python -m tools.precheck` — the repo's one-shot static gate:
molint (invariant checkers, tools/molint/), mokey (trace-capture /
cache-key completeness, tools/mokey/) and bench_guard (scoreboard
regression floors, tools/bench_guard.py), plus opt-in smoke stages:
`--san-smoke` (mosan concurrency stress drill, <30s), `--qa-smoke`
(small moqa differential corpus + planted-bug drill, <30s),
`--trace-smoke` (motrace span-tree round-trip, <30s), `--key-smoke`
(mokey planted fixture pairs, static + one armed runtime audit
round-trip, <30s) and `--crash-smoke` (mocrash capped crash-recovery
sweep + the planted early-truncate violation, <30s).

Independent legs run CONCURRENTLY: the static analyses (molint,
mokey, bench_guard) share nothing but the parsed-AST cache and
overlap freely, while the runtime smokes — which arm process-global
state (sanitizer, canary, key auditor, tracer) — serialize among
themselves on one lock but still overlap the static legs.  Output is
printed per leg in submission order, so the gate reads the same as
the old serial run.

Exit 0 = all gates green; 1 = findings/regressions (details printed).
"""

from __future__ import annotations

import io
import os
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

#: runtime smokes mutate process-global state (arm the sanitizer /
#: canary / key auditor, swap env knobs) — they overlap the static
#: legs but never each other
_RUNTIME_LOCK = threading.Lock()


def _bufprint(buf, *a):
    import builtins
    builtins.print(*a, file=buf)


# each leg is `def run(print)` — the builtin's name rebound to a
# printer writing into THAT leg's buffer (never the process-global
# sys.stdout, which concurrent legs would misattribute)


def _leg(fn, exclusive: bool = False):
    """Run one leg, capturing its output: -> (rc, text).  The leg
    receives a printer bound to its own buffer — redirect_stdout would
    swap the PROCESS-global sys.stdout, which concurrent threads
    misattribute (and a non-LIFO exit order could leave sys.stdout
    pointing at a finished leg's dead buffer)."""
    import functools
    buf = io.StringIO()
    printer = functools.partial(_bufprint, buf)
    try:
        if exclusive:
            with _RUNTIME_LOCK:
                rc = fn(printer)
        else:
            rc = fn(printer)
    except Exception as e:      # noqa: BLE001 — a crashed leg must
        # fail the gate with its traceback, not kill the other legs
        import traceback
        buf.write(traceback.format_exc())
        buf.write(f"leg crashed: {e}\n")
        rc = 1
    return rc, buf.getvalue()


def _molint_leg(root):
    def run(print):
        from tools import molint
        findings, stats = molint.run_checks(root)
        if findings:
            for f in findings:
                print(f.format())
            print(f"molint: {len(findings)} finding(s) across "
                  f"{stats['files']} file(s)")
            return 1
        secs = stats.get("checker_seconds", {})
        slowest = ", ".join(f"{r}={s}s"
                            for r, s in list(secs.items())[:3])
        print(f"molint: ok ({stats['checkers']} checkers, "
              f"{stats['files']} files, "
              f"{stats['suppressions_used']} suppressions; "
              f"slowest: {slowest})")
        return 0
    return run


def _mokey_leg(root):
    def run(print):
        from tools import mokey
        findings, stats = mokey.run_checks(root)
        if findings:
            for f in findings:
                print(f.format())
            print(f"mokey: {len(findings)} finding(s) across "
                  f"{stats['files']} file(s)")
            return 1
        print(f"mokey: ok ({stats['roots']} traced closures, "
              f"{stats['captures']} captures, {stats['files']} files)")
        return 0
    return run


def _bench_leg(root):
    def run(print):
        from tools import bench_guard
        ok, report = bench_guard.check(root)
        for ln in report:
            print(ln)
        if not ok:
            print("bench_guard: REGRESSION")
            return 1
        print("bench_guard: ok")
        return 0
    return run


def _san_leg():
    def run(print):
        from tools import mosan
        rc = 0
        rep = mosan.run_stress()
        if rep["findings"] or rep["errors"]:
            for line in rep["findings_formatted"]:
                print(line)
            for e in rep["errors"]:
                print(e)
            print("san-smoke: FINDINGS")
            rc = 1
        else:
            print(f"san-smoke: clean drill ok ({rep['reads']} reads / "
                  f"{rep['writes']} writes, {rep['edges']} edges)")
        planted = mosan.run_stress(plant="eviction-race")
        caught = any(f["rule"] == "unguarded-mutation"
                     for f in planted["findings"])
        if caught:
            print("san-smoke: planted eviction race caught ok")
        else:
            print("san-smoke: planted eviction race NOT caught")
            rc = 1
        return rc
    return run


def _qa_leg():
    def run(print):
        from tools import moqa
        rc = 0
        rep = moqa.run_smoke()
        for line in rep["findings_formatted"]:
            print(line)
        if rep["findings"]:
            print("qa-smoke: FINDINGS")
            rc = 1
        else:
            print(f"qa-smoke: corpus clean ({rep['queries']} queries, "
                  f"{rep['total_checks']} checks, "
                  f"{rep['seconds']}s)")
        if rep["plant_caught"]:
            print("qa-smoke: planted pad-leak caught ok")
        else:
            print("qa-smoke: planted pad-leak NOT caught")
            rc = 1
        return rc
    return run


def _trace_leg():
    def run(print):
        from tools import motrace as motrace_smoke
        rep = motrace_smoke.run_smoke()
        for e in rep["errors"]:
            print(f"trace-smoke: {e}")
        if rep["ok"]:
            print(f"trace-smoke: span tree + chrome export ok "
                  f"({rep['traces']} traces, {rep['spans']} spans, "
                  f"{rep['seconds']}s)")
            return 0
        print("trace-smoke: FAIL")
        return 1
    return run


def _key_leg():
    def run(print):
        from tools.mokey import plants
        rc = 0
        st = plants.run_static_smoke()
        for bad, caught in sorted(st["caught"].items()):
            if caught:
                print(f"key-smoke: static plant {bad} caught ok")
            else:
                print(f"key-smoke: static plant {bad} NOT caught")
                rc = 1
        if not all(st["clean"].values()):
            print("key-smoke: a clean static twin was flagged")
            rc = 1
        rt = plants.run_runtime_smoke()
        for bad, caught in sorted(rt["caught"].items()):
            if caught:
                print(f"key-smoke: runtime plant {bad} caught ok")
            else:
                print(f"key-smoke: runtime plant {bad} NOT caught")
                rc = 1
        if not all(rt["clean"].values()):
            print("key-smoke: a clean runtime twin was flagged")
            rc = 1
        return rc
    return run


def _crash_leg():
    def run(print):
        from tools import mocrash
        rc = 0
        rep = mocrash.run_smoke()
        for line in rep["findings_formatted"]:
            print(line)
        if rep["findings"]:
            print("crash-smoke: FINDINGS")
            rc = 1
        else:
            print(f"crash-smoke: clean sweep ok ({rep['points']} "
                  f"crash points, {rep['recoveries']} recoveries, "
                  f"{rep['seconds']}s)")
        if rep["plant_caught"]:
            print("crash-smoke: planted early-truncate caught ok")
        else:
            print("crash-smoke: planted early-truncate NOT caught")
            rc = 1
        if rep["merge_plant_caught"]:
            print("crash-smoke: planted merge gc-early caught ok")
        else:
            print("crash-smoke: planted merge gc-early NOT caught")
            rc = 1
        return rc
    return run


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="python -m tools.precheck")
    ap.add_argument("--root", default=None,
                    help="repo root (default: inferred from tools/)")
    ap.add_argument("--skip-bench", action="store_true",
                    help="run only the static analyses (no "
                         "BENCH_*.json history needed)")
    ap.add_argument("--san-smoke", action="store_true",
                    help="also run the mosan stress drill armed "
                         "(writers vs cached readers + the planted "
                         "eviction-race regression; <30s)")
    ap.add_argument("--qa-smoke", action="store_true",
                    help="also run the moqa differential smoke (small "
                         "seeded corpus across the config lattice + "
                         "the planted pad-leak drill; <30s)")
    ap.add_argument("--trace-smoke", action="store_true",
                    help="also run a query with motrace armed and "
                         "assert a well-formed span tree + valid "
                         "Chrome-trace JSON (tools/motrace.py; <30s)")
    ap.add_argument("--key-smoke", action="store_true",
                    help="also run the mokey planted fixture pairs: "
                         "static pass over a planted temp tree + one "
                         "armed runtime audit round-trip (<30s)")
    ap.add_argument("--crash-smoke", action="store_true",
                    help="also run the mocrash crash-recovery smoke: "
                         "a capped clean sweep over every durability "
                         "boundary + the planted early-truncate "
                         "violation (<30s)")
    args = ap.parse_args(argv)

    from tools import molint
    root = os.path.abspath(args.root or molint.repo_root())

    legs = [("molint", _molint_leg(root), False),
            ("mokey", _mokey_leg(root), False)]
    if not args.skip_bench:
        legs.append(("bench_guard", _bench_leg(root), False))
    if args.san_smoke:
        legs.append(("san-smoke", _san_leg(), True))
    if args.qa_smoke:
        legs.append(("qa-smoke", _qa_leg(), True))
    if args.trace_smoke:
        legs.append(("trace-smoke", _trace_leg(), True))
    if args.key_smoke:
        legs.append(("key-smoke", _key_leg(), True))
    if args.crash_smoke:
        legs.append(("crash-smoke", _crash_leg(), True))

    rc = 0
    with ThreadPoolExecutor(max_workers=min(len(legs), 6)) as pool:
        futures = [(name, pool.submit(_leg, fn, exclusive))
                   for name, fn, exclusive in legs]
        for name, fut in futures:       # submission order = old serial
            leg_rc, text = fut.result()
            sys.stdout.write(text)
            if leg_rc:
                print(f"{name}: FAILED", file=sys.stderr)
                rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
