"""Where one Star Schema Benchmark statement's time goes, on the chip.

    chiprun -- python -m tools.trace_ssb [--sf 1] [--templates q1.1 q2.1 ...]

Loads the five tables at `--sf` through the engine (4 commits a table,
checkpoint, close, re-open), serves them over the MySQL wire, and runs each
named template three times with fresh constants: cold (compiles), warm,
and warm with motrace armed and the profiler on.  Per template it prints
one JSON line: the three latencies on the client's clock, what compiled
and for how long, the delta of the fusion / join / device-wait counters over
the traced statement, the self time of every span name on the statement's
thread, and the reduction of the device trace (busy share, time by XLA
module, idle gaps named by the spans that cover them).  `--tiny` rehearses
on the CPU at SF 0.01 and prints no device time.  Every answer is checked
against `utils/ssb.py`'s plain reference.
"""

import argparse
import json
import os
import random
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "benchmark"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FLIGHTS = ["q1.1", "q2.1", "q3.1", "q4.1"]
WATCHED = ("mo_fusion_", "mo_device_wait", "mo_join_", "mo_scan_slice")


def _counters():
    from matrixone_tpu.utils import metrics as M
    out = {}
    for name, snap in M.REGISTRY.snapshot().items():
        if not name.startswith(WATCHED):
            continue
        for v in snap.get("values", []):
            labels = ",".join(f"{k}={val}"
                              for k, val in sorted(v["labels"].items()))
            out[f"{name}{{{labels}}}"] = v["value"]
    return out


def _self_ms(spans):
    """{span name: [count, self ms]} over one statement's spans."""
    child = {}
    for s in spans:
        child[s["psid"]] = child.get(s["psid"], 0) + s["dur_us"]
    out = {}
    for s in spans:
        n, ms = out.get(s["name"], (0, 0.0))
        out[s["name"]] = (n + 1, ms + (s["dur_us"]
                                       - child.get(s["sid"], 0)) / 1e3)
    return {k: [n, round(ms, 2)] for k, (n, ms) in sorted(
        out.items(), key=lambda kv: -kv[1][1])}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=2654435761)
    ap.add_argument("--templates", nargs="*", default=FLIGHTS)
    ap.add_argument("--budget", type=float, default=1500.0,
                    help="start no new template after this many seconds")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import loadgen
    import xplane
    import matrixone_tpu  # noqa: F401
    from matrixone_tpu.frontend.server import MOServer
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import LocalFS
    from matrixone_tpu.utils import motrace, ssb
    platform = jax.devices()[0].platform
    if args.tiny:
        args.sf = 0.01
    elif platform != "tpu":
        raise SystemExit(f"no TPU (jax reports {platform}); --tiny "
                         f"rehearses on the CPU")
    compiles = []
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(
        lambda ev, s, **kw: compiles.append((round(s, 2),
                                             kw.get("fun_name", "?")))
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    t0 = time.perf_counter()
    tables = ssb.gen_ssb(args.sf, args.seed)
    t_gen = time.perf_counter() - t0
    workdir = tempfile.mkdtemp(prefix="mo_ssb_")
    trace_dir = tempfile.mkdtemp(prefix="mo_ssb_trace_")
    t0 = time.perf_counter()
    eng = Engine(LocalFS(workdir))
    ssb.load_ssb(eng, tables, commits=4)
    eng.checkpoint()
    eng.close()
    eng = Engine.open(LocalFS(workdir))
    srv = MOServer(engine=eng, port=0).start()
    conn = loadgen.Connection(srv.port, timeout=3600.0)
    print(json.dumps({"phase": "setup", "platform": platform,
                      "sf": args.sf, "generate_s": round(t_gen, 2),
                      "load_reopen_s": round(time.perf_counter() - t0, 2),
                      "lineorder_rows": len(tables["lineorder"]
                                            ["lo_orderkey"])}), flush=True)
    star = ssb.Star(tables)
    rng = random.Random(args.seed)
    ok = True
    try:
        for name in args.templates:
            if time.perf_counter() - t_start > args.budget:
                print(json.dumps({"template": name, "skipped": "budget"}))
                continue
            line = {"template": name, "latency_s": []}
            for run in ("cold", "warm", "traced"):
                params = ssb.draw_world(rng)
                sql = ssb.render(name, params)
                n0 = len(compiles)
                if run == "traced":
                    conn.query("select mo_ctl('trace', 'clear')")
                    conn.query("select mo_ctl('trace', 'on')")
                    before = _counters()
                    rec = xplane.Recorder(trace_dir)
                    rec.start()
                t0 = time.perf_counter()
                rows = conn.query(sql)
                line["latency_s"].append(round(time.perf_counter() - t0, 3))
                if run == "traced":
                    rec.stop()
                    after = _counters()
                    conn.query("select mo_ctl('trace', 'off')")
                    spans = [s for tid in motrace.TRACER.trace_ids()
                             for s in motrace.TRACER.spans_of(tid)]
                    line["counters"] = {
                        k: after[k] - before.get(k, 0) for k in after
                        if after[k] != before.get(k, 0)}
                    line["span_self_ms"] = _self_ms(spans)
                    loaded = xplane.load(rec.path())
                    if loaded["devices"]:
                        red = xplane.reduce(loaded, *rec.on_trace_clock(
                            loaded["marker_ns"], spans), chips=1)
                        line["device"] = {
                            k: red[k] for k in ("busy_s", "window_s",
                                                "device_ops", "idle_gaps")}
                    shutil.rmtree(trace_dir, ignore_errors=True)
                else:
                    line[f"compiles_{run}"] = sorted(
                        compiles[n0:], reverse=True)[:6]
                    line[f"n_compiles_{run}"] = len(compiles) - n0
                want = ssb.answer(star, name, params)
                got = [tuple(None if v is None else
                             (int(v) if isinstance(w, int) else v)
                             for v, w in zip(r, wr))
                       for r, wr in zip(rows, want)]
                if len(rows) != len(want) or sorted(
                        got, key=repr) != sorted(want, key=repr):
                    ok = False
                    line["wrong"] = {"run": run, "got": rows[:2],
                                     "want": want[:2]}
            print(json.dumps(line), flush=True)
    finally:
        conn.close()
        srv.stop()
        eng.close()
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": ok, "seconds": round(time.perf_counter()
                                                 - t_start, 1)}), flush=True)
    return 3 if args.tiny else int(not ok)


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
