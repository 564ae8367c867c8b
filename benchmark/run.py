"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it makes the data from `--seed`, loads it
through the engine in several commits, checkpoints, closes, re-opens the
engine from the flushed files, starts `MOServer` in process and warms the
cell's own statement shapes.  The load generator is a child process that
never imports jax (`loadgen.py`); every timed operation is one statement
over the MySQL wire, from the send to the last row received, on the
client's clock.  After the window the answers the window received are
compared with the plain references that the traffic's templates name.

The harness knows no cell by name.  A cell names a configuration
(`configs/<config>.json`, which names its loader module) and a traffic mix
(`traffic/<mix>.json`, whose templates name their reference module and
their work function); a metric is `metrics/<name>.json`, which names its
reader module under `readers/`.  See README.md.

The last line of standard output is the result object.  Phase lines come
before it.  `--rehearse` is the CPU rehearsal at toy size: the same code
path, but it never prints the result line and always exits 3, so a CPU
number can never stand under a device metric's name.
"""

import time

T_PROCESS = time.perf_counter()

import argparse                                   # noqa: E402
import gc                                         # noqa: E402
import importlib                                  # noqa: E402
import json                                       # noqa: E402
import os                                         # noqa: E402
import shutil                                     # noqa: E402
import statistics                                 # noqa: E402
import subprocess                                 # noqa: E402
import sys                                        # noqa: E402
import tempfile                                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import loadgen                                    # noqa: E402
import traffic                                    # noqa: E402
import xplane                                     # noqa: E402

TRACE_SLICE_S = 5.0
OFFRAMPS = ('mo_fusion_compile_total{outcome="trace_fail"}',
            'mo_fusion_dispatch_total{kind="eager"}',
            "mo_exchange_degrade_total")


def emit(**fields):
    print(json.dumps(fields), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def module(kind, name):
    """`loaders/<name>.py`, `references/<name>.py`, `readers/<name>.py`."""
    return importlib.import_module(f"{kind}.{name}")


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json names no {what} {name!r}")


def within(numbers):
    """Every number compared is at or under its limit."""
    return all(v <= limit for v, limit in numbers.values())


def cell_metrics(manifest, cell_name, group):
    return [m for m in manifest[group]
            if cell_name in m.get("workloads", [cell_name])]


# ------------------------------------------------------------------ meters

class Meters:
    """Compile events (jax.monitoring), read as deltas."""

    def __init__(self):
        from jax import monitoring
        self.compiles = []           # (seconds, fun_name) incl. cache hits
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((seconds, kw.get("fun_name", "?")))

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def n_compiled(self):
        """Programs the backend really compiled (persistent-cache hits
        pass through the same event and are taken off)."""
        return len(self.compiles) - self.cache_hits


def counters():
    """Every counter and gauge of the program's registry, flat:
    `name{label="value",...}` -> value; and the block cache's device tier
    as `blockcache.device_tier.<field>`."""
    from matrixone_tpu.storage import blockcache
    from matrixone_tpu.utils import metrics as M
    out = {}
    for name, snap in M.REGISTRY.snapshot().items():
        for v in snap.get("values", []):
            labels = ",".join(f'{k}="{val}"'
                              for k, val in sorted(v["labels"].items()))
            out[f"{name}{{{labels}}}" if labels else name] = v["value"]
    for k, v in blockcache.CACHE.stats()["device_tier"].items():
        if isinstance(v, (int, float)):
            out[f"blockcache.device_tier.{k}"] = v
    return out


def memory_peak(jax):
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


def server_spans():
    """The program's completed spans, oldest first."""
    from matrixone_tpu.utils import motrace
    return [s for tid in motrace.TRACER.trace_ids()
            for s in motrace.TRACER.spans_of(tid)]


# -------------------------------------------------------------------- child

class Generator:
    """The load generator child (see loadgen.py)."""

    def __init__(self, port, plan):
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True)
        self._say({"port": port, "clients": plan["clients"],
                   "session": plan["session"],
                   "statements": plan["statements"],
                   "starts": plan["starts"]})
        if not self._hear().get("ready"):
            raise RuntimeError("the load generator did not get ready")

    def _say(self, obj):
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def _hear(self):
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"the load generator ended (exit {self.proc.wait()})")
        return json.loads(line)

    def go(self, seconds):
        self._say({"go": seconds})

    def result(self):
        out = self._hear()
        self.close()
        return out

    def close(self):
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(timeout=60)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        self.proc.stdout.close()


# ---------------------------------------------------------------- the run

def judge(references, cfg, data, executed, control=False):
    """Each reference module judges the statements of the templates that
    name it.  -> (numbers {name: [value, limit]}, facts).  With `control`
    the statements are first answered by the module's own control."""
    numbers, facts = {}, {}
    for name in references:
        ref = module("references", name)
        own = [e for e in executed if e["reference"] == name]
        if control:
            own = ref.control_answers(cfg, data, own)
        n, f = ref.compare(cfg, data, own)
        if set(n) & set(numbers):
            raise ValueError(f"references/{name}.py reports a number that "
                             f"another reference of this mix reports too: "
                             f"{sorted(set(n) & set(numbers))}")
        numbers.update(n)
        facts.update(f)
    return numbers, facts


def run_cell(workload, seed, seconds, trace, rehearse=False, control=False,
             fault=None, manifest="BENCHMARK.json"):
    """One run.  -> the result object.  `control` also answers
    the window's statements with the references' controls; `fault` is a
    function (server, engine) -> None that breaks the timed path (tests);
    `manifest` is the manifest's path from the repo's root."""
    manifest = load_json(ROOT, manifest)
    cell = find(manifest["workloads"], workload, "workload")
    cfg_entry = find(manifest["configs"], cell["config"], "configuration")
    cfg = load_json(ROOT, cfg_entry["file"])
    if rehearse:
        cfg.update(cfg.get("rehearsal", {}))
    mix = traffic.load_mix(cell["traffic"])
    loader = module("loaders", cfg["loader"])
    references = sorted({t["reference"] for t in mix["templates"]})

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if rehearse:
        if device["platform"] != "cpu":
            raise SystemExit("--rehearse is the CPU rehearsal; run it with "
                             "JAX_PLATFORMS=cpu")
    elif device["platform"] != "tpu" or device["count"] < cell["chips"]:
        raise SystemExit(f"{workload} needs {cell['chips']} TPU chip(s); "
                         f"jax reports {device}")
    import matrixone_tpu  # noqa: F401  (enables x64)
    from matrixone_tpu.frontend.server import MOServer
    from matrixone_tpu.storage import blockcache
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import LocalFS
    from matrixone_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    meters = Meters()
    emit(phase="device", **device, jax=jax.__version__,
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         workload=workload, seed=seed, seconds=seconds, trace=trace)

    workdir = tempfile.mkdtemp(prefix="mo_bench_")
    trace_dir = tempfile.mkdtemp(prefix="mo_bench_trace_")
    srv = gen = None
    try:
        # ---- set-up: generate, load, checkpoint, re-open, prepare, warm
        t0 = time.perf_counter()
        data = loader.generate(cfg, seed)
        t_generate = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng = Engine(LocalFS(workdir))
        loader.load(cfg, data, eng)
        t_load = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.checkpoint()
        eng.close()
        eng = Engine.open(LocalFS(workdir))
        t_reopen = time.perf_counter() - t0
        srv = MOServer(engine=eng, port=0).start()
        t0 = time.perf_counter()
        conn = loadgen.Connection(srv.port, timeout=3600.0)
        numbers = dict(loader.prepare(cfg, data, conn))
        t_prepare = time.perf_counter() - t0
        plan = traffic.generate(mix, cfg, loader.pools(cfg, data), seed)
        table_rows = loader.rows(cfg, data)
        gen = Generator(srv.port, plan)
        t0 = time.perf_counter()
        for sql in plan["session"]:
            conn.query(sql)
        n_templates = len(mix["templates"])
        for _ in range(2):               # the cell's own shapes, twice
            for sql in plan["statements"][-n_templates:]:
                conn.query(sql)
        t_warm = time.perf_counter() - t0
        if trace:
            conn.query("select mo_ctl('trace', 'on')")
        if fault is not None:
            fault(srv, eng)
        setup = {"generate_s": t_generate, "load_s": t_load,
                 "checkpoint_reopen_s": t_reopen, "prepare_s": t_prepare,
                 "warm_s": t_warm, "compiles_in_setup": meters.n_compiled(),
                 "compile_cache_hits_in_setup": meters.cache_hits}
        emit(phase="setup", **setup,
             slowest_compiles=[[round(s, 1), f] for s, f in
                               sorted(meters.compiles, reverse=True)[:5]],
             disk_bytes=sum(os.path.getsize(os.path.join(d, f))
                            for d, _, fs in os.walk(workdir) for f in fs),
             bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use")
                           for d in devs])

        # ---- the window
        before, compiled0 = counters(), meters.n_compiled()
        recorder = xplane.Recorder(trace_dir) if trace else None
        setup["setup_s"] = time.perf_counter() - T_PROCESS
        gen.go(seconds)
        if recorder is not None:
            slice_s = min(TRACE_SLICE_S, seconds / 2)
            time.sleep((seconds - slice_s) / 2)
            recorder.start()
            time.sleep(slice_s)
            recorder.stop()
        window = gen.result()
        gen = None
        after = counters()
        peak = memory_peak(jax)
        spans = server_spans() if trace else []
        compiles_in_window = meters.n_compiled() - compiled0
        conn.close()
        srv.stop()
        srv = None
        eng.close()
        del eng
        blockcache.CACHE.clear()
        gc.collect()
    finally:
        if gen is not None:
            gen.close()
        if srv is not None:
            srv.stop()
        shutil.rmtree(workdir, ignore_errors=True)

    executed = [dict(plan["meta"][idx], client=client, statement=idx,
                     t_send_ns=t_send, t_done_ns=t_done, rows=rows,
                     error=err)
                for client, idx, t_send, t_done, rows, err
                in window["results"]]
    elapsed = (window["t_last_done_ns"] - window["t_start_ns"]) / 1e9
    latency_ms = {}
    for e in executed:
        latency_ms.setdefault(e["template"], []).append(
            (e["t_done_ns"] - e["t_send_ns"]) / 1e6)
    emit(phase="window", statements=len(executed), elapsed_s=elapsed,
         latency_ms_min_median_max={
             t: [min(v), statistics.median(v), max(v)]
             for t, v in latency_ms.items()},
         latency_ms_in_order=[round((e["t_done_ns"] - e["t_send_ns"]) / 1e6, 1)
                              for e in executed[:128]],
         generator_cpu_share=window["cpu_s"] / max(elapsed, 1e-9),
         compiles_in_window=compiles_in_window, memory_peak_bytes=peak,
         device_tier={k.rsplit(".", 1)[1]: v for k, v in after.items()
                      if k.startswith("blockcache.device_tier.")})

    # ---- correct: the answers of the window against the plain reference
    t0 = time.perf_counter()
    ref_numbers, facts = judge(references, cfg, data, executed)
    numbers.update(ref_numbers)
    numbers["offramp_events"] = [
        sum(after.get(k, 0) - before.get(k, 0) for k in OFFRAMPS), 0]
    numbers["window_empty"] = [int(not executed), 0]
    emit(phase="reference", seconds=time.perf_counter() - t0, facts=facts)
    correct = within(numbers)
    result_extra = {}
    if control:
        t0 = time.perf_counter()
        c_numbers, c_facts = judge(references, cfg, data, executed,
                                   control=True)
        result_extra["control"] = {
            "compared": c_numbers, "facts": c_facts,
            "correct": within(c_numbers)}
        emit(phase="control", seconds=time.perf_counter() - t0,
             **result_extra["control"])

    # ---- metrics
    ctx = {"cell": cell, "config": cfg, "device": device,
           "seconds": seconds, "window": window, "executed": executed,
           "table_rows": table_rows, "before": before, "after": after,
           "setup": setup, "compiles_in_window": compiles_in_window,
           "facts": facts, "spans": spans, "trace": None,
           "trace_slice_perf_ns": None}
    if recorder is not None:
        t0 = time.perf_counter()
        ctx["trace_slice_perf_ns"] = (recorder.start_perf_ns,
                                      recorder.stop_perf_ns)
        loaded = xplane.load(recorder.path())
        if loaded["devices"] or not rehearse:   # the CPU has no such plane
            ctx["trace"] = xplane.reduce(
                loaded, *recorder.on_trace_clock(loaded["marker_ns"], spans),
                chips=cell["chips"])
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
        emit(phase="trace", seconds=time.perf_counter() - t0,
             xplane_bytes=os.path.getsize(recorder.path()),
             clock_marker_found=loaded["marker_ns"] is not None,
             spans=len(spans), reduced=ctx["trace"] and {
                 k: ctx["trace"][k] for k in
                 ("longest_gap_s", "module_calls", "busy_s_by_chip")})
    shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in cell_metrics(manifest, workload,
                          "per_layer" if trace else "end_to_end"):
        spec = load_json(HERE, "metrics", m["name"] + ".json")
        value = module("readers", spec["reader"]).read(
            ctx, **spec.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": len(executed),
              "failed": sum(e["error"] is not None for e in executed),
              "metrics": metrics, "device": device}
    if ctx["trace"] is not None:
        result["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                               "idle_gaps": ctx["trace"]["idle_gaps"]}
    result.update(result_extra)
    result["compared"] = {k: {"value": v, "limit": limit}
                          for k, (v, limit) in numbers.items()}
    for k, (v, limit) in numbers.items():
        print(f"compared {k}: {v} (limit {limit})"
              f"{'' if v <= limit else '  <-- over its limit'}",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at toy size; always exits 3")
    ap.add_argument("--control", action="store_true",
                    help="also answer the window's statements with the "
                         "references' controls (never set by the driver)")
    ap.add_argument("--manifest", default="BENCHMARK.json",
                    help="another manifest, from the repo's root: the "
                         "staged cells of benchmark/staged/ (never set by "
                         "the driver)")
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), rehearse=args.rehearse,
                      control=args.control, manifest=args.manifest)
    if args.rehearse:
        emit(rehearsal="cpu, toy size: not a chip run", **result)
        print("rehearsal: no result line", flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # worker threads of the program may outlive main(): leave through
    # os._exit once everything is flushed
    try:
        code = main()
    except SystemExit as e:
        if e.code not in (None, 0):
            print(e.code, file=sys.stderr)
        code = e.code if isinstance(e.code, int) else int(bool(e.code))
    except BaseException:                # noqa: BLE001 - report, then leave
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
