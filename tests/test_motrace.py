"""motrace: end-to-end distributed tracing + the scrapeable metrics
plane (matrixone_tpu/utils/motrace.py, utils/metrics.py render/snapshot,
tools/moscrape, tools/motrace smoke).

Covers the PR-12 acceptance surface:
  * span trees for ordinary statements (root -> parse/run/plan);
  * cross-process propagation: a CN session -> worker offload -> TN
    commit statement produces ONE trace_id whose Chrome export carries
    spans from >= 2 logical processes with parent/child links intact
    across the RPC hop;
  * chaos-marker: a breaker-open / transport-lost worker offload
    records the local fallback as a span event (PR-2 injector);
  * StatementRecorder span-summary columns, slow-query tree persist,
    old-schema auto-recreate, flush-on-close;
  * Prometheus text exposition that a strict parser accepts, plus the
    Registry.snapshot()/Histogram.quantile public read API.

And of the split of the `run` span (PR 27): the scan path's spans on
the prefetch thread through `motrace.bind`, the generator rule, the
ring's per-trace index under overflow, and the counters at the same
boundaries.
"""

import json
import os
import re
import tempfile

import numpy as np
import pytest

from matrixone_tpu.frontend import Session
from matrixone_tpu.storage.engine import Engine, TableMeta
from matrixone_tpu.storage.fileservice import MemoryFS
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.utils import motrace
from matrixone_tpu.utils.trace import STMT_TABLE, StatementRecorder


@pytest.fixture
def tracer():
    tr = motrace.TRACER
    was = (tr.armed, tr.sample, tr.slow_ms)
    tr.arm(sample=1.0)
    tr.slow_ms = 0.0
    tr.clear()
    yield tr
    tr.armed, tr.sample, tr.slow_ms = was
    tr.clear()


@pytest.fixture
def sess():
    s = Session(catalog=Engine(MemoryFS()))
    yield s
    s.close()


def _tree_names(node, depth=0):
    out = [(depth, node["name"], node["proc"])]
    for c in node["children"]:
        out.extend(_tree_names(c, depth + 1))
    return out


# ------------------------------------------------------------- disarmed
def test_disarmed_is_noop(sess):
    tr = motrace.TRACER
    assert not tr.armed          # MO_TRACE defaults off under pytest
    tr.clear()
    assert motrace.span("x") is motrace._NOOP
    assert motrace.statement_span("select 1") is motrace._NOOP
    sess.execute("create table d0 (a bigint)")
    sess.execute("insert into d0 values (1)")
    assert tr.trace_ids() == []
    # events/annotations are dropped silently
    motrace.event("nothing")
    motrace.annotate(k=1)
    h = {}
    motrace.inject(h)
    assert h == {}


def test_head_sampling_zero_records_nothing(tracer, sess):
    tracer.sample = 0.0
    sess.execute("create table s0 (a bigint)")
    sess.execute("insert into s0 values (1)")
    assert tracer.trace_ids() == []


# ----------------------------------------------------------- span trees
def test_statement_span_tree_shape(tracer, sess):
    sess.execute("create table t1 (a bigint, b double)")
    sess.execute("insert into t1 values (1, 1.5), (2, 2.5), (1, 3.0)")
    sess.execute("select a, sum(b) from t1 group by a order by a")
    tids = tracer.trace_ids()
    assert len(tids) == 3        # one trace per statement
    roots = motrace.tree(tids[-1])
    assert len(roots) == 1
    flat = _tree_names(roots[0])
    names = [n for _, n, _ in flat]
    assert names[0] == "statement"
    assert "parse" in names and "run" in names and "plan" in names
    # parse/run are direct children of the root
    kids = {c["name"] for c in roots[0]["children"]}
    assert {"parse", "run"} <= kids
    # every parent link resolves inside the trace
    spans = tracer.spans_of(tids[-1])
    sids = {sp["sid"] for sp in spans}
    for sp in spans:
        assert sp["psid"] == "" or sp["psid"] in sids


def test_reentrant_execute_nests_not_forks(tracer, sess):
    """A nested execute (dynamic-table refresh) must join the outer
    statement's trace as a child, never start a second trace."""
    sess.execute("create table src (a bigint)")
    sess.execute("insert into src values (1), (2)")
    tracer.clear()
    sess.execute("create dynamic table dyn as select a from src")
    tids = tracer.trace_ids()
    assert len(tids) == 1        # refresh rode the CREATE's trace
    names = [n for _, n, _ in _tree_names(motrace.tree(tids[0])[0])]
    assert names.count("statement") >= 2    # nested root became child


# ------------------------------------------------- cross-process traces
def test_distributed_single_trace_cn_worker_tn(tracer, monkeypatch):
    """THE acceptance path: CN session -> worker UDF offload -> TN
    commit in one INSERT..SELECT statement = ONE trace_id spanning the
    cn, worker, and tn lanes with intact parent/child links."""
    from matrixone_tpu.cluster import RemoteCatalog, TNService
    from matrixone_tpu.udf import executor as uexec
    from matrixone_tpu.worker.server import TpuWorkerServer
    srv = TpuWorkerServer(port=0).start()
    d = tempfile.mkdtemp(prefix="mo_motrace_")
    tn = TNService(data_dir=d).start()
    cat = RemoteCatalog(("127.0.0.1", tn.port), data_dir=d)
    s = Session(catalog=cat)
    try:
        monkeypatch.setenv("MO_UDF_OFFLOAD", "1")
        monkeypatch.setenv("MO_UDF_WORKER", f"127.0.0.1:{srv.port}")
        s.execute("create function trf(x BIGINT) returns BIGINT "
                  "language python as $$ x * 3 $$")
        s.execute("create table tsrc (a bigint)")
        s.execute("insert into tsrc values (1), (2), (3)")
        s.execute("create table tdst (v bigint)")
        tracer.clear()
        s.execute("insert into tdst select trf(a) from tsrc")
        assert sorted(r[0] for r in
                      s.execute("select v from tdst").rows()) == \
            [3, 6, 9]
        # the INSERT..SELECT produced exactly one trace (the later
        # SELECT added its own; take the first)
        tid = tracer.trace_ids()[0]
        spans = tracer.spans_of(tid)
        procs = {sp["proc"] for sp in spans}
        assert {"cn", "worker", "tn"} <= procs
        roots = motrace.tree(tid)
        assert len(roots) == 1 and roots[0]["name"] == "statement"
        flat = _tree_names(roots[0])
        # worker span parents under worker.run, tn span under rpc.call
        by_name = {n: d_ for d_, n, _ in flat}
        assert by_name["worker.udf_eval"] == by_name["worker.run"] + 1
        assert by_name["tn.commit"] == by_name["rpc.call"] + 1
        # chrome export: >= 2 process lanes, valid JSON, links intact
        ct = json.loads(json.dumps(motrace.chrome_trace(tid)))
        lanes = {e["args"]["name"] for e in ct["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert len(lanes) >= 2 and "worker" in lanes
        xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
        ids = {e["args"]["span_id"] for e in xs}
        for e in xs:
            assert e["args"]["parent_id"] == "" \
                or e["args"]["parent_id"] in ids
    finally:
        s.close()
        cat.close()
        tn.stop()
        uexec.reset_clients()
        srv.stop()


# --------------------------------------------------- chaos span events
@pytest.mark.chaos
def test_fallback_records_span_events(tracer, sess, monkeypatch):
    """PR-2 injector chaos-marker: a transport-lost offload records the
    local fallback as a span event; a breaker-open peer records its own
    fallback reason without touching the network."""
    from matrixone_tpu.cluster import rpc as _rpc
    addr = "127.0.0.1:1"        # nothing listens; breaker is ours
    monkeypatch.setenv("MO_UDF_OFFLOAD", "1")
    monkeypatch.setenv("MO_UDF_WORKER", addr)
    sess.execute("create function cf(x BIGINT) returns BIGINT "
                 "language python as $$ x + 1 $$")
    sess.execute("create table ct (a bigint)")
    sess.execute("insert into ct values (1), (2)")
    try:
        # transport loss via the fault injector (udf.remote site)
        sess.execute("set fault_point = 'udf.remote:return:drop'")
        tracer.clear()
        r = sess.execute("select cf(a) from ct")
        assert sorted(x[0] for x in r.rows()) == [2, 3]
        evs = [ev for sp in tracer.spans_of(tracer.trace_ids()[0])
               for ev in sp["events"]]
        assert any(ev["name"] == "udf.fallback"
                   and ev["attrs"]["reason"] == "transport"
                   for ev in evs)
        sess.execute("set fault_point_clear = 'udf.remote'")
        # breaker open: fail the peer past its threshold first
        b = _rpc.breaker_for(addr)
        for _ in range(b.threshold):
            b.record_failure()
        assert b.state == "open"
        tracer.clear()
        r = sess.execute("select cf(a) from ct")
        assert sorted(x[0] for x in r.rows()) == [2, 3]
        evs = [ev for sp in tracer.spans_of(tracer.trace_ids()[0])
               for ev in sp["events"]]
        assert any(ev["name"] == "udf.fallback"
                   and ev["attrs"]["reason"] == "breaker"
                   for ev in evs)
    finally:
        from matrixone_tpu.utils.fault import INJECTOR
        INJECTOR.clear()
        _rpc.reset_breakers()


# ------------------------------------------- statement table integration
def test_recorder_span_summary_columns(tracer, sess):
    sess.execute("create table rr (a bigint)")
    sess.execute("insert into rr values (1)")
    sess.catalog.stmt_recorder.flush()
    rows = sess.execute(
        f"select statement, trace_id, span_count, span_summary, "
        f"span_tree from {STMT_TABLE}").rows()
    ins = [r for r in rows if r[0].startswith("insert into rr")]
    assert ins, rows
    _, tid, n_spans, summary, tree_js = ins[0]
    assert re.fullmatch(r"[0-9a-f]{16}", tid)
    assert n_spans >= 2
    by_name = json.loads(summary)
    assert "parse" in by_name and "run" in by_name
    assert tree_js == ""         # not slow: no tree persisted


def test_slow_query_hook_persists_full_tree(tracer, sess):
    tracer.slow_ms = 0.001       # everything is "slow"
    sess.execute("create table sq (a bigint)")
    sess.execute("insert into sq values (1), (2)")
    sess.catalog.stmt_recorder.flush()
    rows = sess.execute(
        f"select statement, span_tree from {STMT_TABLE}").rows()
    ins = [r for r in rows if r[0].startswith("insert into sq")]
    tree = json.loads(ins[0][1])
    assert isinstance(tree, list) and tree
    names = {n for root in tree
             for _, n, _ in _tree_names(root)}
    assert "run" in names


def test_recorder_old_schema_auto_recreates():
    """A pre-motrace data dir (cache_hit present, trace_id absent) must
    recreate the statement table instead of failing every flush."""
    from matrixone_tpu.container import dtypes as dt
    eng = Engine(MemoryFS())
    old = [("stmt_id", dt.INT64), ("statement", dt.TEXT),
           ("status", dt.varchar(16)), ("duration_us", dt.INT64),
           ("rows_out", dt.INT64), ("error", dt.TEXT),
           ("ts", dt.INT64), ("cache_hit", dt.varchar(8)),
           ("queue_wait_ms", dt.INT64)]
    eng.create_table(TableMeta(STMT_TABLE, old, ["stmt_id"]), log=False)
    rec = StatementRecorder(eng)
    cols = [c for c, _ in eng.tables[STMT_TABLE].meta.schema]
    assert "trace_id" in cols and "span_tree" in cols
    rec.record("select 1", "ok", 0.001, 1)
    rec.flush()
    assert eng.get_table(STMT_TABLE).n_rows == 1


def test_recorder_flushes_on_engine_close():
    """flush_every buffering must not drop the session tail: close()
    flushes (satellite: engine close / mo_ctl both flush)."""
    eng = Engine(MemoryFS())
    s = Session(catalog=eng)
    s.execute("create table fc (a bigint)")
    s.execute("insert into fc values (1)")
    # buffered (flush_every=64), nothing flushed yet
    assert STMT_TABLE not in eng.tables \
        or eng.get_table(STMT_TABLE).n_rows == 0
    eng.close()
    assert eng.get_table(STMT_TABLE).n_rows == 2
    s.close()


# ------------------------------------------------------- metrics plane
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\]|\\.)*\")*\})?"
    r" [-+]?[0-9.eE+-]+$")


def test_prometheus_text_format_parses_strict(sess):
    """render() must be real exposition format: HELP/TYPE per family,
    every sample line well-formed, histograms cumulative with
    bucket/sum/count and +Inf == count."""
    sess.execute("create table pm (a bigint)")
    sess.execute("insert into pm values (1)")
    sess.execute("select sum(a) from pm")
    text = M.REGISTRY.render()
    families = {}
    cur = None
    for line in text.strip().split("\n"):
        if line.startswith("# HELP "):
            cur = line.split()[2]
            families.setdefault(cur, {"help": True})
        elif line.startswith("# TYPE "):
            parts = line.split()
            assert parts[2] == cur, f"TYPE without HELP: {line}"
            assert parts[3] in ("counter", "gauge", "histogram")
            families[cur]["type"] = parts[3]
        else:
            assert _SAMPLE_RE.match(line), f"malformed sample: {line!r}"
            name = line.split("{")[0].split(" ")[0]
            base = re.sub(r"_(bucket|sum|count)$", "", name)
            assert base in families or name in families, line
    # histogram invariants on a driven family
    h = [ln for ln in text.split("\n")
         if ln.startswith("mo_query_duration_seconds")]
    buckets = [float(ln.rsplit(" ", 1)[1]) for ln in h
               if "_bucket{" in ln and "+Inf" not in ln]
    assert buckets == sorted(buckets)          # cumulative
    inf = [float(ln.rsplit(" ", 1)[1]) for ln in h
           if 'le="+Inf"' in ln][0]
    count = [float(ln.rsplit(" ", 1)[1]) for ln in h
             if ln.startswith("mo_query_duration_seconds_count")][0]
    assert inf == count > 0
    # counters registered for the trace plane are present
    assert "# TYPE mo_trace_spans_total counter" in text


def test_multi_statement_span_attribution(tracer, sess):
    """In a multi-statement execute each row's span_summary covers ONLY
    that statement's spans — statement 2 must not re-report statement
    1's run/commit durations (the cumulative-window bug)."""
    sess.execute("create table mA (a bigint); create table mB (b bigint)")
    sess.catalog.stmt_recorder.flush()
    rows = sess.execute(
        f"select statement, span_count, span_summary from {STMT_TABLE} "
        f"where statement like 'create table mA%'").rows()
    assert len(rows) == 2        # one row per statement, same sql text
    first, second = sorted(rows, key=lambda r: r[1], reverse=True)
    s1 = json.loads(first[2])
    s2 = json.loads(second[2])
    # statement 1 owns the shared parse span; statement 2 does not
    assert "parse" in s1 and "parse" not in s2
    # each window holds exactly one run span's worth of spans
    assert first[1] >= 2 and second[1] >= 1
    assert s2.get("run", 0) <= s1.get("run", 1e9)


def test_histogram_delta_quantile():
    from matrixone_tpu.utils.metrics import (Histogram,
                                             histogram_delta_quantile)
    h = Histogram("mo_test_delta_seconds", "t")
    for _ in range(100):
        h.observe(0.002)         # history: all in the 5e-3 bucket
    before = h.snapshot()
    for _ in range(10):
        h.observe(0.3)           # the phase under measurement
    after = h.snapshot()
    # phase-only quantiles ignore the 100 fast historical observations
    assert histogram_delta_quantile(before, after, 0.5) == 0.5
    assert after["count"] - before["count"] == 10
    # cumulative quantile over everything stays dominated by history
    assert h.quantile(0.5) == 0.005


def test_registry_snapshot_and_quantile(sess):
    sess.execute("create table sn (a bigint)")
    sess.execute("insert into sn values (1)")
    snap = M.REGISTRY.snapshot()
    q = snap["mo_query_duration_seconds"]
    assert q["type"] == "histogram" and q["count"] > 0
    assert q["sum"] > 0
    assert sum(b["count"] for b in q["buckets"]) == q["count"]
    c = snap["mo_txn_commit_total"]
    assert c["type"] == "counter"
    assert M.query_seconds.quantile(0.5) > 0
    assert M.query_seconds.quantile(0.99) >= \
        M.query_seconds.quantile(0.5)


def test_moscrape_http_endpoint(sess):
    import urllib.request
    from tools import moscrape
    sess.execute("create table ms (a bigint)")
    httpd = moscrape.serve(port=0)
    try:
        port = httpd.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
            body = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/plain")
        assert "# TYPE mo_query_duration_seconds histogram" in body
        assert body == M.REGISTRY.render() or body  # scrape is render()
    finally:
        httpd.shutdown()
        httpd.server_close()


# ----------------------------------------------------------- ops surface
def test_mo_ctl_trace_and_show_trace(tracer, sess, tmp_path):
    sess.execute("create table oc (a bigint)")
    sess.execute("insert into oc values (1)")
    st = json.loads(
        sess.execute("select mo_ctl('trace','status')").rows()[0][0])
    assert st["armed"] and st["traces"] >= 2
    rows = sess.execute("show trace").rows()
    assert any(r[1] == "statement" and r[3] >= 2 for r in rows)
    # dump: one Perfetto-loadable file per trace_id
    out = str(tmp_path / "traces")
    msg = sess.execute(
        f"select mo_ctl('trace','dump:{out}')").rows()[0][0]
    assert msg.startswith("dumped")
    files = sorted(os.listdir(out))
    # one file per trace_id: every trace counted at status time, plus
    # the later status/show/dump statements' own traces
    assert len(files) >= st["traces"]
    assert all(f.startswith("trace_") and f.endswith(".json")
               for f in files)
    ct = json.loads(open(os.path.join(out, files[0])).read())
    assert ct["traceEvents"]
    # slow threshold + sampling are settable at runtime
    sess.execute("select mo_ctl('trace','slow:25')")
    assert tracer.slow_ms == 25.0
    sess.execute("select mo_ctl('trace','sample:0.25')")
    assert tracer.sample == 0.25
    tracer.sample = 1.0
    sess.execute("select mo_ctl('trace','off')")
    assert not tracer.armed
    sess.execute("select mo_ctl('trace','on')")
    assert tracer.armed
    with pytest.raises(Exception):
        sess.execute("select mo_ctl('trace','bogus')")


def test_mo_ctl_metrics_dump(sess):
    sess.execute("create table md (a bigint)")
    text = sess.execute(
        "select mo_ctl('metrics','dump')").rows()[0][0]
    assert "# TYPE mo_query_duration_seconds histogram" in text
    snap = json.loads(sess.execute(
        "select mo_ctl('metrics','snapshot')").rows()[0][0])
    assert snap["mo_query_duration_seconds"]["count"] > 0


# ------------------------------------------ the split of `run` (PR 27)
SCAN_ROWS = 2000          # rows a commit, two commits: two segments


@pytest.fixture
def cold(tmp_path, monkeypatch):
    """A table of two object-backed segments (a = 0..1999, 2000..3999)
    in an engine re-opened from its files, nothing of it in the block
    cache, every batch on the fused path.  -> (session, clear): clear()
    makes the next scan cold again."""
    from matrixone_tpu.storage import blockcache
    from matrixone_tpu.storage.fileservice import LocalFS
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    eng = Engine(LocalFS(str(tmp_path)))
    s = Session(catalog=eng)
    s.execute("create table sc (a bigint, b bigint)")
    for c in range(2):
        s.execute("insert into sc values " + ",".join(
            f"({i},{i % 7})"
            for i in range(c * SCAN_ROWS, (c + 1) * SCAN_ROWS)))
    eng.checkpoint()
    s.close()
    eng.close()
    blockcache.CACHE.clear()
    s = Session(catalog=Engine.open(LocalFS(str(tmp_path))))
    yield s, blockcache.CACHE.clear
    s.close()
    blockcache.CACHE.clear()


def _last_trace(tracer):
    spans = tracer.spans_of(tracer.trace_ids()[-1])
    return spans, {sp["sid"]: sp for sp in spans}


def test_cold_scan_spans_ride_the_prefetch_thread(tracer, cold):
    """What the prefetch thread reads, decodes and uploads lands in the
    statement's trace: `bind` carried the context across the hop."""
    s, _clear = cold
    tracer.clear()                # the fixture's own statements
    assert s.execute("select sum(b) from sc where a >= 0").rows() \
        == [(sum(i % 7 for i in range(2 * SCAN_ROWS)),)]
    spans, by_sid = _last_trace(tracer)
    assert len(tracer.trace_ids()) == 1
    on_prefetch = {sp["name"] for sp in spans
                   if sp["thread"] == "mo-scan-prefetch"}
    assert {"scan.chunk", "blockcache.load", "object.read",
            "object.decode", "blockcache.upload",
            "scan.zonemap"} <= on_prefetch
    names = {sp["name"] for sp in spans}
    assert {"scan.wait", "scan.batch", "fusion.dispatch",
            "fusion.finalize"} <= names
    # the tree: the fused fragment's step > scan.chunk > blockcache.load >
    # read/decode/upload, scan.wait beside scan.chunk on the statement's
    # own thread
    parent = {sp["name"]: by_sid[sp["psid"]]["name"]
              for sp in spans if sp["psid"] in by_sid}
    assert parent["scan.chunk"] == parent["scan.wait"] == "op.fused"
    assert parent["op.fused"] == "op.project" and parent["op.project"] == "run"
    assert parent["blockcache.load"] == parent["scan.zonemap"] \
        == "scan.chunk"
    assert parent["object.read"] == parent["object.decode"] \
        == parent["blockcache.upload"] == "blockcache.load"
    waits = [sp for sp in spans if sp["name"] == "scan.wait"]
    assert all(sp["thread"] != "mo-scan-prefetch" for sp in waits)
    chunks = [sp for sp in spans if sp["name"] == "scan.chunk"]
    assert [sp["attrs"]["rows"] for sp in chunks] == [SCAN_ROWS] * 2
    assert all(sp["attrs"]["table"] == "sc" for sp in chunks)
    # one load a column a segment, one upload for its data and one for
    # its validity; the attributes are the bytes that moved
    loads = [sp for sp in spans if sp["name"] == "blockcache.load"]
    assert sorted(sp["attrs"]["col"] for sp in loads) == ["a", "a",
                                                          "b", "b"]
    uploads = [sp["attrs"]["bytes"] for sp in spans
               if sp["name"] == "blockcache.upload"]
    assert sorted(uploads) == [SCAN_ROWS] * 4 + [8 * SCAN_ROWS] * 4


def test_generator_rule_no_span_is_held_across_a_yield(tracer, cold):
    """A span in a generator wraps the work between two yields: what the
    consumer opens (`fusion.*`, `scan.batch`) never hangs under a scan
    span, and no scan span lasts into the consumer's work."""
    s, clear = cold
    for where in ("a >= 0", "a >= 1000"):
        clear()
        s.execute(f"select sum(b), count(*) from sc where {where}")
        spans, by_sid = _last_trace(tracer)
        fused = [sp for sp in spans if sp["name"].startswith("fusion.")]
        assert any(sp["name"] == "fusion.dispatch" for sp in fused)
        for sp in fused + [x for x in spans if x["name"] == "scan.batch"]:
            assert by_sid[sp["psid"]]["name"] == "op.fused", sp
        # on one thread a child lies inside its parent: a span held
        # across a yield would end after spans that are not its children
        for sp in spans:
            p = by_sid.get(sp["psid"])
            if p is None or p["thread"] != sp["thread"]:
                continue
            assert p["ts_us"] <= sp["ts_us"]
            assert sp["ts_us"] + sp["dur_us"] <= \
                p["ts_us"] + p["dur_us"] + 1      # microsecond rounding


def test_disarmed_scan_records_nothing_and_bind_is_identity(cold):
    tr = motrace.TRACER
    assert not tr.armed
    tr.clear()
    s, _clear = cold

    def work():
        return motrace.current_ctx()

    assert motrace.bind(work) is work
    s.execute("select sum(b) from sc where a >= 0")
    assert tr.trace_ids() == [] and tr.status()["spans"] == 0


def test_bind_carries_only_the_trace_context(tracer):
    import contextvars
    import threading
    other = contextvars.ContextVar("other", default="unset")

    def work():
        with motrace.span("hop"):
            pass
        return other.get()

    assert motrace.bind(work) is work           # armed, no trace active
    got = []
    with motrace.root_span("root") as root:
        other.set("the caller's")
        bound = motrace.bind(work)
        assert bound is not work
        t = threading.Thread(target=lambda: got.append(bound()),
                             name="hop-thread")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert got == ["unset"]       # nothing but motrace's context crossed
    spans, _ = _last_trace(tracer)
    hop = [sp for sp in spans if sp["name"] == "hop"][0]
    assert hop["thread"] == "hop-thread" and hop["psid"] == root._sid
    assert motrace.current_ctx() is None


def test_span_holds_a_profiler_annotation_while_open(tracer):
    """Armed, a span enters a `jax.profiler.TraceAnnotation` of its name
    (jax is loaded here), so a profile of the process holds the spans on
    the profiler's own clock."""
    import jax.profiler
    with motrace.root_span("annotated") as sp:
        assert isinstance(sp._twin, jax.profiler.TraceAnnotation)


def test_ring_overflow_keeps_the_index_right(tracer, monkeypatch):
    """Filled past its capacity the ring drops its oldest spans, and
    `spans_of` / `trace_mark` / `statement_record` read what is left of
    one trace through the index, not the ring."""
    monkeypatch.setattr(tracer, "_cap", 32)
    dropped0 = M.trace_ring_dropped.get()
    with motrace.root_span("old"):
        for _ in range(20):
            with motrace.span("o"):
                pass
    old_tid = tracer.trace_ids()[0]
    assert tracer.span_count(old_tid) == 21
    with motrace.root_span("statement"):
        tid = motrace.current_ctx().trace_id
        for i in range(40):
            with motrace.span("s", i=i):
                pass
        assert motrace.trace_mark() == 32       # the ring is all ours
        tr_id, n, summary, _tree = motrace.statement_record(1.0, since=30)
        assert (tr_id, n) == (tid, 2) and set(json.loads(summary)) == {"s"}
    assert tracer.spans_of(old_tid) == []
    assert tracer.trace_ids() == [tid]
    spans = tracer.spans_of(tid)
    assert [sp["attrs"].get("i") for sp in spans] \
        == list(range(9, 40)) + [None]          # 31 children + the root
    assert spans == list(tracer._ring)
    st = tracer.status()
    assert (st["spans"], st["traces"], st["ring_capacity"]) == (32, 1, 32)
    assert M.trace_ring_dropped.get() - dropped0 == 21 + 41 - 32
    tracer.clear()
    assert tracer.spans_of(tid) == [] and tracer.trace_ids() == []


@pytest.mark.parametrize("setup, where, chunks, segments_read", [
    # batch_rows 1000: two chunks a segment, four in all
    ("", "a >= 0", {"scanned": 4}, (0, 1)),
    ("", "a >= 2000", {"scanned": 2, "pruned_segment": 2}, (1,)),
    ("", "a >= 1500", {"scanned": 3, "pruned_chunk": 1}, (0, 1)),
    ("", "a >= 100000", {"pruned_segment": 4}, ()),
    ("delete from sc where a < 1000", "a >= 0",
     {"scanned": 3, "all_dead": 1}, (0, 1)),
])
def test_scan_counters_on_pruned_and_unpruned_scans(
        cold, setup, where, chunks, segments_read):
    """`mo_scan_chunks_total` counts every chunk of the scan once, by
    what became of it, and `mo_object_read_bytes_total` moves by the
    stored bytes of exactly the column blocks the scan had to read: a
    segment pruned on its stored zonemap costs none."""
    from matrixone_tpu.storage import objectio
    s, clear = cold
    s.execute("set batch_rows = 1000")
    if setup:
        s.execute(setup)
    s.execute("select sum(b) from sc where a >= 0")  # headers now read
    clear()
    table = s.catalog.get_table("sc")
    stored = []
    for seg in table.segments[:2]:
        _meta, raw = objectio.read_header_ranged(s.catalog.fs,
                                                 seg.obj_path)
        stored.append(sum(ent[1] for ent in raw["cols"].values()))
    outcomes = ("scanned", "pruned_segment", "pruned_chunk", "all_dead")
    before = {o: M.scan_chunks.get(outcome=o) for o in outcomes}
    read0 = M.object_read_bytes.get()
    waits0 = M.device_wait.get(site="zonemap")
    sources = ("memo", "device", "host")
    checks0 = {k: M.scan_zonemap_checks.get(source=k) for k in sources}
    s.execute(f"select sum(b) from sc where {where}")
    moved = {o: M.scan_chunks.get(outcome=o) - before[o]
             for o in outcomes}
    assert {o: n for o, n in moved.items() if n} == chunks
    assert M.object_read_bytes.get() - read0 \
        == sum(stored[i] for i in segments_read)
    # the chunk's own check waits on the device for nothing: the first
    # statement over these objects kept every chunk's summary with them
    # (the block cache was cleared since; the summaries are not its),
    # and each chunk that was read checks its one predicate against that
    read_chunks = chunks.get("scanned", 0) + chunks.get("pruned_chunk", 0)
    assert M.device_wait.get(site="zonemap") - waits0 == 0
    assert {k: M.scan_zonemap_checks.get(source=k) - checks0[k]
            for k in sources} == {"memo": read_chunks, "device": 0,
                                  "host": 0}


# --------------------------------------------------------------- smoke
def test_trace_smoke_gate():
    """The precheck --trace-smoke stage (tools/motrace.py) runs green
    and restores the tracer's disarmed state."""
    from tools import motrace as smoke
    was = motrace.TRACER.armed
    rep = smoke.run_smoke()
    assert rep["ok"], rep["errors"]
    assert rep["spans"] >= 3 and rep["chrome_events"] >= 4
    assert motrace.TRACER.armed == was
