"""motrace — end-to-end distributed tracing for the engine.

Reference analogue: `pkg/util/trace` (motrace) — per-statement span
trees feeding `statement_info`, with trace context propagated on the
RPC wire.  Here the span tree covers the whole statement lifecycle:

    statement (root, frontend/session.py)
      parse                      sql/parser via Session.execute
      run                        per-statement execution envelope
        admission.queue          serving/admission.py slot wait
        plan                     bind + optimize on a plan-cache miss
        op.<kind>                vm/: one step of an operator's
                                 `execute()` (`steps`), its children's
                                 steps and every span below nested in it
        scan.wait                vm/operators.py: the statement's thread
                                 blocked on the scan prefetcher
        scan.chunk               storage/engine.py iter_chunks, one per
                                 chunk; on thread `mo-scan-prefetch`
                                 when the scan is cold (see `bind`)
          blockcache.load        storage/blockcache.py: one column of
                                 one object brought in (miss path)
            object.read          storage/objectio.py: bytes from the
                                 file service
            object.decode        decompress + Arrow IPC -> numpy
            blockcache.upload    host column -> device tier
          scan.zonemap           chunk-level min/max check and the
                                 waits for the device's answers
        scan.batch               vm/operators.py: chunk -> ExecBatch
        fusion.compile           vm/fusion*.py fragment trace+compile
        fusion.flags             all-valid flags program + its wait
        fusion.dispatch          compiled step dispatch
        fusion.finalize          carry -> result batch, limit check
        vector.search            vm/vector_scan.py index search
        vector.fetch             candidate rows by id (blockcache.*
                                 below it after a re-open)
        rpc.call                 cluster/rpc.py (CN->TN commit, DDL, ...)
          tn.<op>                cluster/tn.py server-side handling
        worker.run               worker/client.py gRPC offload
          worker.<op>            worker/server.py server-side handling
        dml.find                 frontend/session.py: the plan that finds
                                 a DELETE's / UPDATE's row ids (its scan
                                 and fused spans below it)
        txn.commit               txn/client.py commit pipeline
          wal.sync               storage/wal.py: the append and flush a
                                 commit waits for
        mview.apply              mview/maintain.py delta maintenance

Rule for generators (kept by tests/test_motrace.py and the molint rule
`span-hygiene`): a span in a generator wraps the work between two
yields and NEVER a `yield`.  A span held open across a yield becomes
the ambient parent of whatever the consumer opens next, and its
duration counts the consumer's work.

The one thread hop of the scan path (the prefetch thread) carries the
trace context through `bind(fn)`; a new thread inherits no contextvars,
and a span opened without a context is the no-op.

Cross-process propagation rides the SAME wire header that already
carries `deadline_ms`: `inject()` adds a compact `trace` entry
([trace_id, parent_span_id]) to the outgoing header, servers re-enter
it with `remote_session()`, and the server's spans ship back to the
caller on the RESPONSE header (`trace_spans`) so one process ends up
owning the complete tree — the Chrome exporter then renders each
logical process (cn/tn/worker/proxy) as its own lane.

Every span records its thread's CPU time (`cpu_us`, from
`time.thread_time_ns`) beside its wall time (`dur_us`): the difference
is the time the thread was off the CPU, waiting for the GIL, a lock,
the device or I/O.

Cost discipline (same contract as utils/fault.py and utils/san.py):
disarmed, every instrumentation site costs ONE attribute read
(`TRACER.armed`) — `span()` returns a shared no-op context manager
before touching anything else.  Armed, completed spans land in a
bounded per-process ring buffer with head sampling: the sampling
decision is made ONCE at root-span creation (`MO_TRACE_SAMPLE`) and
children inherit it through the ambient context, so an unsampled
statement pays almost nothing either.

Knobs: `MO_TRACE` (arm), `MO_TRACE_SAMPLE` (head-sampling fraction),
`MO_TRACE_SLOW_MS` (auto-persist slow statements' full span tree into
system_statement_info), `MO_TRACE_RING` (ring capacity in spans,
65,536 by default: about 0.6 KB a span, 40 MB when full).
Ops surface: `SHOW TRACE`, `mo_ctl('trace', 'status|on|off|clear|'
'sample:<f>|slow:<ms>|dump:<path>')`.
"""

from __future__ import annotations

import contextvars
import json
import os
import random
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from matrixone_tpu.utils import san


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


class _Ctx:
    """Ambient trace context for one open span (immutable but for
    `parent_of_recorded`; the contextvar stack IS the span stack)."""

    __slots__ = ("trace_id", "span_id", "proc", "sink", "attrs",
                 "events", "parent_of_recorded")

    def __init__(self, trace_id: str, span_id: str, proc: str,
                 sink: Optional[list], attrs: dict, events: list):
        self.trace_id = trace_id
        self.span_id = span_id
        self.proc = proc
        #: remote sessions collect spans here (shipped back on the
        #: response) instead of the local ring
        self.sink = sink
        #: live references so event()/annotate() reach the OPEN span
        self.attrs = attrs
        self.events = events
        #: a span opened under this one (on any thread) was recorded
        self.parent_of_recorded = False


_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "mo_trace_ctx", default=None)


def _new_id() -> str:
    return f"{random.getrandbits(64):016x}"


class Tracer:
    """Process-global tracer: armed flag, sampling, bounded span ring."""

    def __init__(self):
        self.armed = os.environ.get("MO_TRACE", "0").lower() not in (
            "0", "", "false", "off")
        self.sample = _env_float("MO_TRACE_SAMPLE", 1.0)
        self.slow_ms = _env_float("MO_TRACE_SLOW_MS", 0.0)
        self.proc = "cn"
        #: the ring: completed spans in arrival order, at most _cap
        self._cap = max(16, int(_env_float("MO_TRACE_RING", 65536)))
        self._ring: deque = deque()
        #: the same records by trace id (each trace's spans in arrival
        #: order), so a read costs one trace and not the ring
        self._by_tid: Dict[str, deque] = {}
        self._lock = san.lock("motrace.Tracer._lock", internal=True)

    # ------------------------------------------------------------ control
    def arm(self, sample: Optional[float] = None,
            slow_ms: Optional[float] = None) -> None:
        if sample is not None:
            self.sample = float(sample)
        if slow_ms is not None:
            self.slow_ms = float(slow_ms)
        self.armed = True

    def disarm(self) -> None:
        self.armed = False

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._by_tid.clear()

    # ------------------------------------------------------------- record
    def record(self, rec: dict, sink: Optional[list] = None) -> None:
        """One completed span: to the remote-session sink when present
        (shipped back to the caller), else to the local ring.  The
        counter ticks only on RING arrival — a sink span counts once,
        when the trace-owning process merges it (otherwise an
        in-process TN/worker would double-count every shipped span)."""
        from matrixone_tpu.utils import metrics as M
        if sink is not None:
            sink.append(rec)
            return
        M.trace_spans.inc(proc=rec["proc"])
        with self._lock:
            if len(self._ring) >= self._cap:
                # the ring's oldest span is its trace's oldest too
                old = self._ring.popleft()
                own = self._by_tid[old["tid"]]
                own.popleft()
                if not own:
                    del self._by_tid[old["tid"]]
                M.trace_ring_dropped.inc()
            self._ring.append(rec)
            own = self._by_tid.get(rec["tid"])
            if own is None:
                own = self._by_tid[rec["tid"]] = deque()
            own.append(rec)

    # -------------------------------------------------------------- reads
    def spans_of(self, trace_id: str) -> List[dict]:
        with self._lock:
            return list(self._by_tid.get(trace_id, ()))

    def span_count(self, trace_id: str) -> int:
        with self._lock:
            return len(self._by_tid.get(trace_id, ()))

    def trace_ids(self) -> List[str]:
        """Distinct trace ids, by the arrival of each trace's first
        span: oldest first."""
        with self._lock:
            return list(self._by_tid)

    def traces(self) -> List[dict]:
        """Per-trace summaries (SHOW TRACE), oldest first."""
        with self._lock:
            by_tid = {tid: list(own) for tid, own in self._by_tid.items()}
        out = []
        for tid, spans in by_tid.items():
            t = {"trace_id": tid, "root": "", "spans": len(spans),
                 "procs": {s["proc"] for s in spans},
                 "ts_us": min(s["ts_us"] for s in spans), "dur_ms": 0.0}
            ids = {s["sid"] for s in spans}
            roots = [s for s in spans if s["psid"] not in ids]
            if roots:
                root = max(roots, key=lambda s: s["dur_us"])
                t["root"] = root["name"]
                t["dur_ms"] = round(root["dur_us"] / 1000.0, 3)
            t["procs"] = ",".join(sorted(t["procs"]))
            out.append(t)
        out.sort(key=lambda t: t["ts_us"])
        return out

    def status(self) -> dict:
        with self._lock:
            n = len(self._ring)
            tids = len(self._by_tid)
        return {"armed": self.armed, "sample": self.sample,
                "slow_ms": self.slow_ms, "proc": self.proc,
                "ring_capacity": self._cap,
                "spans": n, "traces": tids}


TRACER = Tracer()


# ------------------------------------------------------------------ spans
class _NoopSpan:
    """Shared do-nothing context manager: the disarmed/unsampled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def last_step(self) -> None:
        return None


_NOOP = _NoopSpan()

#: An operator's last step, the `next()` that finds its input done, is a
#: span only where a span below it was recorded or it took this much of
#: its thread's CPU: a step that only learns the end adds nothing to the
#: ring (a one-batch operator is one span, not two).
LAST_STEP_CPU_NS = 100_000

_ANNOTATION = None


def _annotation():
    """`jax.profiler.TraceAnnotation`, once jax is loaded (a process
    that never imported jax has no profiler to write to)."""
    global _ANNOTATION
    if _ANNOTATION is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class _Span:
    """One recording span.  ONLY ever opened via `with` (molint rule
    span-hygiene) — enter/exit balance is what keeps the ambient
    context stack and the ring consistent.  While it is open it also
    holds a `jax.profiler.TraceAnnotation` of its name, so a profile
    of the process shows the program's spans on the profiler's own
    clock beside the device's operations."""

    __slots__ = ("name", "attrs", "_tid", "_psid", "_sid", "_proc",
                 "_sink", "_events", "_t0", "_c0", "_token", "_twin",
                 "_ctx", "_parent", "_last_step")

    def __init__(self, name: str, trace_id: str, parent_sid: str,
                 proc: str, sink: Optional[list], attrs: dict):
        self.name = name
        self.attrs = attrs
        self._tid = trace_id
        self._psid = parent_sid
        self._sid = _new_id()
        self._proc = proc
        self._sink = sink
        self._events: list = []
        self._t0 = self._c0 = 0
        self._token = None
        self._twin = None
        self._ctx = self._parent = None
        self._last_step = False

    def __enter__(self):
        self._t0 = time.time_ns()
        self._c0 = time.thread_time_ns()
        annotation = _annotation()
        if annotation is not None:
            self._twin = annotation(self.name)
            self._twin.__enter__()
        self._parent = _CTX.get()
        self._ctx = _Ctx(self._tid, self._sid, self._proc, self._sink,
                         self.attrs, self._events)
        self._token = _CTX.set(self._ctx)
        return self

    def last_step(self) -> None:
        """This span is an operator's step that found its input done
        (`steps`): it is recorded only if it did work."""
        self._last_step = True

    def __exit__(self, exc_type, exc, tb):
        _CTX.reset(self._token)
        if self._twin is not None:
            self._twin.__exit__(exc_type, exc, tb)
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        cpu = time.thread_time_ns() - self._c0
        dur = time.time_ns() - self._t0
        if (self._last_step and cpu < LAST_STEP_CPU_NS
                and not self._ctx.parent_of_recorded):
            return False
        if self._parent is not None:
            self._parent.parent_of_recorded = True
        TRACER.record({"tid": self._tid, "sid": self._sid,
                       "psid": self._psid, "name": self.name,
                       "proc": self._proc,
                       "thread": threading.current_thread().name,
                       "ts_us": self._t0 // 1000,
                       "dur_us": dur // 1000, "cpu_us": cpu // 1000,
                       "attrs": self.attrs, "events": self._events},
                      sink=self._sink)
        return False


def span(name: str, **attrs):
    """Child span under the current context; no-op when disarmed OR
    when no sampled trace is active (head sampling: the root decides)."""
    if not TRACER.armed:
        return _NOOP
    ctx = _CTX.get()
    if ctx is None:
        return _NOOP
    return _Span(name, ctx.trace_id, ctx.span_id, ctx.proc, ctx.sink,
                 attrs)


def steps(name: Optional[str], it):
    """`it`, an operator's batch iterator, with each `next()` under a
    span `name`: the work between two yields, the pulls from its
    children included, and never a yield.  Closing the result closes
    `it`.  Disarmed, with no trace active or no name, it is `it`."""
    if not TRACER.armed or name is None or _CTX.get() is None:
        return it
    return _steps(name, it)


def _steps(name: str, it):
    try:
        while True:
            with span(name) as step:
                try:
                    item = next(it)
                except StopIteration:
                    step.last_step()
                    return
            yield item
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()


def root_span(name: str, proc: Optional[str] = None, **attrs):
    """Explicit new-trace root, head-sampled; nested under an existing
    context it degrades to an ordinary child span (a re-entrant
    Session.execute must not fork a second trace)."""
    from matrixone_tpu.utils import metrics as M
    if not TRACER.armed:
        return _NOOP
    ctx = _CTX.get()
    if ctx is not None:
        return _Span(name, ctx.trace_id, ctx.span_id, ctx.proc,
                     ctx.sink, attrs)
    if random.random() >= TRACER.sample:
        M.trace_traces.inc(outcome="unsampled")
        return _NOOP
    M.trace_traces.inc(outcome="sampled")
    return _Span(name, _new_id(), "", proc or TRACER.proc, None, attrs)


def statement_span(sql: str):
    """Root span for one Session.execute — the trace boundary."""
    if not TRACER.armed:
        return _NOOP
    return root_span("statement", sql=sql[:1024])


def instant(name: str, proc: Optional[str] = None, **attrs) -> None:
    """Zero-duration standalone marker (e.g. a proxy failover): its own
    head-sampled root when no trace is active, a span event otherwise."""
    if not TRACER.armed:
        return
    ctx = _CTX.get()
    if ctx is not None:
        event(name, **attrs)
        return
    with root_span(name, proc=proc, **attrs):
        pass


def event(name: str, **attrs) -> None:
    """Attach a point event to the CURRENT open span (dropped when
    disarmed or no span is open)."""
    if not TRACER.armed:
        return
    ctx = _CTX.get()
    if ctx is None:
        return
    ctx.events.append({"name": name, "ts_us": time.time_ns() // 1000,
                       "attrs": attrs})


def annotate(**attrs) -> None:
    """Merge attributes into the CURRENT open span."""
    if not TRACER.armed:
        return
    ctx = _CTX.get()
    if ctx is not None:
        ctx.attrs.update(attrs)


def current_ctx() -> Optional[_Ctx]:
    return _CTX.get()


def bind(fn):
    """`fn`, to be run on another thread under the caller's current
    trace context: spans it opens there join the caller's trace as
    children of the span open now.  A new thread inherits no
    contextvars, and only motrace's context is carried (not
    `contextvars.copy_context()`: nothing else the caller has set may
    change what the other thread does).  Disarmed, or with no trace
    active, it is `fn` itself."""
    if not TRACER.armed:
        return fn
    ctx = _CTX.get()
    if ctx is None:
        return fn

    def bound(*args, **kwargs):
        token = _CTX.set(ctx)
        try:
            return fn(*args, **kwargs)
        finally:
            _CTX.reset(token)

    return bound


# --------------------------------------------------- wire propagation
def inject(header: dict) -> None:
    """Add the trace context to an outgoing wire header (rides next to
    `deadline_ms`).  One attribute read when disarmed."""
    if not TRACER.armed:
        return
    ctx = _CTX.get()
    if ctx is not None:
        header["trace"] = [ctx.trace_id, ctx.span_id]


def merge_remote(resp_header) -> None:
    """Fold spans a server shipped back on its response header into the
    local trace (or onward, if WE are mid remote-session — multi-hop
    chains keep forwarding toward the root owner)."""
    if not TRACER.armed or not isinstance(resp_header, dict):
        return
    spans = resp_header.pop("trace_spans", None)
    if not spans:
        return
    ctx = _CTX.get()
    sink = ctx.sink if ctx is not None else None
    for rec in spans:
        if isinstance(rec, dict) and "tid" in rec:
            TRACER.record(rec, sink=sink)


class _NoopRemote:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def attach(self, resp) -> None:
        return None

    def harvest(self):
        return None


_NOOP_REMOTE = _NoopRemote()


class _RemoteSession:
    """Server-side re-entry of a caller's trace context: one server
    span (named for the op) whose children collect into a sink that
    `attach()` ships back on the response header."""

    __slots__ = ("_span", "_sink")

    def __init__(self, trace_id: str, parent_sid: str, proc: str,
                 name: str, attrs: dict):
        self._sink: list = []
        self._span = _Span(name, trace_id, parent_sid, proc,
                           self._sink, attrs)

    def __enter__(self):
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        return self._span.__exit__(exc_type, exc, tb)

    def harvest(self) -> Optional[list]:
        return self._sink or None

    def attach(self, resp) -> None:
        if self._sink and isinstance(resp, dict):
            resp["trace_spans"] = self._sink


def remote_session(header, proc: str, name: str, **attrs):
    """Re-enter the trace context a request header carries (the server
    half of `inject`); no-op when disarmed or the caller sent none."""
    if not TRACER.armed:
        return _NOOP_REMOTE
    t = header.get("trace") if isinstance(header, dict) else None
    if not (isinstance(t, (list, tuple)) and len(t) == 2):
        return _NOOP_REMOTE
    return _RemoteSession(str(t[0]), str(t[1]), proc, name, attrs)


# ----------------------------------------------------------- summaries
def trace_mark() -> int:
    """Current span count of the active trace — the `since` watermark
    for per-statement attribution in a multi-statement execute (the
    shared statement root is ONE trace; each statement summarizes only
    the spans recorded after the previous statement's mark)."""
    if not TRACER.armed:
        return 0
    ctx = _CTX.get()
    if ctx is None:
        return 0
    return TRACER.span_count(ctx.trace_id)


def statement_record(dur_ms: float, since: int = 0):
    """-> (trace_id, span_count, span_summary_json, span_tree_json) for
    the statement recorder, covering the trace's spans from index
    `since` (a trace_mark() watermark) onward; tree only persists past
    MO_TRACE_SLOW_MS (the slow-query hook).  Empty strings when
    disarmed/unsampled."""
    if not TRACER.armed:
        return "", 0, "", ""
    ctx = _CTX.get()
    if ctx is None:
        return "", 0, "", ""
    spans = TRACER.spans_of(ctx.trace_id)[since:]
    if not spans:
        return ctx.trace_id, 0, "", ""
    by_name: Dict[str, float] = {}
    for s in spans:
        by_name[s["name"]] = by_name.get(s["name"], 0.0) \
            + s["dur_us"] / 1000.0
    summary = json.dumps({k: round(v, 3)
                          for k, v in sorted(by_name.items())})
    tree_js = ""
    if TRACER.slow_ms > 0 and dur_ms >= TRACER.slow_ms:
        tree_js = json.dumps(_forest(spans))
    return ctx.trace_id, len(spans), summary, tree_js


def tree(trace_id: str) -> List[dict]:
    """Nested span tree(s) of one trace: roots are spans whose parent
    is not in the ring (the statement root mid-flight counts its
    completed children as roots — still one coherent forest)."""
    return _forest(TRACER.spans_of(trace_id))


def _forest(spans: List[dict]) -> List[dict]:
    by_sid = {s["sid"]: dict(s, children=[]) for s in spans}
    roots = []
    for s in spans:
        node = by_sid[s["sid"]]
        parent = by_sid.get(s["psid"])
        if parent is None:
            roots.append(node)
        else:
            parent["children"].append(node)
    for n in by_sid.values():
        n["children"].sort(key=lambda c: c["ts_us"])
    roots.sort(key=lambda c: c["ts_us"])
    return roots


# ------------------------------------------------------ chrome export
def chrome_trace(trace_id: str) -> dict:
    """Chrome trace-event JSON (Perfetto-loadable): one pid lane per
    logical process (cn/tn/worker/...), one tid lane per thread,
    complete ("X") events carrying span/parent ids, instant ("i")
    events for span events."""
    spans = TRACER.spans_of(trace_id)
    procs = sorted({s["proc"] for s in spans})
    pid_of = {p: i + 1 for i, p in enumerate(procs)}
    tid_of: Dict[tuple, int] = {}
    events: List[dict] = []
    for p in procs:
        events.append({"ph": "M", "name": "process_name",
                       "pid": pid_of[p], "tid": 0,
                       "args": {"name": p}})
    for s in spans:
        key = (s["proc"], s["thread"])
        if key not in tid_of:
            tid_of[key] = len([k for k in tid_of
                               if k[0] == s["proc"]]) + 1
            events.append({"ph": "M", "name": "thread_name",
                           "pid": pid_of[s["proc"]],
                           "tid": tid_of[key],
                           "args": {"name": s["thread"]}})
    for s in spans:
        pid = pid_of[s["proc"]]
        tid = tid_of[(s["proc"], s["thread"])]
        events.append({
            "ph": "X", "name": s["name"], "cat": "motrace",
            "pid": pid, "tid": tid, "ts": s["ts_us"],
            "dur": max(1, s["dur_us"]),
            "args": dict(s["attrs"], span_id=s["sid"],
                         parent_id=s["psid"], cpu_us=s.get("cpu_us"))})
        for ev in s["events"]:
            events.append({
                "ph": "i", "s": "t", "name": ev["name"],
                "cat": "motrace", "pid": pid, "tid": tid,
                "ts": ev["ts_us"], "args": dict(ev["attrs"])})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"trace_id": trace_id}}


def dump(dirpath: str) -> List[str]:
    """Write one Perfetto-loadable JSON file per trace_id in the ring;
    returns the written paths."""
    os.makedirs(dirpath, exist_ok=True)
    out = []
    for tid in TRACER.trace_ids():
        path = os.path.join(dirpath, f"trace_{tid}.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump(chrome_trace(tid), f)
        out.append(path)
    return out
