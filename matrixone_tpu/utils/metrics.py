"""Prometheus-style metrics registry (reference: pkg/util/metric/v2 +
mometric — redesigned to a minimal host-side registry with text
exposition; the collector writing system_metrics tables rides the same
trace pipeline as statement_info).
"""

from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict, List, Tuple

from matrixone_tpu.utils import san

# metric primitives are leaf locks acquired INSIDE the sanitizer's own
# reporting path, so they are san.lock(internal=True): adopted (the
# san-adoption rule sees the factory) but never tracked (tracking them
# would recurse into the tracker)


def _escape_label(v) -> str:
    """Prometheus text-format label value escaping (\\ " and newline)."""
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _escape_help(v: str) -> str:
    return v.replace("\\", "\\\\").replace("\n", "\\n")


class Counter:
    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._values: Dict[Tuple, float] = defaultdict(float)
        self._lock = san.lock("Counter._lock", internal=True)

    def inc(self, value: float = 1.0, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] += value

    def get(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0.0)

    kind = "counter"

    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {_escape_help(self.help)}",
                 f"# TYPE {self.name} {self.kind}"]
        with self._lock:
            snapshot = dict(self._values)
        for key, v in sorted(snapshot.items()):
            lbl = ",".join(f'{k}="{_escape_label(val)}"'
                           for k, val in key)
            lines.append(f"{self.name}{{{lbl}}} {v}" if lbl
                         else f"{self.name} {v}")
        return lines

    def snapshot(self) -> dict:
        with self._lock:
            snapshot = dict(self._values)
        return {"type": self.kind, "help": self.help,
                "values": [{"labels": dict(key), "value": v}
                           for key, v in sorted(snapshot.items())]}


class Gauge(Counter):
    """A value that can go up and down (breaker state, pool occupancy)."""

    kind = "gauge"

    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._values: Dict[Tuple, float] = {}
        self._lock = san.lock("Gauge._lock", internal=True)

    def set(self, value: float, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = value

    def inc(self, value: float = 1.0, **labels):
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value

    def get(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0.0)


class Histogram:
    _BUCKETS = [1e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 60]

    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self.counts = [0] * (len(self._BUCKETS) + 1)
        self.sum = 0.0
        self.total = 0
        self._lock = san.lock("Histogram._lock", internal=True)

    def observe(self, v: float):
        with self._lock:
            self.sum += v
            self.total += 1
            for i, b in enumerate(self._BUCKETS):
                if v <= b:
                    self.counts[i] += 1
                    return
            self.counts[-1] += 1

    def time(self):
        h = self

        class _Timer:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *a):
                h.observe(time.perf_counter() - self.t0)
        return _Timer()

    def render(self) -> List[str]:
        """Prometheus text format: cumulative `_bucket` lines (each
        bucket counts every observation <= le), `+Inf`, `_sum`,
        `_count` — consistent under the lock so a scrape mid-observe
        never shows count ahead of the buckets."""
        lines = [f"# HELP {self.name} {_escape_help(self.help)}",
                 f"# TYPE {self.name} histogram"]
        with self._lock:
            counts = list(self.counts)
            total, sum_ = self.total, self.sum
        acc = 0
        for b, c in zip(self._BUCKETS, counts):
            acc += c
            lines.append(f'{self.name}_bucket{{le="{b}"}} {acc}')
        lines.append(f'{self.name}_bucket{{le="+Inf"}} {total}')
        lines.append(f"{self.name}_sum {sum_}")
        lines.append(f"{self.name}_count {total}")
        return lines

    def snapshot(self) -> dict:
        with self._lock:
            counts = list(self.counts)
            total, sum_ = self.total, self.sum
        return {"type": "histogram", "help": self.help,
                "sum": sum_, "count": total,
                "buckets": [{"le": b, "count": c}
                            for b, c in zip(self._BUCKETS, counts)]
                           + [{"le": "+Inf", "count": counts[-1]}]}

    def quantile(self, q: float) -> float:
        """Approximate quantile from the bucket histogram (upper bound
        of the bucket holding the q-th observation) — the public read
        path for p50/p99 reporting (bench.py), replacing direct pokes
        at `counts`/`sum`."""
        with self._lock:
            counts = list(self.counts)
            total = self.total
        if total <= 0:
            return 0.0
        target = q * total
        acc = 0
        for b, c in zip(self._BUCKETS, counts):
            acc += c
            if acc >= target:
                return b
        return float(self._BUCKETS[-1])


def histogram_delta_quantile(before: dict, after: dict,
                             q: float) -> float:
    """Approximate quantile of the observations made BETWEEN two
    Histogram.snapshot() captures (bucket-count difference), so a
    bench phase can report its own p50/p99 without the process-global
    histogram's earlier history polluting the number."""
    diffs = []
    b_by_le = {b["le"]: b["count"] for b in before["buckets"]}
    for b in after["buckets"]:
        if b["le"] == "+Inf":
            continue
        diffs.append((b["le"], b["count"] - b_by_le.get(b["le"], 0)))
    total = after["count"] - before["count"]
    if total <= 0:
        return 0.0
    target = q * total
    acc = 0
    for le, c in diffs:
        acc += c
        if acc >= target:
            return le
    return float(diffs[-1][0]) if diffs else 0.0


class Registry:
    def __init__(self):
        self._metrics: Dict[str, object] = {}
        self._lock = san.lock("Registry._lock")
        san.guard(self, self._lock, name="metrics.Registry")

    def counter(self, name: str, help_: str = "") -> Counter:
        with self._lock:
            if name not in self._metrics:
                san.mutating(self)
                self._metrics[name] = Counter(name, help_)
            return self._metrics[name]

    def histogram(self, name: str, help_: str = "") -> Histogram:
        with self._lock:
            if name not in self._metrics:
                san.mutating(self)
                self._metrics[name] = Histogram(name, help_)
            return self._metrics[name]

    def gauge(self, name: str, help_: str = "") -> Gauge:
        with self._lock:
            if name not in self._metrics:
                san.mutating(self)
                self._metrics[name] = Gauge(name, help_)
            return self._metrics[name]

    def render(self) -> str:
        """Prometheus text exposition format (the scrape surface:
        `mo_ctl('metrics','dump')` and `python -m tools.moscrape`).
        Every family carries # HELP/# TYPE; histograms emit cumulative
        `_bucket`/`_sum`/`_count`; label values are escaped — output
        parses with a standard Prometheus client."""
        with self._lock:
            metrics = sorted(self._metrics.items())
        lines: List[str] = []
        for _name, m in metrics:
            lines.extend(m.render())
        return "\n".join(lines) + "\n"

    def expose(self) -> str:
        """Back-compat alias for render()."""
        return self.render()

    def snapshot(self) -> Dict[str, dict]:
        """Structured point-in-time view of every metric — the public
        programmatic read API (bench.py, dashboards) so callers never
        poke `_values`/`counts` internals."""
        with self._lock:
            metrics = sorted(self._metrics.items())
        return {name: m.snapshot() for name, m in metrics}


#: process-global registry (reference: metric/v2 package-level vars)
REGISTRY = Registry()

query_seconds = REGISTRY.histogram(
    "mo_query_duration_seconds", "SQL statement execution latency")
rows_scanned = REGISTRY.counter(
    "mo_scan_rows_total", "rows scanned by table scans")
txn_commits = REGISTRY.counter(
    "mo_txn_commit_total", "transaction commits by outcome")
join_spills = REGISTRY.counter(
    "mo_join_spill_total", "joins whose build side Grace-spilled to host")
join_probe_rows = REGISTRY.counter(
    "mo_join_probe_rows_total",
    "rows of the fused join probe by stage: in (live probe rows offered) "
    "and matched (lanes the probe emitted, after the residual)")
join_probe_lanes = REGISTRY.counter(
    "mo_join_probe_lanes_total",
    "match lanes the fused probe expanded its live rows to (rows x lanes "
    "a row; one a row against a build that is unique on the join keys)")
join_probe_retries = REGISTRY.counter(
    "mo_join_probe_retries_total",
    "probe batches re-run with doubled lanes after a duplicate overflow")
join_build_rows = REGISTRY.counter(
    "mo_join_build_rows_total",
    "live rows of the build sides the fused join finalized")
join_build_columns = REGISTRY.counter(
    "mo_join_build_columns_total",
    "columns of executed joins' build sides by outcome, once per join: "
    "gathered (those the probe gathers a lane: the join's output, which "
    "sql/optimize.prune_columns narrows to what is read above it, and "
    "what its residual reads), pruned (the build side's other columns: "
    "keys and filter-only columns, which cost the probe nothing)")
blockcache_ops = REGISTRY.counter(
    "mo_blockcache_ops_total", "decoded-column cache lookups by outcome")
blockcache_bytes = REGISTRY.counter(
    "mo_blockcache_fetch_bytes_total",
    "decoded bytes brought into the block cache on misses")
blockcache_device_ops = REGISTRY.counter(
    "mo_blockcache_device_ops_total",
    "device-tier cache lookups: hit (zero-upload), upload (host hit, "
    "re-staged), miss (decode required)")
blockcache_upload_bytes = REGISTRY.counter(
    "mo_blockcache_upload_bytes_total",
    "host->device bytes staged for cached columns (warm loops drive "
    "this to ~0)")
object_read_bytes = REGISTRY.counter(
    "mo_object_read_bytes_total",
    "stored (compressed) bytes read from the file service by object "
    "reads; over mo_blockcache_fetch_bytes_total it is what "
    "compression saves, per row it is the read amplification")
decode_seconds = REGISTRY.counter(
    "mo_object_decode_seconds_total",
    "seconds spent fetching+decoding object column blocks (miss path)")
object_write_seconds = REGISTRY.counter(
    "mo_object_write_seconds_total",
    "seconds spent serializing+writing objectio objects")
scan_chunks = REGISTRY.counter(
    "mo_scan_chunks_total",
    "chunks of table scans by outcome: scanned (handed to the "
    "consumer), pruned_segment (every chunk of a segment excluded by "
    "its stored zonemap, before any read), pruned_chunk (excluded by "
    "the chunk's own min/max, after the read), all_dead (every row "
    "deleted)")
scan_columns = REGISTRY.counter(
    "mo_scan_columns_total",
    "columns of executed table scans by outcome, once per scan: read "
    "(the columns the planned Scan carries), pruned (the table's other "
    "columns, which sql/optimize.prune_columns dropped because the plan "
    "references none of them)")
from_numpy_columns = REGISTRY.counter(
    "mo_from_numpy_columns_total",
    "columns container/device.from_numpy staged, once a column of every "
    "call, by path: device (a device array already at bucket length, "
    "taken as it is), device_pad (a shorter device array, padded to the "
    "bucket on the device), host (a host array, padded and uploaded), "
    "roundtrip (a device array pulled to the host and uploaded again, "
    "e.g. for a dtype the column cannot keep: held to 0 on a scan)")
scan_slice_dispatch = REGISTRY.counter(
    "mo_scan_slice_dispatch_total",
    "programs MVCCTable._read_chunk dispatched over a chunk's "
    "device-resident columns, by how: chunk (the one program that slices "
    "every data and validity array of the chunk).  A chunk that is its "
    "whole segment, and a numpy column, dispatch nothing; nothing is "
    "dispatched a column any more (how=column was the tombstone gather: "
    "a chunk's dead rows ride the row mask)")
scan_chunk_rows = REGISTRY.counter(
    "mo_scan_chunk_rows_total",
    "rows of the chunks handed to a scan's consumer, once a scanned "
    "chunk, by state: live (visible to the statement), dead (tombstoned: "
    "read, sliced and uploaded at the chunk's own length, then masked "
    "out)")
scan_chunks_backing = REGISTRY.counter(
    "mo_scan_chunks_backing_total",
    "chunks handed to a scan's consumer, once a scanned chunk, by what "
    "backs their segment: object (flushed, served through the block "
    "cache and its device tier), memory (numpy in RAM since its commit, "
    "or a transaction's workspace: uploaded again by every scan)")
scan_zonemap_checks = REGISTRY.counter(
    "mo_scan_zonemap_checks_total",
    "zonemap predicates checked against a chunk's own min/max, once a "
    "predicate a chunk, by source of the summary: memo (kept with the "
    "immutable object from an earlier scan: no program, no wait), device "
    "(filled now by one program and one fetch a chunk), host (numpy "
    "columns, computed on the host)")
device_wait = REGISTRY.counter(
    "mo_device_wait_total",
    "host reads of a device value that block the statement's path, by "
    "site: zonemap (a chunk's n_valid/min/max, once a chunk of an object "
    "and then kept with it), flags (fused all-valid "
    "flags), limit (fused LIMIT rows seen), finalize (the fused carry "
    "handed to the result path, whose fetch is the statement's last "
    "wait), vector_search (the candidates of a vector index search and "
    "its exact re-rank), vector_delta (the exact scan of an index's "
    "delta segment), join_rf (a fused build's scalars: runtime-filter "
    "ranges, the key's range, live rows, column ranges; one a build), "
    "join_flags (a fused probe's all-valid flags), join_overflow (a probe "
    "batch's duplicate-overflow flag; never read against a unique build), "
    "join_stats (a join's probe row counts, once after its last step), "
    "agg_slots (which slots of the wide dense grouped aggregate hold rows)")
vector_fetch_rows = REGISTRY.counter(
    "mo_vector_fetch_rows_total",
    "candidate rows a VectorTopK fetched from its table by row id")
vector_fetch_bytes = REGISTRY.counter(
    "mo_vector_fetch_bytes_total",
    "host bytes (data and validity) MVCCTable.fetch_rows gathered for a "
    "VectorTopK: rows x the widths of the columns the statement names")
scan_prefetch = REGISTRY.counter(
    "mo_scan_prefetch_total",
    "scan read-ahead outcomes: chunks served ready vs waited-on")
scan_prefetch_wait_seconds = REGISTRY.counter(
    "mo_scan_prefetch_wait_seconds_total",
    "seconds the scan consumer blocked waiting on the prefetcher")

# ---- resilient RPC fabric (cluster/rpc.py, reference: morpc metrics)
rpc_attempts = REGISTRY.counter(
    "mo_rpc_attempts_total", "RPC send attempts by op")
rpc_retries = REGISTRY.counter(
    "mo_rpc_retries_total", "RPC attempts that were retries, by op")
rpc_errors = REGISTRY.counter(
    "mo_rpc_errors_total",
    "RPC calls that failed after all attempts, by error kind")
rpc_seconds = REGISTRY.histogram(
    "mo_rpc_call_seconds", "successful RPC round-trip latency")
rpc_breaker_state = REGISTRY.gauge(
    "mo_rpc_breaker_state",
    "per-peer circuit breaker state (0 closed, 1 half-open, 2 open)")
rpc_breaker_transitions = REGISTRY.counter(
    "mo_rpc_breaker_transitions_total",
    "circuit breaker state transitions, by peer and new state")
fault_fired = REGISTRY.counter(
    "mo_fault_triggered_total", "armed fault points that fired, by point")

# ---- vector search fast path (vectorindex/, reference: cgo/cuvs worker)
vector_search_seconds = REGISTRY.counter(
    "mo_vector_search_seconds_total",
    "IVF search wall seconds by stage (probe/score/merge — filled by the "
    "diagnostic staged re-execution, bench.py)")
vector_search_queries = REGISTRY.counter(
    "mo_vector_search_queries_total", "queries entering ivf search")
vector_search_pad_rows = REGISTRY.counter(
    "mo_vector_search_pad_rows_total",
    "pad rows added by the internal power-of-two batch bucketing "
    "(waste visibility: pad/queries = batch occupancy loss)")
vector_build_seconds = REGISTRY.counter(
    "mo_vector_build_seconds_total",
    "IVF build wall seconds by stage (kmeans/assign/pack)")
vector_shard_imbalance = REGISTRY.gauge(
    "mo_vector_shard_imbalance",
    "sharded IVF row imbalance: max shard rows / mean shard rows")
vector_batch_rows = REGISTRY.counter(
    "mo_vector_batch_rows_total",
    "worker micro-batcher: real query rows dispatched to the device")
vector_batch_coalesced = REGISTRY.counter(
    "mo_vector_batch_coalesced_total",
    "worker micro-batcher: requests that rode another request's dispatch")
proxy_failovers = REGISTRY.counter(
    "mo_proxy_failover_total",
    "proxied sessions moved to another backend after a backend loss")
proxy_conn_refused = REGISTRY.counter(
    "mo_proxy_conn_refused_total",
    "client connections refused: every backend at its connection cap")

# ---- serving layer (serving/, reference: proxy/queryservice tier)
plan_cache_ops = REGISTRY.counter(
    "mo_plan_cache_ops_total",
    "plan cache lookups by outcome (hit/miss/uncacheable/invalidated/"
    "bypass)")
plan_cache_entries = REGISTRY.gauge(
    "mo_plan_cache_entries", "resident plan cache entries")
result_cache_ops = REGISTRY.counter(
    "mo_result_cache_ops_total",
    "result cache lookups by outcome (hit/miss/stale/bypass)")
result_cache_entries = REGISTRY.gauge(
    "mo_result_cache_entries", "resident result cache entries")
result_cache_bytes = REGISTRY.gauge(
    "mo_result_cache_bytes", "bytes held by cached result sets")
result_cache_evictions = REGISTRY.counter(
    "mo_result_cache_evictions_total",
    "result entries evicted by the byte-budget LRU")
admission_total = REGISTRY.counter(
    "mo_admission_total",
    "admission decisions by lane and outcome (admitted/shed_capacity/"
    "shed_timeout/shed_deadline/killed)")
admission_queue_seconds = REGISTRY.histogram(
    "mo_admission_queue_seconds",
    "time admitted statements spent waiting for a slot")
admission_running = REGISTRY.gauge(
    "mo_admission_running", "statements currently holding a slot")
admission_queued = REGISTRY.gauge(
    "mo_admission_queued", "statements waiting in the admission queue")

# ---- whole-plan XLA fusion (vm/fusion.py)
fusion_dispatch = REGISTRY.counter(
    "mo_fusion_dispatch_total",
    "fused-fragment step executions by kind (step = one compiled "
    "device program per batch; eager = degraded per-op evaluation)")
fusion_compile = REGISTRY.counter(
    "mo_fusion_compile_total",
    "fragment compile-cache lookups by outcome (hit/miss/trace_fail)")
fusion_trace_seconds = REGISTRY.counter(
    "mo_fusion_trace_seconds_total",
    "seconds spent tracing+compiling fused fragment programs")
fusion_exec = REGISTRY.counter(
    "mo_fusion_exec_total",
    "fragment executions by mode (fused/eager/fallback/degraded)")

pallas_traces = REGISTRY.counter(
    "mo_pallas_trace_total",
    "Pallas kernels traced into a program, by kernel and by whether "
    "the trace was for interpret mode (chosen by ops/kernels.py; counted "
    "at trace time, so once per compiled program, not per dispatch)")

# ---- Python/JAX UDF subsystem (udf/, reference: pkg/udf/pythonservice)
udf_calls = REGISTRY.counter(
    "mo_udf_calls_total",
    "UDF evaluations by tier (jit/row/remote/aggregate)")
udf_rows = REGISTRY.counter(
    "mo_udf_rows_total", "rows processed by UDF evaluations, by tier")
udf_compile = REGISTRY.counter(
    "mo_udf_compile_total",
    "UDF compile-cache lookups by outcome (hit/miss/trace_fail)")
udf_offload = REGISTRY.counter(
    "mo_udf_offload_total",
    "remote UDF offload outcomes (ok/fallback_breaker/"
    "fallback_transport)")
udf_batch_rows = REGISTRY.counter(
    "mo_udf_batch_rows_total",
    "rows through the worker's UDF micro-batcher")
udf_batch_coalesced = REGISTRY.counter(
    "mo_udf_batch_coalesced_total",
    "remote UDF requests that rode another request's dispatch")

# ---- materialized views (matrixone_tpu/mview)
mview_apply = REGISTRY.counter(
    "mo_mview_apply_total",
    "materialized-view maintenance applications by tier "
    "(dense/general/recompute/init)")
mview_rows = REGISTRY.counter(
    "mo_mview_rows_total",
    "delta rows processed by materialized-view maintenance")
mview_apply_seconds = REGISTRY.counter(
    "mo_mview_apply_seconds_total",
    "seconds spent in view maintenance by kind (delta/full)")

# ---- CDC delta economy (matrixone_tpu/cdc)
cdc_events = REGISTRY.counter(
    "mo_cdc_events_total",
    "CDC events delivered to sinks by path (live/backfill)")
cdc_backfills = REGISTRY.counter(
    "mo_cdc_backfill_total",
    "CDC backfill/resume runs by outcome (seed: from-scratch replay; "
    "live: resume with no fence crossed; fenced: exactly-once resume "
    "across a compaction via its snapshot fence; refused: resume at or "
    "below the GC'd delta floor — history gone, caller must re-seed)")

# ---- background compaction scheduler (storage/merge_sched.py)
merge_tasks = REGISTRY.counter(
    "mo_merge_tasks_total",
    "merge-scheduler task outcomes by kind (compact/checkpoint/gc) and "
    "outcome (ok/noop/deferred/failed)")
merge_rows = REGISTRY.counter(
    "mo_merge_rows_total", "live rows rewritten into merged segments")
merge_segments = REGISTRY.counter(
    "mo_merge_segments_total", "pre-merge segments compacted by merges")
merge_seconds = REGISTRY.counter(
    "mo_merge_seconds_total",
    "merge wall seconds by phase (rewrite: off-lock concat + object "
    "write; swap: under-lock catalog publish)")
merge_fences_released = REGISTRY.counter(
    "mo_merge_fences_released_total",
    "snapshot fences released by delta-aware GC (nothing below the "
    "merge point could still reach them)")
merge_gc_objects = REGISTRY.counter(
    "mo_merge_gc_objects_total",
    "pre-merge object files deleted by fence GC")

# ---- differential query-equivalence analyzer (utils/qa.py, tools/moqa)
qa_queries = REGISTRY.counter(
    "mo_qa_queries_total",
    "queries generated and executed by the moqa corpus runner")
qa_oracle_checks = REGISTRY.counter(
    "mo_qa_oracle_checks_total",
    "moqa oracle verdicts by oracle (lockstep/tlp/norec/limit/sqlite/"
    "mview/staleness)")
qa_findings = REGISTRY.counter(
    "mo_qa_findings_total",
    "moqa findings by kind (lockstep-mismatch/oracle failures/"
    "canary-in-result/canary-in-carry/error)")

# ---- distributed tracing plane (utils/motrace.py, tools/moscrape)
trace_spans = REGISTRY.counter(
    "mo_trace_spans_total",
    "completed motrace spans landed in this process's ring, by the "
    "span's origin process (remote-session spans count once, at the "
    "trace-owning process that merges them)")
trace_traces = REGISTRY.counter(
    "mo_trace_traces_total",
    "root-span head-sampling decisions (sampled/unsampled)")
trace_ring_dropped = REGISTRY.counter(
    "mo_trace_ring_dropped_total",
    "spans evicted from the bounded trace ring (raise MO_TRACE_RING)")

# ---- runtime concurrency sanitizer (utils/san.py, tools/mosan)
san_findings = REGISTRY.counter(
    "mo_san_findings_total",
    "sanitizer findings by rule (lock-order-cycle/blocking-under-lock/"
    "unguarded-mutation/thread-leak)")
san_lock_edges = REGISTRY.gauge(
    "mo_san_lock_edges",
    "distinct lock-order edges observed by the armed sanitizer")

# ---- trace-capture / cache-key auditor (utils/keys.py, tools/mokey)
key_captures = REGISTRY.counter(
    "mo_key_captures_total",
    "capture content hashes recorded at compile time by the armed "
    "key auditor (one per dep per first-sighted cache key)")
key_audits = REGISTRY.counter(
    "mo_key_audits_total",
    "cache-hit re-hash audits by outcome (ok/mismatch)")
key_findings = REGISTRY.counter(
    "mo_key_findings_total",
    "capture-content mismatches under a colliding cache key, by "
    "audited site label (fragment/joinbuild/joinprobe/mview/udf/tree)")

# ---- device-shard exchanges (parallel/dist_query.py shard executor)
exchange_shuffle_rows = REGISTRY.counter(
    "mo_exchange_shuffle_rows_total",
    "rows that crossed a hash exchange (vm/operators._hash_route row "
    "routing; co-partitioned reads that resolve structurally count 0)")
exchange_broadcast_bytes = REGISTRY.counter(
    "mo_exchange_broadcast_bytes_total",
    "bytes replicated to the non-owning shards by broadcast join "
    "builds (materialized once, bytes x (n_shards - 1))")
exchange_partial_merge = REGISTRY.counter(
    "mo_exchange_partial_merge_total",
    "cross-shard partial-result merges by kind "
    "(dense/general/scalar/topk/join)")
exchange_degrade = REGISTRY.counter(
    "mo_exchange_degrade_total",
    "sharded fragments that failed on the shards and re-ran on one "
    "device (the `[shard] degrading` line on stderr)")

# ---- restart recovery (Engine.open) + crash sweep (utils/crash.py,
# ---- tools/mocrash)
recovery_frames = REGISTRY.counter(
    "mo_recovery_frames_total",
    "intact WAL frames replayed by Engine.open restarts")
recovery_torn_bytes = REGISTRY.counter(
    "mo_recovery_torn_bytes_total",
    "torn-tail bytes discarded at the end of the WAL during restart "
    "replay (a crash mid-append leaves them; non-zero is normal after "
    "a kill, growth without kills is a bug)")
recovery_orphans = REGISTRY.counter(
    "mo_recovery_orphans_total",
    "orphaned *.tmp files GC'd by Engine.open (a writer died between "
    "its tmp fsync and the atomic replace)")
crash_points = REGISTRY.counter(
    "mo_crash_points_total",
    "crash points materialized by the mocrash sweep, by torn/lossy "
    "variant")
crash_recoveries = REGISTRY.counter(
    "mo_crash_recoveries_total",
    "mocrash recovery attempts by outcome (ok/violation)")
crash_findings = REGISTRY.counter(
    "mo_crash_findings_total",
    "mocrash invariant violations by invariant name")
