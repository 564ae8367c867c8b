"""Headline benchmark: IVF-Flat vector search on one TPU chip.

Mirrors the reference's first-party benchmark (cgo/cuvs/blog.md: wiki_all
768-d, top-20, IVF-Flat CPU search = 768 QPS @ recall 0.86 at 1M rows,
nprobe=8 — BASELINE.md). Same shape here: 1M x 768 synthetic clustered
embeddings, top-20, batched queries on a single TPU v5e.

Prints ONE JSON line:
  {"metric": ..., "value": QPS, "unit": "qps", "vs_baseline": QPS/768,
   ...aux fields (recall, build seconds)}

Env overrides: MO_BENCH_N (rows), MO_BENCH_D (dim), MO_BENCH_Q (queries),
MO_BENCH_SMOKE=1 (tiny shapes, CPU-friendly sanity run).
"""

import json
import os
import sys
import threading
import time

import jax

# Persistent XLA compilation cache (MO_JAX_CACHE=0 disables): build and
# search compiles are part of the timed numbers, and the cuVS worker the
# design chases caches its compiled kernels the same way.
from matrixone_tpu.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import jax.numpy as jnp
import numpy as np

import matrixone_tpu  # noqa: F401  (enables x64)
from matrixone_tpu.vectorindex import brute_force, ivf_flat
from matrixone_tpu.vectorindex.recall import recall_at_k

SMOKE = os.environ.get("MO_BENCH_SMOKE") == "1"
INDEX_KIND = os.environ.get("MO_BENCH_INDEX", "ivfflat")   # ivfflat | ivfpq
METRIC = os.environ.get("MO_BENCH_METRIC", "ivf")          # ivf | q1
N = int(os.environ.get("MO_BENCH_N", 20_000 if SMOKE else 1_000_000))
D = int(os.environ.get("MO_BENCH_D", 64 if SMOKE else 768))
NQ = int(os.environ.get("MO_BENCH_Q", 256 if SMOKE else 1024))
K = 20
NLIST = 64 if SMOKE else 1024
NPROBE = 8
BATCH = 128 if SMOKE else 256
# a CPU-box timing chose 64; not measured on the chip
QUERY_CHUNK = int(os.environ.get("MO_BENCH_QC", 64))
BASELINE_QPS = 768.0  # cgo/cuvs/blog.md:149 — IVF-Flat CPU search, 1M, nprobe=8


def make_data(key, n, d, n_centers=2048):
    """Clustered synthetic embeddings (recall on structureless uniform data
    is meaningless; wiki_all embeddings are strongly clustered)."""
    kc, kl, kn, kq = jax.random.split(key, 4)
    centers = jax.random.normal(kc, (min(n_centers, n // 4 or 1), d),
                                jnp.float32) * 1.0
    # generate in chunks to bound peak memory
    chunks = []
    step = 1 << 17
    for i in range(0, n, step):
        m = min(step, n - i)
        lab = jax.random.randint(jax.random.fold_in(kl, i), (m,), 0,
                                 centers.shape[0])
        noise = jax.random.normal(jax.random.fold_in(kn, i), (m, d),
                                  jnp.float32) * 0.35
        chunks.append(centers[lab] + noise)
    data = jnp.concatenate(chunks)
    qlab = jax.random.randint(kq, (NQ,), 0, centers.shape[0])
    qnoise = jax.random.normal(jax.random.fold_in(kq, 1), (NQ, d),
                               jnp.float32) * 0.35
    queries = centers[qlab] + qnoise
    return data, queries


def bench_q1(n: int = None) -> dict:
    """TPC-H Q1 rows/sec through the full SQL engine (BASELINE config #1),
    measured WITH the object-backed storage path enabled: the table is
    loaded, checkpointed to objectio objects on a LocalFS object store,
    and its segments demoted to blockcache-served lazy views — every
    timed scan goes through the out-of-core read path, no bypass.

    The reference publishes no first-party Q1 throughput (BASELINE.md), so
    vs_baseline is null; the number itself is the tracked metric."""
    import tempfile

    from matrixone_tpu.frontend import Session
    from matrixone_tpu.storage import blockcache
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import LocalFS
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.utils import tpch
    if n is None:
        n = int(os.environ.get("MO_BENCH_N",
                               100_000 if SMOKE else 6_001_215))
    # size the decoded-column cache to the working set (~96 B/row over
    # the scanned columns + validity) so the warm loop measures the hot
    # path, not eviction thrash; an explicit MO_BLOCK_CACHE_MB wins
    os.environ.setdefault("MO_BLOCK_CACHE_MB",
                          str(max(256, n * 96 >> 20)))
    fs = LocalFS(tempfile.mkdtemp(prefix="mo_bench_q1_"))
    eng = Engine(fs)
    s = Session(catalog=eng)
    # load = generate + insert + checkpoint-to-objects + demote: the
    # number includes every byte reaching the object store (r5 measured
    # 23.74 s here; the coalesced lz4 write path is the fix)
    t0 = time.time()
    arrays = tpch.load_lineitem(s.catalog, n)
    eng.checkpoint(demote=True)
    t_load = time.time() - t0
    lazy = [seg.is_lazy for seg in eng.get_table("lineitem").segments]
    assert lazy and all(lazy), "bench must run object-backed (no bypass)"
    oracle = tpch.q1_oracle(arrays)
    t0 = time.time()
    rows = s.execute(tpch.Q1_SQL).rows()      # cold: decode + compile
    t_cold = time.time() - t0
    exact = tpch.q1_check(rows, oracle)
    blockcache.CACHE.reset_stats()            # warm loop accounting
    # ---- warm fused loop (MO_PLAN_FUSION default on): one compiled
    # device program per fragment per batch; dispatch + trace deltas
    # ride the JSON line as the fusion evidence
    disp0 = M.fusion_dispatch.get(kind="step")
    trace0 = M.fusion_trace_seconds.get()
    best = 0.0
    for _ in range(3):
        t0 = time.time()
        s.execute(tpch.Q1_SQL)
        best = max(best, n / (time.time() - t0))
    fused_dispatches = M.fusion_dispatch.get(kind="step") - disp0
    trace_seconds = M.fusion_trace_seconds.get() - trace0
    # ---- warm-RESIDENT loop: after the reps above the blockcache's
    # device tier holds every decoded column as a ready device array,
    # so this window measures pure device residency — the tentpole
    # claim is device_cache_hit_rate >= 0.99 with ~0 re-upload bytes
    # (every byte staged host->device during the window is counted)
    blockcache.CACHE.reset_stats()
    best_res = 0.0
    for _ in range(2):
        t0 = time.time()
        s.execute(tpch.Q1_SQL)
        best_res = max(best_res, n / (time.time() - t0))
    cache_res = blockcache.CACHE.stats()
    dev_tier = cache_res["device_tier"]
    # ---- the pre-fusion per-operator path, kept as its own
    # non-comparable metric family (same convention as the r04->r05
    # object-backed methodology split): trends continue for both
    fusion_was = os.environ.get("MO_PLAN_FUSION")
    os.environ["MO_PLAN_FUSION"] = "0"
    try:
        s.execute(tpch.Q1_SQL)                # re-warm the unfused jits
        best_unfused = 0.0
        for _ in range(2):
            t0 = time.time()
            s.execute(tpch.Q1_SQL)
            best_unfused = max(best_unfused, n / (time.time() - t0))
    finally:
        if fusion_was is None:
            os.environ.pop("MO_PLAN_FUSION", None)
        else:
            os.environ["MO_PLAN_FUSION"] = fusion_was
    # ---- MO_TRACE_PROFILE=1: one diagnostic rep with motrace armed —
    # the fused run's full span tree (statement -> fusion.compile /
    # fusion.dispatch / txn spans) lands as a Perfetto-loadable Chrome
    # trace artifact next to the JSON line
    trace_artifact = None
    trace_spans = 0
    if os.environ.get("MO_TRACE_PROFILE") == "1":
        import tempfile as _tf
        from matrixone_tpu.utils import motrace
        was_armed, was_sample = (motrace.TRACER.armed,
                                 motrace.TRACER.sample)
        motrace.TRACER.arm(sample=1.0)
        motrace.TRACER.clear()
        try:
            s.execute(tpch.Q1_SQL)
            tids = motrace.TRACER.trace_ids()
            if tids:
                trace_spans = len(motrace.TRACER.spans_of(tids[-1]))
            paths = motrace.dump(_tf.mkdtemp(prefix="mo_q1_trace_"))
            trace_artifact = paths[-1] if paths else None
        finally:
            # restore BOTH armed and sample: an MO_TRACE=1 run at 1%
            # sampling must not leave later families tracing at 100%
            motrace.TRACER.armed = was_armed
            motrace.TRACER.sample = was_sample
            motrace.TRACER.clear()
    cache = blockcache.CACHE.stats()
    # roofline-style evidence for the scan+agg path: Q1 touches 7
    # columns (l_quantity/extendedprice/discount/tax as decimal64,
    # returnflag/linestatus codes, shipdate) — effective scan bandwidth
    # is the honest "how close to HBM" number for a bandwidth-bound query
    q1_bytes = n * (4 * 8 + 2 * 4 + 4)
    # analytic flop count per row: 7 agg lanes (sum/avg inputs, the
    # disc_price/charge products, predicates and the group scatter) —
    # ~40 flops/row is the honest order of magnitude for Q1's arithmetic
    q1_flops = n * 40
    from matrixone_tpu.utils import roofline as _rf
    pb = (_rf.peaks() or {}).get("bytes_per_s")
    # roofline promotion: achieved bytes/s + flops/s for the fused
    # family vs the device's published peaks (utilizations stay null
    # on the CPU smoke, which has no peak; the achieved rates trend)
    rf_q1 = _rf.mfu(q1_flops, q1_bytes, 1.0, n / best) if best else {}
    serving = None
    if os.environ.get("MO_BENCH_NO_SERVING") != "1":
        try:
            serving = bench_serving(s, n)
        except Exception as e:               # noqa: BLE001
            serving = {"metric": "serving_hot_qps", "value": 0,
                       "unit": "error", "vs_baseline": None,
                       "error": f"{type(e).__name__}: {e}"}
    udf_entry = None
    if os.environ.get("MO_BENCH_NO_UDF") != "1":
        try:
            udf_entry = bench_udf()
        except Exception as e:               # noqa: BLE001
            udf_entry = {"metric": "udf_qps", "value": 0,
                         "unit": "error", "vs_baseline": None,
                         "error": f"{type(e).__name__}: {e}"}
    mview_entry = None
    if os.environ.get("MO_BENCH_NO_MVIEW") != "1":
        try:
            mview_entry = bench_mview()
        except Exception as e:               # noqa: BLE001
            mview_entry = {"metric": "mview_delta_refresh_speedup",
                           "value": 0, "unit": "error",
                           "vs_baseline": None,
                           "error": f"{type(e).__name__}: {e}"}
    q3_entries = []
    if os.environ.get("MO_BENCH_NO_Q3") != "1":
        try:
            q3_entry = bench_q3()
            # hoist the nested unfused family: the driver contract and
            # bench_guard read one level of extra_metrics
            q3_entries = [q3_entry] + q3_entry.pop("extra_metrics", [])
        except Exception as e:               # noqa: BLE001
            q3_entries = [{"metric": "tpch_q3_fused_rows_per_sec",
                           "value": 0, "unit": "error",
                           "vs_baseline": None,
                           "error": f"{type(e).__name__}: {e}"}]
    if os.environ.get("MO_BENCH_NO_Q3S") != "1":
        try:
            q3s_entry = bench_q3_sharded()
            q3_entries += [q3s_entry] + q3s_entry.pop("extra_metrics",
                                                      [])
        except Exception as e:               # noqa: BLE001
            q3_entries.append({
                "metric": "tpch_q3_sharded_rows_per_sec",
                "value": 0, "unit": "error", "vs_baseline": None,
                "error": f"{type(e).__name__}: {e}"})
    unfused_entry = {
        # the per-operator path's own family: the absolute floor for it
        # stays in BENCH_FLOORS.json, the fused family gets its own
        "metric": f"tpch_q1_rows_per_sec_{n}",
        "value": round(best_unfused, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "plan_fusion": 0,
        "backend": jax.default_backend(),
    }
    warmres_entry = {
        # the device-residency family: same query, measured in the
        # window where the blockcache's device tier is fully hot —
        # the floor for it guards the zero-re-upload property, the
        # hit-rate/upload fields ARE the acceptance evidence
        "metric": f"tpch_q1_warmres_rows_per_sec_{n}",
        "value": round(best_res, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "device_cache_hit_rate": dev_tier["hit_rate"],
        "upload_bytes": cache_res["uploaded_bytes"],
        "device_cache_used_bytes": dev_tier["used_bytes"],
        "device_cache_budget_bytes": dev_tier["budget_bytes"],
        "backend": jax.default_backend(),
    }
    extras = [m for m in (unfused_entry, warmres_entry, serving,
                          udf_entry, mview_entry) if m] + q3_entries
    return {
        **({"extra_metrics": extras} if extras else {}),
        "metric": f"tpch_q1_fused_rows_per_sec_{n}",
        "value": round(best, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "exact_vs_oracle": exact,
        "fused_dispatches": int(fused_dispatches),
        "trace_seconds": round(trace_seconds, 4),
        "fused_over_unfused": (round(best / best_unfused, 2)
                               if best_unfused else None),
        "load_seconds": round(t_load, 2),
        "cold_run_seconds": round(t_cold, 2),
        "object_backed": True,
        "object_write_seconds": round(M.object_write_seconds.get(), 3),
        "blockcache_hits": cache["hits"],
        "blockcache_misses": cache["misses"],
        "blockcache_hit_rate": cache["hit_rate"],
        "decode_seconds": cache["decode_seconds"],
        "device_cache_hit_rate": dev_tier["hit_rate"],
        "warm_upload_bytes": cache_res["uploaded_bytes"],
        "prefetch_ready": M.scan_prefetch.get(outcome="ready"),
        "prefetch_waited": M.scan_prefetch.get(outcome="waited"),
        "backend": jax.default_backend(),
        "scan_gbps": round(q1_bytes * best / n / 1e9, 2),
        "hbm_util": (round(q1_bytes * best / n / pb, 4) if pb else None),
        **({"roofline": rf_q1} if rf_q1 else {}),
        **({"trace_artifact": trace_artifact,
            "trace_spans": trace_spans} if trace_artifact else {}),
    }


def bench_q3(n: int = None) -> dict:
    """TPC-H Q3 rows/sec: the multi-join family the fused join/topk
    fragments exist for — customer ⋈ orders ⋈ lineitem with a grouped
    aggregate and an ORDER BY … LIMIT 10 tail, over object-backed
    tables.  Reports the fused headline next to an unfused lockstep
    re-measure (MO_PLAN_FUSION=0, same r04->r05 separate-family
    convention as Q1) plus the fused dispatch count per probe batch —
    the "whole query in single-digit dispatches" evidence.  Results
    are checked exactly: fused == unfused == the integer-domain
    q3_oracle."""
    import tempfile

    from matrixone_tpu.frontend import Session
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import LocalFS
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.utils import tpch
    if n is None:
        n = int(os.environ.get("MO_BENCH_Q3_N",
                               50_000 if SMOKE else 1_500_000))
    os.environ.setdefault("MO_BLOCK_CACHE_MB",
                          str(max(256, n * 160 >> 20)))
    fs = LocalFS(tempfile.mkdtemp(prefix="mo_bench_q3_"))
    eng = Engine(fs)
    s = Session(catalog=eng)
    t0 = time.time()
    arrays = tpch.load_lineitem(s.catalog, n)
    q3data = tpch.load_tpch_q3(s.catalog, max(n // 4, 100))
    eng.checkpoint(demote=True)
    t_load = time.time() - t0
    lazy = [seg.is_lazy for seg in eng.get_table("lineitem").segments]
    assert lazy and all(lazy), "bench must run object-backed (no bypass)"
    t0 = time.time()
    rows = s.execute(tpch.Q3_SQL).rows()      # cold: decode + compile
    t_cold = time.time() - t0
    # exactness: engine rows vs the integer-domain oracle (revenue is
    # decimal scale-4 exact, dates compare as day counts)
    import datetime as _dt
    epoch = _dt.date(1970, 1, 1)
    exp = tpch.q3_oracle(arrays, q3data)
    exact = (len(rows) == len(exp) and all(
        g[0] == e[0] and round(g[1] * 10000) == e[1]
        and (g[2] - epoch).days == e[2]
        for g, e in zip(rows, exp)))
    disp0 = M.fusion_dispatch.get(kind="step")
    best = 0.0
    reps = 2 if SMOKE else 3
    for _ in range(reps):
        t0 = time.time()
        s.execute(tpch.Q3_SQL)
        best = max(best, n / (time.time() - t0))
    fused_dispatches = M.fusion_dispatch.get(kind="step") - disp0
    # warm-resident window: device tier is hot after the reps above —
    # measure the residency evidence (hit rate / re-upload bytes) over
    # one more fused execution
    from matrixone_tpu.storage import blockcache
    blockcache.CACHE.reset_stats()
    s.execute(tpch.Q3_SQL)
    cache_res = blockcache.CACHE.stats()
    dev_tier = cache_res["device_tier"]
    # lineitem streams in ceil(n / 2^20)-row batches; the dim sides add
    # their own (one-batch) builds — per-batch is the honest form of
    # the single-digit-dispatches claim
    n_batches = max(1, -(-n // (1 << 20))) * reps
    # ---- unfused lockstep: same engine, same data, per-operator path,
    # bit-identical rows (exact_vs_oracle holds for both)
    fusion_was = os.environ.get("MO_PLAN_FUSION")
    os.environ["MO_PLAN_FUSION"] = "0"
    try:
        rows_unfused = s.execute(tpch.Q3_SQL).rows()   # re-warm jits
        best_unfused = 0.0
        for _ in range(reps - 1):
            t0 = time.time()
            s.execute(tpch.Q3_SQL)
            best_unfused = max(best_unfused, n / (time.time() - t0))
    finally:
        if fusion_was is None:
            os.environ.pop("MO_PLAN_FUSION", None)
        else:
            os.environ["MO_PLAN_FUSION"] = fusion_was
    s.close()
    # roofline promotion for the fused-join family: analytic bytes over
    # the three tables' touched columns (~56B/lineitem row + the
    # n/4-row dim sides) and ~30 flops/row of join+agg math
    from matrixone_tpu.utils import roofline as _rf
    rf_q3 = (_rf.mfu(n * 30, n * 56 + (n // 4) * 32, 1.0, n / best)
             if best else {})
    return {
        "metric": f"tpch_q3_fused_rows_per_sec_{n}",
        "value": round(best, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "exact_vs_oracle": bool(exact and rows == rows_unfused),
        "fused_dispatches": int(fused_dispatches),
        "fused_dispatches_per_batch": round(fused_dispatches
                                            / n_batches, 2),
        "fused_over_unfused": (round(best / best_unfused, 2)
                               if best_unfused else None),
        "device_cache_hit_rate": dev_tier["hit_rate"],
        "warm_upload_bytes": cache_res["uploaded_bytes"],
        "load_seconds": round(t_load, 2),
        "cold_run_seconds": round(t_cold, 2),
        "object_backed": True,
        "backend": jax.default_backend(),
        **({"roofline": rf_q3} if rf_q3 else {}),
        "extra_metrics": [{
            "metric": f"tpch_q3_rows_per_sec_{n}",
            "value": round(best_unfused, 1),
            "unit": "rows/s",
            "vs_baseline": None,
            "plan_fusion": 0,
            "backend": jax.default_backend(),
        }],
    }


def bench_q3_sharded(n: int = None) -> dict:
    """TPC-H Q3 across the simulated device mesh (parallel/dist_query.py
    shard executor): the same fused fragment compiled per shard over a
    hash/rr-routed scan, partial group tables merged in one traced
    dispatch.  Headline is rows/sec at the widest mesh the box offers,
    with per-shard-count scaling entries (1/2/4/8) as extras — all
    checked bit-identical to the single-device rows.

    On the 1-core CI box the 8 simulated devices SHARE one core, so the
    sharded path pays XLA:CPU collective + per-shard dispatch overhead
    with zero real parallelism and the speedup target is out of reach
    by construction; when speedup < 1.5x the result documents that
    overhead instead, with per-stage motrace attribution
    (shard.partial / shard.merge / shard.broadcast) so the cost is
    visible, not guessed."""
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.utils import motrace, tpch
    if n is None:
        n = int(os.environ.get("MO_BENCH_Q3S_N",
                               40_000 if SMOKE else 400_000))
    eng = Engine()
    s = Session(catalog=eng)
    t0 = time.time()
    tpch.load_lineitem(s.catalog, n)
    tpch.load_tpch_q3(s.catalog, max(n // 4, 100))
    t_load = time.time() - t0
    local = s.execute(tpch.Q3_SQL).rows()
    s.execute("set dist_min_rows = 0")
    # rr scan routing is chunk-granular: carve segments into ~2 chunks
    # per shard so every shard of the widest mesh owns real data
    s.execute(f"set batch_rows = {max(4096, n // 16)}")
    n_dev = len(jax.devices())
    reps = 2 if SMOKE else 3
    per_shard = {}
    for shards in (1, 2, 4, 8):
        if shards > 1 and n_dev < shards:
            continue
        s.execute(f"set query_shards = {shards}")
        rows = s.execute(tpch.Q3_SQL).rows()       # warm: compile path
        exact = rows == local
        best = 0.0
        for _ in range(reps):
            t0 = time.time()
            s.execute(tpch.Q3_SQL)
            best = max(best, n / (time.time() - t0))
        per_shard[shards] = (best, exact)
    widest = max(per_shard)
    best, exact = per_shard[widest]
    speedup = (round(best / per_shard[1][0], 2)
               if per_shard.get(1, (0, 0))[0] else None)
    # ---- per-stage attribution: one traced run at the widest mesh
    was_armed = motrace.TRACER.armed
    motrace.TRACER.arm(sample=1.0)
    try:
        mark = len(motrace.TRACER._ring)
        s.execute(tpch.Q3_SQL)
        stages = {}
        for rec in list(motrace.TRACER._ring)[mark:]:
            if rec["name"].startswith("shard."):
                stages[rec["name"]] = round(
                    stages.get(rec["name"], 0.0)
                    + rec["dur_us"] / 1000.0, 2)
    finally:
        if not was_armed:
            motrace.TRACER.disarm()
    # ---- sharded Q1 on the same lineitem (the other headline shape)
    s.execute("set query_shards = 0")
    q1_local_rows = s.execute(tpch.Q1_SQL).rows()
    t0 = time.time()
    s.execute(tpch.Q1_SQL)
    q1_local = n / (time.time() - t0)
    s.execute(f"set query_shards = {widest}")
    q1_rows = s.execute(tpch.Q1_SQL).rows()        # warm: compile path
    t0 = time.time()
    s.execute(tpch.Q1_SQL)
    q1_best = n / (time.time() - t0)
    s.execute("set query_shards = 0")
    s.close()
    # ---- breadth: Q5/Q9/Q18 (multi-join + shuffle shapes) at the
    # widest mesh, exact vs the sqlite oracle AND vs the local rows
    from matrixone_tpu.utils import tpch_full as TF
    s2 = Session()
    sf = 0.005 if SMOKE else 0.02
    tables = TF.load_tpch(s2.catalog, sf=sf, seed=1)
    conn = TF.to_sqlite(tables)
    n_li = int(len(tables["lineitem"]["l_orderkey"]))
    s2.execute("set dist_min_rows = 0")
    s2.execute(f"set batch_rows = {max(1024, n_li // (2 * widest))}")
    breadth = []
    for qnum in (5, 9, 18):
        sql = TF.QUERIES[qnum]
        local_rows = s2.execute(sql).rows()
        want = conn.execute(TF.to_sqlite_sql(sql)).fetchall()
        oracle_ok = TF.rows_match(TF.normalize_rows(local_rows),
                                  TF.normalize_rows(want))
        t0 = time.time()
        s2.execute(sql)
        t_local = time.time() - t0
        s2.execute(f"set query_shards = {widest}")
        sh_rows = s2.execute(sql).rows()           # warm: compile path
        t0 = time.time()
        s2.execute(sql)
        t_sh = time.time() - t0
        s2.execute("set query_shards = 0")
        breadth.append({
            "metric": f"tpch_q{qnum}_sharded_rows_per_sec_{widest}dev",
            "value": round(n_li / t_sh, 1),
            "unit": "rows/s",
            "vs_baseline": None,
            "local_rows_per_sec": round(n_li / t_local, 1),
            "exact_vs_local": bool(TF.rows_match(
                TF.normalize_rows(sh_rows),
                TF.normalize_rows(local_rows))),
            "exact_vs_oracle": bool(oracle_ok),
            "shards": widest,
            "backend": jax.default_backend(),
        })
    conn.close()
    s2.close()
    return {
        "metric": f"tpch_q3_sharded_rows_per_sec_{n}x{widest}dev",
        "value": round(best, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "exact_vs_local": bool(exact
                               and all(e for _, e in per_shard.values())),
        "shards": widest,
        "sharded_over_local": speedup,
        # the 1-core escape hatch: when < 1.5x, the per-stage spans ARE
        # the documented XLA:CPU collective/dispatch overhead breakdown
        "stage_ms": stages,
        "simulated_devices_share_cores": os.cpu_count(),
        "load_seconds": round(t_load, 2),
        "q1_sharded_rows_per_sec": round(q1_best, 1),
        "q1_local_rows_per_sec": round(q1_local, 1),
        "q1_sharded_over_local": round(q1_best / q1_local, 2),
        "q1_exact_vs_local": q1_rows == q1_local_rows,
        "backend": jax.default_backend(),
        "extra_metrics": [{
            "metric": f"tpch_q3_sharded_rows_per_sec_{n}x{sc}dev",
            "value": round(v, 1),
            "unit": "rows/s",
            "vs_baseline": None,
            "shards": sc,
            "exact_vs_local": bool(e),
            "backend": jax.default_backend(),
        } for sc, (v, e) in sorted(per_shard.items())
            if sc != widest] + breadth,
    }


def bench_mview(n: int = None) -> dict:
    """Materialized-view maintenance: delta apply vs full
    rematerialization on a Q1-shaped view (group by two dict-coded
    dims, SUM/AVG/COUNT over decimals).  The headline is the SPEEDUP of
    applying one 1k-row commit's delta over re-running the defining
    SELECT and rewriting the table — the path every refresh paid before
    matrixone_tpu/mview existed."""
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.utils import metrics as M
    if n is None:
        n = int(os.environ.get("MO_BENCH_N",
                               50_000 if SMOKE else 1_000_000))
    delta_rows = 1000
    reps = 3 if SMOKE else 5
    rng = np.random.default_rng(7)
    eng = Engine()
    s = Session(catalog=eng)
    s.execute("create table mv_src (flag varchar(1), status varchar(1),"
              " qty decimal(12,2), price decimal(12,2))")
    t = eng.get_table("mv_src")
    flags, statuses = ["A", "N", "R"], ["F", "O"]

    def chunk(m):
        return (
            {"qty": rng.integers(100, 10000, m).astype(np.int64),
             "price": rng.integers(100, 1000000, m).astype(np.int64)},
            {"flag": (rng.integers(0, len(flags), m).astype(np.int32),
                      list(flags)),
             "status": (rng.integers(0, len(statuses),
                                     m).astype(np.int32),
                        list(statuses))})
    step = 1 << 19
    for i in range(0, n, step):
        arrays, strings = chunk(min(step, n - i))
        t.insert_numpy(arrays, strings=strings)
    sql = ("select flag, status, sum(qty) sq, avg(price) ap,"
           " count(*) cnt from mv_src group by flag, status")
    t0 = time.time()
    s.execute(f"create materialized view mv_q1 as {sql}")
    t_create = time.time() - t0
    # warm the delta step's compile cache (one trace per view shape —
    # steady-state production cost is what the metric tracks)
    arrays, strings = chunk(delta_rows)
    t.insert_numpy(arrays, strings=strings)
    # ---- delta apply: maintenance seconds around 1k-row commits (the
    # mo_mview_apply_seconds counter brackets exactly the maintenance
    # work: partial eval + state merge + changed-group rewrite)
    d0 = M.mview_apply_seconds.get(kind="delta")
    dense0 = M.mview_apply.get(tier="dense")
    for _ in range(reps):
        arrays, strings = chunk(delta_rows)
        t.insert_numpy(arrays, strings=strings)
    delta_s = (M.mview_apply_seconds.get(kind="delta") - d0) / reps
    dense_applies = M.mview_apply.get(tier="dense") - dense0
    # ---- full rematerialization: the pre-mview refresh path (run the
    # SELECT over the full source, DELETE + INSERT the result)
    from matrixone_tpu.stream import rematerialize
    best_full = None
    for _ in range(2):
        t0 = time.time()
        rematerialize(s, "mv_q1", sql)
        dt_full = time.time() - t0
        best_full = dt_full if best_full is None else min(best_full,
                                                          dt_full)
    rows = s.execute("select * from mv_q1").rows()
    # the metric exists to catch the delta path regressing to full
    # refresh — a run where it never fired must FAIL the floor, not
    # divide by ~zero into a fantastic pass
    from matrixone_tpu.mview import catalog as _vcat
    mode = _vcat.lookup(eng, "mv_q1").mode
    if mode != "incremental" or delta_s <= 0 or dense_applies < reps:
        return {"metric": f"mview_delta_refresh_speedup_{n}",
                "value": 0, "unit": "error", "vs_baseline": None,
                "error": f"delta path did not run (mode={mode}, "
                         f"delta_s={delta_s}, dense={dense_applies})"}
    speedup = best_full / delta_s
    return {
        "metric": f"mview_delta_refresh_speedup_{n}",
        "value": round(speedup, 1),
        "unit": "x",
        "vs_baseline": None,
        "delta_apply_seconds": round(delta_s, 5),
        "full_refresh_seconds": round(best_full, 3),
        "delta_rows": delta_rows,
        "source_rows": n,
        "view_groups": len(rows),
        "dense_applies": int(dense_applies),
        "create_seconds": round(t_create, 2),
        "backend": jax.default_backend(),
    }


def bench_ingest(rounds: int = None, rows_per_round: int = None) -> dict:
    """Sustained ingest under background compaction (the weeks-of-write-
    traffic scenario shrunk to a bench): R commit rounds with a rolling
    delete churn into one table, measured with the merge scheduler OFF
    (segments accumulate unboundedly) vs ON (compaction cycles interleave
    with the ingest, their cost paid inline).  The headline is sustained
    rows/s WITH the scheduler; the off-run's segment count vs the on-
    run's is the read-amplification the scheduler exists to bound, and
    the timed full-table aggregate under both shapes prices it."""
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.storage.engine import Engine
    from matrixone_tpu.storage.fileservice import MemoryFS
    from matrixone_tpu.storage.merge_sched import MergeScheduler
    if rounds is None:
        rounds = int(os.environ.get("MO_BENCH_INGEST_ROUNDS",
                                    24 if SMOKE else 64))
    if rows_per_round is None:
        rows_per_round = int(os.environ.get("MO_BENCH_INGEST_ROWS",
                                            5_000 if SMOKE else 50_000))
    total = rounds * rows_per_round
    churn = max(1, rows_per_round // 8)     # rows retired per 4 rounds

    def run(with_sched: bool) -> dict:
        rng = np.random.default_rng(11)     # identical row streams
        eng = Engine(MemoryFS())
        s = Session(catalog=eng)
        s.execute("create table ing (id bigint, v bigint)")
        t = eng.get_table("ing")
        sched = MergeScheduler(eng)
        cycles = merges = deleted = 0
        base = 0
        t0 = time.time()
        for r in range(rounds):
            ids = np.arange(base, base + rows_per_round, dtype=np.int64)
            base += rows_per_round
            t.insert_numpy(
                {"id": ids,
                 "v": rng.integers(0, 1000, rows_per_round
                                   ).astype(np.int64)})
            if r % 4 == 3:                  # rolling churn window
                s.execute(f"delete from ing where id >= {deleted} and "
                          f"id < {deleted + churn}")
                deleted += churn
            if with_sched and r % 4 == 3:
                summary = sched.run_cycle()
                cycles += 1
                merges += len(summary["merged"])
        wall = time.time() - t0
        if with_sched:                      # drain: final merge + GC
            merges += len(sched.run_cycle()["merged"])
            cycles += 1
        # read amplification: segments a full scan touches, priced by
        # the aggregate every dashboard query pays
        s.execute("select sum(v), count(*) from ing")      # warm/compile
        best_read = None
        for _ in range(3):
            r0 = time.time()
            (sv, cnt), = s.execute(
                "select sum(v), count(*) from ing").rows()
            dt = time.time() - r0
            best_read = dt if best_read is None else min(best_read, dt)
        assert cnt == total - deleted, "ingest lost rows"
        return {"rows_per_sec": total / wall, "segments": len(t.segments),
                "read_seconds": best_read, "merges": merges,
                "cycles": cycles, "deleted": deleted}

    off = run(with_sched=False)
    on = run(with_sched=True)
    if on["merges"] == 0 or on["segments"] >= off["segments"]:
        # the scheduler never compacted: a floor pass at the off-path's
        # shape would guard nothing — fail loudly instead
        return {"metric": f"sustained_ingest_rows_per_sec_{total}",
                "value": 0, "unit": "error", "vs_baseline": None,
                "error": f"scheduler did not compact (merges="
                         f"{on['merges']}, segments {on['segments']} vs "
                         f"{off['segments']} off)"}
    return {
        "metric": f"sustained_ingest_rows_per_sec_{total}",
        "value": round(on["rows_per_sec"], 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "rows_per_sec_sched_on": round(on["rows_per_sec"], 1),
        "rows_per_sec_sched_off": round(off["rows_per_sec"], 1),
        "segments_sched_on": on["segments"],
        "segments_sched_off": off["segments"],
        "read_amplification": round(off["segments"] / on["segments"], 1),
        "read_seconds_sched_on": round(on["read_seconds"], 4),
        "read_seconds_sched_off": round(off["read_seconds"], 4),
        "merge_cycles": on["cycles"],
        "merges": on["merges"],
        "rounds": rounds,
        "rows_per_round": rows_per_round,
        "deleted_rows": on["deleted"],
        "backend": jax.default_backend(),
    }


def bench_serving(s, n: int) -> dict:
    """Serving-layer hot path: a repeated parameterized point query plus
    the Q1 shape, cold (caches off) vs warm (plan + result cache on),
    with the cache hit rates that explain the ratio. Reuses bench_q1's
    loaded lineitem session so the workload is the object-backed path."""
    from matrixone_tpu.serving import serving_for
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.utils import tpch

    sv = serving_for(s.catalog)
    point = ("select count(*), sum(l_quantity) from lineitem"
             " where l_orderkey = ?")
    keys = [1 + 8 * i for i in range(8)]        # 8 distinct params
    n_rounds = 4 if SMOKE else 5

    def one_pass():
        for k in keys:
            s.execute(point, [k])
        s.execute(tpch.Q1_SQL)

    stmts_per_pass = len(keys) + 1

    plan_was = sv.plan_cache.enabled
    mb_was = sv.result_cache.max_bytes
    try:
        # ---- cold: serving caches off, every execution pays full price
        sv.plan_cache.enabled = False
        sv.result_cache.max_bytes = 0
        sv.clear()
        one_pass()                              # compile warm-up
        t0 = time.time()
        for _ in range(n_rounds):
            one_pass()
        cold_qps = n_rounds * stmts_per_pass / (time.time() - t0)

        # ---- plan-only: isolates the bind/optimize savings (a result
        # hit would short-circuit the plan lookup and zero its hit rate)
        sv.plan_cache.enabled = True
        sv.result_cache.max_bytes = 0
        sv.clear()
        one_pass()                              # note templates
        one_pass()                              # activate + store
        h0p = M.plan_cache_ops.get(outcome="hit")
        m0p = M.plan_cache_ops.get(outcome="miss")
        t0 = time.time()
        for _ in range(n_rounds):
            one_pass()
        plan_qps = n_rounds * stmts_per_pass / (time.time() - t0)
        ph = M.plan_cache_ops.get(outcome="hit") - h0p
        pm = M.plan_cache_ops.get(outcome="miss") - m0p

        # ---- warm: both caches on; first pass populates, then measure
        sv.result_cache.max_bytes = 256 << 20
        one_pass()                              # populate results
        h0 = M.result_cache_ops.get(outcome="hit")
        m0 = (M.result_cache_ops.get(outcome="miss")
              + M.result_cache_ops.get(outcome="stale"))
        q_before = M.query_seconds.snapshot()
        t0 = time.time()
        for _ in range(n_rounds):
            one_pass()
        warm_qps = n_rounds * stmts_per_pass / (time.time() - t0)
        rh = M.result_cache_ops.get(outcome="hit") - h0
        rm = (M.result_cache_ops.get(outcome="miss")
              + M.result_cache_ops.get(outcome="stale") - m0)
        # statement-latency percentiles of the WARM loop only, via the
        # registry's public snapshot delta API (utils/metrics.py) —
        # never by poking histogram internals, and never polluted by
        # the process's earlier Q1/load history (same delta discipline
        # as the h0/m0 cache counters above)
        q_after = M.query_seconds.snapshot()
        p50 = M.histogram_delta_quantile(q_before, q_after, 0.50)
        p99 = M.histogram_delta_quantile(q_before, q_after, 0.99)
        q_count = q_after["count"] - q_before["count"]
    finally:
        # restore the caller's configuration even when a pass raises (a
        # deployment-enabled result cache must survive the bench)
        sv.plan_cache.enabled = plan_was
        sv.result_cache.max_bytes = mb_was
        sv.clear()
    return {
        "metric": "serving_hot_qps",
        "value": round(warm_qps, 1),
        "unit": "qps",
        "vs_baseline": None,
        "cold_qps": round(cold_qps, 2),
        "plan_only_qps": round(plan_qps, 2),
        "warm_over_cold": round(warm_qps / cold_qps, 1) if cold_qps else None,
        "result_cache_hit_rate": round(rh / (rh + rm), 4) if rh + rm else 0,
        "plan_cache_hit_rate": round(ph / (ph + pm), 4) if ph + pm else 0,
        "query_p50_s": p50,
        "query_p99_s": p99,
        "query_observations": int(q_count),
        "statements": int((3 * n_rounds + 4) * stmts_per_pass),
        "rows": n,
        "backend": jax.default_backend(),
    }


def bench_udf(n: int = None) -> dict:
    """Python/JAX UDF subsystem: a scalar arithmetic UDF over an n-row
    DOUBLE column through the full SQL engine, jit tier vs row-loop tier
    (matrixone_tpu/udf).  The query aggregates the UDF output
    (sum(f(x))) so the measurement is scan + UDF + reduce on device, not
    a host materialization of n rows.

    The row tier runs the SAME body per row in Python — measured on a
    smaller slice (its rows/s is scale-free) so the bench stays bounded.
    `jit_over_row` is the rows/s ratio; the acceptance bar is >= 50x at
    1M rows."""
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.udf.executor import COMPILE_CACHE
    if n is None:
        n = int(os.environ.get("MO_BENCH_UDF_N",
                               50_000 if SMOKE else 1_000_000))
    n_row = min(n, int(os.environ.get("MO_BENCH_UDF_ROW_N", 50_000)))
    s = Session()
    s.execute("create table udf_bench (x double)")
    t = s.catalog.get_table("udf_bench")
    xs = np.random.default_rng(7).normal(size=n)
    t.insert_numpy({"x": xs})
    s.execute("create table udf_bench_small (x double)")
    s.catalog.get_table("udf_bench_small").insert_numpy(
        {"x": xs[:n_row]})
    s.execute("create function bench_fma(x DOUBLE) returns DOUBLE "
              "language python as $$ x * 1.0000001 + 0.5 $$")
    q = "select sum(bench_fma(x)) from udf_bench"
    q_small = "select sum(bench_fma(x)) from udf_bench_small"

    jit_was = os.environ.get("MO_UDF_JIT")
    try:
        # ---- jit tier (the subsystem's reason to exist)
        os.environ["MO_UDF_JIT"] = "1"
        COMPILE_CACHE.clear()
        s.execute(q)                         # compile + warm
        best = 0.0
        # a jit rep is only ~20-40ms at 1M rows, so a single scheduler
        # hiccup halves one sample: best-of-7 keeps the headline from
        # under-reporting on a loaded box (adds ~0.2s total)
        for _ in range(7):
            t0 = time.time()
            s.execute(q)
            best = max(best, n / (time.time() - t0))
        jit_qps = best / n                    # queries/s at this shape

        # ---- row tier (the correctness fallback, deliberately slow)
        os.environ["MO_UDF_JIT"] = "0"
        s.execute(q_small)                   # warm the scan path
        row_rps = 0.0
        for _ in range(2):                   # best-of, same as the jit
            t0 = time.time()                 # tier: its BEST honestly
            s.execute(q_small)               # shrinks the ratio
            row_rps = max(row_rps, n_row / (time.time() - t0))
    finally:
        if jit_was is None:
            os.environ.pop("MO_UDF_JIT", None)
        else:
            os.environ["MO_UDF_JIT"] = jit_was
    return {
        "metric": f"udf_qps_{n}",
        "value": round(best, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "jit_rows_per_sec": round(best, 1),
        "row_rows_per_sec": round(row_rps, 1),
        "jit_over_row": round(best / row_rps, 1) if row_rps else None,
        "jit_queries_per_sec": round(jit_qps, 2),
        "rows": n,
        "row_tier_rows": n_row,
        "backend": jax.default_backend(),
    }


def _require_chip():
    """A measurement run needs the accelerator: without one this exits
    non-zero and prints no result.  MO_BENCH_SMOKE=1 is the CPU
    correctness run (tiny shapes, no rate is a device number)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not SMOKE:
        sys.exit(f"bench.py: no TPU (jax reports platform "
                 f"{dev.platform!r}); MO_BENCH_SMOKE=1 runs the CPU "
                 f"correctness check")


def main():
    _require_chip()
    if METRIC == "q1":
        print(json.dumps(bench_q1()))
        return
    if METRIC == "q3":
        print(json.dumps(bench_q3()))
        return
    if METRIC == "q3s":
        print(json.dumps(bench_q3_sharded()))
        return
    if METRIC == "mview":
        print(json.dumps(bench_mview()))
        return
    if METRIC == "ingest":
        print(json.dumps(bench_ingest()))
        return
    key = jax.random.PRNGKey(1234)
    t0 = time.time()
    data, queries = make_data(key, N, D)
    jax.block_until_ready(data)
    t_data = time.time() - t0

    # ---- build
    from matrixone_tpu.utils import metrics as MM
    from matrixone_tpu.vectorindex import ivf_pq
    # bf16 storage/compute halves HBM traffic and doubles MXU rate on
    # the chip; the CPU smoke keeps f32 (XLA:CPU has no native bf16 and
    # pays an upcast pass over every gathered candidate tile)
    storage_dtype = None if SMOKE else jnp.bfloat16
    compute_dtype = jnp.float32 if SMOKE else jnp.bfloat16
    t0 = time.time()
    if INDEX_KIND == "ivfpq":
        from matrixone_tpu.indexing import _pick_subspaces
        index = ivf_pq.build(data, nlist=NLIST,
                             n_subspaces=_pick_subspaces(D),
                             n_iter=10, balance_weight=0.3,
                             kmeans_sample=min(N, 262144),
                             compute_dtype=jnp.bfloat16)
        jax.block_until_ready(index.codes)
    else:
        # split-balanced build: minibatch Lloyd + local splitting of
        # oversized lists (see kmeans.split_oversized) — both the
        # build_seconds and the search gather budget levers. 6 minibatch
        # iterations: recall@20 is flat (~0.88) from 6 to 10 iters at
        # these shapes because the split stage absorbs residual
        # imbalance
        index = ivf_flat.build(data, nlist=NLIST, n_iter=6,
                               storage_dtype=storage_dtype,
                               balance_weight=0.3,
                               kmeans_sample=min(N, 262144),
                               kmeans_minibatch=65536,
                               balance_mode="split",
                               compute_dtype=jnp.bfloat16)
        jax.block_until_ready(index.vectors)
    t_build = time.time() - t0
    build_stages = {
        s: round(MM.vector_build_seconds.get(stage=s), 2)
        for s in ("kmeans", "assign", "pack")}
    search_fn = ivf_pq.search if INDEX_KIND == "ivfpq" else ivf_flat.search

    # ---- ground truth: exact f32 at HIGHEST matmul precision (bf16 truth
    # would bias the recall measurement)
    chunk = 8192 if SMOKE else 65536
    padded, n_real = brute_force.pad_dataset(data, chunk_size=chunk)
    truth_batches = []
    for i in range(0, NQ, BATCH):
        _, tidx = brute_force.search(padded, queries[i:i + BATCH], k=K,
                                     n_valid=n_real, chunk_size=chunk,
                                     compute_dtype=None)
        truth_batches.append(np.asarray(tidx))
    truth = np.concatenate(truth_batches)
    # raw dataset + padded copy are dead weight from here (the index holds
    # its own residual-encoded storage) — free ~6 GB of HBM before search
    del padded, truth_batches, data

    # ---- search: warmup (compile) then timed
    def run_all():
        outs = []
        for i in range(0, NQ, BATCH):
            _, ids = search_fn(index, queries[i:i + BATCH], k=K,
                               nprobe=NPROBE, query_chunk=QUERY_CHUNK,
                               compute_dtype=compute_dtype)
            outs.append(ids)
        jax.block_until_ready(outs[-1])
        return outs

    outs = run_all()  # compile + first measure of recall
    found = np.concatenate([np.asarray(o) for o in outs])
    rec = recall_at_k(found, truth)

    best_qps = 0.0
    for _ in range(3):
        t0 = time.time()
        run_all()
        dt = time.time() - t0
        best_qps = max(best_qps, NQ / dt)

    # per-stage attribution (probe/score/merge): a diagnostic staged
    # re-execution of one batch with a device sync between stages —
    # fills mo_vector_search_seconds and the JSON breakdown below
    search_stages = prof = None
    sidx = s_outs = s_found = None
    if INDEX_KIND == "ivfflat":
        prof = ivf_flat.search_profiled(index, queries[:BATCH], k=K,
                                        nprobe=NPROBE,
                                        query_chunk=QUERY_CHUNK,
                                        compute_dtype=compute_dtype)
        search_stages = {s: round(prof[f"{s}_seconds"], 4)
                         for s in ("probe", "score", "merge")}

    # ---- multichip: cluster-sharded serving over the device mesh
    # (vectorindex/sharded.py). Only measured when the backend exposes
    # >1 device — virtual host devices share the same cores, so a CPU
    # "mesh" measures overhead, not scaling.
    multichip = None
    if INDEX_KIND == "ivfflat" and len(jax.devices()) > 1:
        try:
            from matrixone_tpu.parallel.mesh import make_mesh
            from matrixone_tpu.vectorindex import sharded as shmod
            n_dev = len(jax.devices())
            sidx = shmod.shard_ivf(index, make_mesh(n_dev))

            def run_sharded():
                outs = []
                for i in range(0, NQ, BATCH):
                    _, ids = shmod.search_sharded(
                        sidx, queries[i:i + BATCH], k=K, nprobe=NPROBE,
                        query_chunk=QUERY_CHUNK,
                        compute_dtype=compute_dtype)
                    outs.append(ids)
                jax.block_until_ready(outs[-1])
                return outs

            s_outs = run_sharded()
            s_found = np.concatenate([np.asarray(o) for o in s_outs])
            s_qps = 0.0
            for _ in range(3):
                t0 = time.time()
                run_sharded()
                s_qps = max(s_qps, NQ / (time.time() - t0))
            multichip = {
                "metric": f"ivfflat_sharded_qps_{N}x{D}_top{K}"
                          f"_nprobe{NPROBE}x{n_dev}dev",
                "value": round(s_qps, 1),
                "unit": "qps",
                "vs_baseline": None,
                "devices": n_dev,
                "recall_at_20": round(recall_at_k(s_found, truth), 4),
                "shard_imbalance": round(
                    MM.vector_shard_imbalance.get(), 3),
            }
        except Exception as e:               # noqa: BLE001
            multichip = {"metric": "ivfflat_sharded_qps", "value": 0,
                         "unit": "error", "vs_baseline": None,
                         "error": f"{type(e).__name__}: {e}"}

    # vs_baseline only when the config actually matches the published
    # baseline (IVF-Flat, 1M x 768, chip run)
    comparable = (INDEX_KIND == "ivfflat" and N == 1_000_000 and D == 768
                  and not SMOKE)
    result = {
        "metric": f"{INDEX_KIND}_search_qps_{N}x{D}_top{K}_nprobe{NPROBE}",
        "value": round(best_qps, 1),
        "unit": "qps",
        "vs_baseline": (round(best_qps / BASELINE_QPS, 2)
                        if comparable else None),
        "recall_at_20": round(rec, 4),
        "build_seconds": round(t_build, 2),
        "build_stages": build_stages,
        "data_seconds": round(t_data, 2),
        "backend": jax.default_backend(),
        "batch": BATCH,
        "query_chunk": QUERY_CHUNK,
    }
    if search_stages:
        result["search_stages"] = search_stages
    if multichip:
        result.setdefault("extra_metrics", []).append(multichip)
    # roofline evidence (VERDICT r4 #1b): XLA's own FLOPs/bytes for the
    # search step + achieved rates and MFU/HBM utilization vs chip peak
    import functools as _ft
    from matrixone_tpu.utils import roofline
    rf = roofline.report(
        _ft.partial(search_fn, k=K, nprobe=NPROBE,
                    query_chunk=QUERY_CHUNK, compute_dtype=compute_dtype),
        (index, queries[:BATCH]),
        calls=NQ / BATCH, seconds=NQ / best_qps)
    if rf:
        result["roofline"] = rf
    # second trend line (VERDICT r3 #7: the scoreboard must trend with
    # >=2 comparable metrics): TPC-H Q1 rows/s rides in the SAME JSON
    # line so the one-line driver contract holds.  The already-measured
    # IVF number must survive a Q1 that hangs (not raises), so Q1 runs
    # under a watchdog thread with a deadline — on timeout the combined
    # line still prints with an error entry.
    if os.environ.get("MO_BENCH_NO_Q1") != "1":
        # free the index/query HBM before loading lineitem: the chip has
        # ~16 GB and a resident 1M x 768 index + 6M-row table can OOM
        del index, outs, queries, truth, found
        sidx = s_outs = s_found = prof = None  # noqa: F841 (drop HBM refs)
        q1_n = 50_000 if SMOKE else 6_001_215
        box = []

        def _q1():
            try:
                box.append(bench_q1(q1_n))
            except Exception as e:           # noqa: BLE001
                box.append({
                    "metric": "tpch_q1_rows_per_sec", "value": 0,
                    "unit": "error", "vs_baseline": None,
                    "error": f"{type(e).__name__}: {e}"})
        t = threading.Thread(target=_q1, daemon=True)
        t.start()
        t.join(float(os.environ.get("MO_BENCH_Q1_TIMEOUT_S", 1200)))
        q1_entry = box[0] if box else {
            "metric": "tpch_q1_rows_per_sec", "value": 0,
            "unit": "error", "vs_baseline": None,
            "error": "q1 timed out (device wedge?)"}
        # hoist nested extras (serving_hot_qps rides inside bench_q1) so
        # every metric is a top-level extra_metrics entry for the driver
        nested = q1_entry.pop("extra_metrics", None) if box else None
        result.setdefault("extra_metrics", []).append(q1_entry)
        if nested:
            result["extra_metrics"].extend(nested)
    print(json.dumps(result))
    sys.stdout.flush()
    if os.environ.get("MO_BENCH_NO_Q1") != "1" and not box:
        os._exit(0)       # q1 thread is wedged on the device: don't hang


if __name__ == "__main__":
    main()
