"""Physical operators: host-driven loops over device batches.

Reference analogue: `pkg/sql/colexec` operator packages + the `vm.Operator`
pull loop (`vm/pipeline/pipeline.go:62`). Differences by design:

  * operators yield ExecBatch (device arrays + mask) — filters produce
    masks, not compacted rows, so filter+project+aggregate fuse into a
    handful of XLA executables per batch instead of per-operator loops;
  * group-by is the sort/segment kernel (ops.agg) with *streaming partial
    merge*: each batch folds into a bounded device-resident group table
    (the reference's agg hash table, re-expressed);
  * sort/top-k materialize through concat + argsort/top_k — XLA-native.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import time
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container import dtypes as dt
from matrixone_tpu.container.device import (DeviceBatch, DeviceColumn,
                                            bucket_length)
from matrixone_tpu.container.dtypes import DType, TypeOid
from matrixone_tpu.ops import agg as A, filter as F, sort as msort
from matrixone_tpu.sql import plan as P
from matrixone_tpu.sql.expr import AggCall, BoundCol, BoundExpr
from matrixone_tpu.utils import metrics as M, motrace
from matrixone_tpu.vm.exprs import EvalError, ExecBatch, eval_expr


class Operator:
    #: the span each step of `execute()` runs under (`motrace.steps`):
    #: `op.<kind>`, or None for an operator that only hands back batches
    #: it was given
    step_span: Optional[str] = None

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        own = cls.__dict__.get("execute")
        if own is not None:
            cls.execute = _stepped(own)

    def execute(self) -> Iterator[ExecBatch]:
        raise NotImplementedError

    schema: List


def _stepped(execute):
    """A class's own `execute`, its steps under the operator's span.  A
    subclass's `execute` that calls this one through `super()` opens the
    spans alone: only the operator's own class's wrapper does."""
    @functools.wraps(execute)
    def stepped(self):
        if not motrace.TRACER.armed or type(self).execute is not stepped:
            return execute(self)
        return motrace.steps(self.step_span, execute(self))
    return stepped


# ------------------------------------------------------------------- scan

def chunk_to_execbatch(arrays, validity, table_dicts, n, columns, schema,
                       live=None) -> ExecBatch:
    """Host chunk -> padded device ExecBatch, renaming raw table columns to
    the plan's qualified names and tagging varlen columns (used by ScanOp
    and the vector-index scan).  `live` (host bool [n], False for a
    tombstoned row) becomes the batch's row mask: one bool upload at the
    bucket's length, so every program downstream keeps the chunk's shape
    whatever was deleted from it."""
    from matrixone_tpu.container import device as dev
    from matrixone_tpu.ops import encodings as ENC
    qnames = [nm for nm, _ in schema]
    arr2, val2, dicts2, dtypes = {}, {}, {}, {}
    for qn, col, dtype in zip(qnames, columns, [d for _, d in schema]):
        arr2[qn] = arrays[col]
        val2[qn] = validity[col]
        dtypes[qn] = dt.INT32 if dtype.is_varlen else dtype
        if col in table_dicts:
            dicts2[qn] = table_dicts[col]
            # narrow dict codes to the smallest signed width the
            # dictionary fits (lossless — hash/compare/gather are
            # width-invariant); from_numpy preserves the narrow dtype
            arr2[qn] = ENC.narrow_codes(arr2[qn], len(table_dicts[col]))
    db = dev.from_numpy(arr2, dtypes, val2, n_rows=n)
    for qn, (_, dtype) in zip(qnames, schema):
        if dtype.is_varlen:
            c = db.columns[qn]
            db.columns[qn] = DeviceColumn(c.data, c.validity, dtype)
    if live is None:
        mask = db.row_mask()
    else:
        padded = np.zeros(db.padded_len, np.bool_)
        padded[:n] = live
        mask = jnp.asarray(padded)
    return ExecBatch(batch=db, dicts=dicts2, mask=mask)


class _ChunkPrefetcher:
    """Bounded read-ahead over a chunk iterator (reference: the CN
    reader's merged-IO pipelining, `pkg/fileservice/io_merger.go` role).

    A worker thread pulls chunk N+1 — which for object-backed segments
    triggers the column fetch + decode through the blockcache — while
    the consumer's filter/agg compute runs over chunk N, so cold-read IO
    overlaps device compute. Exceptions propagate to the consumer;
    closing stops the worker and closes the source generator.  The
    worker runs under the creator's trace context (`motrace.bind`), so
    what it reads, decodes and uploads lands in the statement's trace."""

    _DONE, _ITEM, _ERR = 0, 1, 2

    def __init__(self, gen, depth: int):
        import queue
        self._q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=motrace.bind(self._run), args=(gen,), daemon=True,
            name="mo-scan-prefetch")
        self._thread.start()

    def _run(self, gen) -> None:
        import queue
        try:
            for item in gen:
                while True:
                    if self._stop.is_set():
                        gen.close()
                        return
                    try:
                        self._q.put((self._ITEM, item), timeout=0.1)
                        break
                    except queue.Full:
                        continue
            self._q.put((self._DONE, None))
        except BaseException as e:                    # noqa: BLE001
            # deliver the error with the same patience as items: a full
            # queue must never swallow it (the consumer would block on
            # get() forever with no DONE sentinel)
            import queue
            while not self._stop.is_set():
                try:
                    self._q.put((self._ERR, e), timeout=0.1)
                    return
                except queue.Full:
                    continue

    def __iter__(self):
        while True:
            ready = not self._q.empty()
            if ready:
                kind, payload = self._q.get()
            else:
                t0 = time.perf_counter()
                with motrace.span("scan.wait"):
                    kind, payload = self._q.get()
            if kind == self._DONE:
                return
            if kind == self._ERR:
                raise payload
            M.scan_prefetch.inc(outcome="ready" if ready else "waited")
            if not ready:
                M.scan_prefetch_wait_seconds.inc(
                    time.perf_counter() - t0)
            yield payload

    def close(self) -> None:
        self._stop.set()
        while not self._q.empty():     # unblock a Full worker
            try:
                self._q.get_nowait()
            except Exception:                         # noqa: BLE001
                break
        # the worker notices _stop within its 0.1s put tick; join it
        # with a deadline instead of abandoning it (mosan leak checker)
        self._thread.join(timeout=5)


class ScanOp(Operator):
    """Table scan with filter pushdown + zonemap chunk pruning
    (reference: colexec/table_scan + readutil block pruning), plus a
    read-ahead stage decoding chunk N+1 while chunk N computes
    (MO_SCAN_PREFETCH chunks deep; 0 disables)."""

    step_span = "op.scan"

    def __init__(self, node: P.Scan, relation, batch_rows: int = 1 << 20,
                 ctx=None):
        self.node = node
        self.rel = relation
        self.batch_rows = batch_rows
        self.schema = node.schema
        self.ctx = ctx
        # filters injected at run time by upstream joins (build-side key
        # ranges — reference: vm/message/runtimeFilterMsg.go); they ride
        # the same zonemap-pruning + early-mask path as planned filters
        self.runtime_filters: List[BoundExpr] = []

    def execute(self) -> Iterator[ExecBatch]:
        return self._batches(apply_mask=True)

    def _batches(self, apply_mask: bool = True) -> Iterator[ExecBatch]:
        """Chunk iterator.  With apply_mask=False the pushed filters are
        still handed to iter_chunks (zonemap pruning) but NOT evaluated
        as an early row mask — a fused fragment (vm/fusion.py) folds
        them into its single traced program instead."""
        from matrixone_tpu.utils.fault import INJECTOR
        INJECTOR.trigger("scan.before")
        qnames = [n for n, _ in self.node.schema]
        meta = getattr(self.rel, "meta", None)
        M.scan_columns.inc(len(self.node.columns), outcome="read")
        if meta is not None:
            M.scan_columns.inc(
                max(len(meta.schema) - len(self.node.columns), 0),
                outcome="pruned")
        read_args = (self.ctx.table_read_args(self.node.table)
                     if self.ctx is not None else {})
        if self.node.as_of_ts is not None:
            # time travel: a historical read, independent of the txn view
            read_args = {"snapshot_ts": self.node.as_of_ts}
        filters = self.node.filters + self.runtime_filters
        batch_rows = self.batch_rows
        if self.ctx is not None and self.ctx.variables:
            batch_rows = int(self.ctx.variables.get("batch_rows",
                                                    batch_rows))
        shard = self.node.shard
        hs = self.node.hash_shard
        hs_aligned = False
        if hs is not None:
            # read-side hash exchange (colexec/shuffle as a route, not a
            # send): when the table is hash-partitioned on the shuffle
            # column with the same fan-out, matching segments are
            # selected structurally (only_part) and no row moves; the
            # row-level mask below stays on as the correctness backstop
            # for any segment without a part id
            pspec = getattr(meta, "partition", None) \
                if meta is not None else None
            hs_aligned = (pspec is not None and pspec.kind == "hash"
                          and pspec.column == hs[0]
                          and pspec.n_parts == hs[2])
            if hs_aligned:
                read_args = dict(read_args)
                read_args["only_part"] = hs[1]
        chunks = self.rel.iter_chunks(
            self.node.columns, batch_rows, filters=filters,
            qualified_names=qnames, **read_args)
        # read-ahead: ON for scans that will actually fetch+decode cold
        # object blocks (IO to overlap with compute); OFF for warm scans
        # where a handoff thread is pure overhead. MO_SCAN_PREFETCH
        # forces a depth (0 disables).
        env_depth = os.environ.get("MO_SCAN_PREFETCH")
        try:
            depth = int(env_depth)          # explicit depth (0 = off)
        except (TypeError, ValueError):     # unset / "auto": cold-only
            is_cold = getattr(self.rel, "scan_is_cold", None)
            depth = 2 if (is_cold is not None
                          and is_cold(self.node.columns)) else 0
        prefetcher = None
        if depth > 0:
            prefetcher = _ChunkPrefetcher(chunks, depth)
            chunks = iter(prefetcher)
        try:
            for ci, chunk in enumerate(chunks):
                if shard is not None and ci % shard[1] != shard[0]:
                    # distributed scan: peers cover disjoint chunk
                    # strides of the SAME deterministic chunk sequence
                    # (same snapshot, same filters -> same pruning on
                    # every replica)
                    continue
                arrays, validity, dicts, n, live = chunk
                if hs is not None:
                    arrays, validity, n, moved = _hash_route(
                        arrays, validity, n, hs, hs_aligned, live)
                    live = None
                    if n == 0:
                        continue
                    if moved:
                        M.exchange_shuffle_rows.inc(moved)
                M.rows_scanned.inc(n if live is None else int(live.sum()),
                                   table=self.node.table)
                with motrace.span("scan.batch", rows=n):
                    ex = chunk_to_execbatch(arrays, validity, dicts, n,
                                            self.node.columns,
                                            self.node.schema, live)
                    # evaluate pushed filters as an early mask (zonemap
                    # pruning already dropped fully-excluded chunks
                    # host-side)
                    if apply_mask:
                        for f in filters:
                            pred = eval_expr(f, ex)
                            ex.mask = ex.mask & F.predicate_mask(
                                pred, ex.batch)
                yield ex
        finally:
            if prefetcher is not None:
                prefetcher.close()


def _hash_route(arrays, validity, n: int, hs, aligned: bool, live=None):
    """Keep only the live rows this shard owns under the hash exchange
    `hash_shard=(column, idx, n_shards)` (`live`: the chunk's tombstone
    mask, None where no row is dead).  Routing is splitmix64 % n with
    NULL -> shard 0 — bit-identical to the commit pipeline's
    storage.partition.assign_partitions, so a partitioned table and an
    implicit repartition agree on every row's home.  Returns
    (arrays, validity, n_kept, n_moved); n_moved counts rows that
    crossed the exchange (0 when the segment selection was structural —
    a co-partitioned read moves nothing)."""
    from matrixone_tpu.storage import partition as partmod
    col, idx, n_shards = hs
    key = arrays.get(col)
    if key is None:
        raise EvalError(f"hash_shard column {col!r} not in scan columns")
    key = np.asarray(key)
    if not np.issubdtype(key.dtype, np.integer):
        raise EvalError(
            f"hash_shard column {col!r} must be int-backed, "
            f"got {key.dtype}")
    v = validity.get(col)
    valid = (np.asarray(v, bool) if v is not None
             else np.ones(n, np.bool_))
    pid = np.where(valid,
                   (partmod._hash64(key.astype(np.int64))
                    % np.uint64(n_shards)).astype(np.int64), 0)
    keep = pid == idx
    if live is not None:
        keep &= live
    kept = int(keep.sum())
    moved = 0 if aligned else kept
    if kept == n:
        return arrays, validity, n, moved
    arrays = {c: a[keep] for c, a in arrays.items()}
    validity = {c: (np.asarray(vv)[keep] if vv is not None else None)
                for c, vv in validity.items()}
    return arrays, validity, kept, moved


class MaterializedOp(Operator):
    """Host arrays as a plan input (P.Materialized): the coordinator's
    merged fragment results re-enter the local operator tree here."""

    step_span = "op.materialized"

    def __init__(self, node):
        self.node = node
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        arrays, validity, dicts = {}, {}, {}
        n = None
        for name, dtype in self.node.schema:
            a = self.node.arrays[name]
            if dtype.is_varlen and name in self.node.dicts:
                arrays[name] = np.asarray(a, np.int32)
                dicts[name] = self.node.dicts[name]
            elif dtype.is_varlen and isinstance(a, list):
                d: List[str] = []
                lut: Dict[str, int] = {}
                codes = np.zeros(len(a), np.int32)
                for i, s_ in enumerate(a):
                    if s_ is None:
                        continue
                    code = lut.get(s_)
                    if code is None:
                        code = len(d)
                        lut[s_] = code
                        d.append(s_)
                    codes[i] = code
                arrays[name] = codes
                dicts[name] = d
            else:
                arrays[name] = np.asarray(a)
            v = self.node.validity.get(name)
            validity[name] = (np.asarray(v, bool) if v is not None
                              else np.ones(len(arrays[name]), np.bool_))
            n = len(arrays[name])
        if n is None or n == 0:
            return
        yield chunk_to_execbatch(arrays, validity, dicts, n,
                                 [c for c, _ in self.node.schema],
                                 self.node.schema)


class ValuesOp(Operator):
    step_span = "op.values"

    def __init__(self, node: P.Values):
        self.node = node
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        from matrixone_tpu.container import device as dev
        arrays, dtypes = {}, {}
        for i, (name, dtype) in enumerate(self.node.schema):
            vals = [row[i] for row in self.node.rows]
            arrays[name] = np.asarray(vals, dtype=dtype.np_dtype)
            dtypes[name] = dtype
        db = dev.from_numpy(arrays, dtypes, n_rows=len(self.node.rows))
        yield ExecBatch(batch=db, dicts={}, mask=db.row_mask())


# ----------------------------------------------------------------- filter

class FilterOp(Operator):
    step_span = "op.filter"

    def __init__(self, node: P.Filter, child: Operator):
        self.node = node
        self.child = child
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        for ex in self.child.execute():
            pred = eval_expr(self.node.pred, ex)
            ex.mask = ex.mask & F.predicate_mask(pred, ex.batch)
            yield ex


class ProjectOp(Operator):
    step_span = "op.project"

    def __init__(self, node: P.Project, child: Operator):
        self.node = node
        self.child = child
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        for ex in self.child.execute():
            cols: Dict[str, DeviceColumn] = {}
            dicts: Dict[str, List[str]] = {}
            for (name, dtype), e in zip(self.node.schema, self.node.exprs):
                col = eval_expr(e, ex)
                cols[name] = col
                src_dict = _expr_dict(e, ex)
                if src_dict is not None:
                    dicts[name] = src_dict
            db = DeviceBatch(columns=cols, n_rows=ex.batch.n_rows)
            yield ExecBatch(batch=db, dicts=dicts, mask=ex.mask)


def _expr_dict(e: BoundExpr, ex: ExecBatch):
    from matrixone_tpu.sql.expr import BoundLiteral
    from matrixone_tpu.vm.exprs import _dict_of
    if isinstance(e, BoundLiteral) and e.dtype.is_varlen:
        return [str(e.value)]
    return _dict_of(e, ex)


class UdfAggregateOp(Operator):
    """Whole-relation aggregate UDFs (plan.UdfAggregate): compact every
    call's argument columns host-side (filter mask AND arg validity —
    NULL-in-any-argument rows are skipped, matching builtin aggregate
    NULL semantics) and run each body ONCE over the concatenated
    arrays. One output row."""

    step_span = "op.udf_aggregate"

    def __init__(self, node: "P.UdfAggregate", child: Operator):
        self.node = node
        self.child = child
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        from matrixone_tpu.udf.executor import (_broadcast,
                                                eval_udf_aggregate)
        parts: List[List[list]] = [[[] for _ in c.args]
                                   for c in self.node.calls]
        for ex in self.child.execute():
            n = ex.padded_len
            for ci, call in enumerate(self.node.calls):
                cols = [eval_expr(a, ex) for a in call.args]
                keep = ex.mask
                datas = []
                for col in cols:
                    datas.append(_broadcast(col.data, n))
                    keep = keep & _broadcast(col.validity, n)
                km = np.asarray(jax.device_get(keep))
                for ai, d in enumerate(datas):
                    arr = np.asarray(jax.device_get(d))[km]
                    if len(arr):
                        parts[ci][ai].append(arr)
        cols_out: Dict[str, DeviceColumn] = {}
        for ci, call in enumerate(self.node.calls):
            arrays = [np.concatenate(p) if p
                      else np.zeros(0, call.arg_types[ai].np_dtype)
                      for ai, p in enumerate(parts[ci])]
            v = eval_udf_aggregate(call, arrays)
            name, dtype = self.schema[ci]
            cols_out[name] = (DeviceColumn.const_null(dtype) if v is None
                              else DeviceColumn.const(v, dtype))
        db = DeviceBatch(columns=cols_out, n_rows=1)
        yield ExecBatch(batch=db, dicts={},
                        mask=jnp.ones((1,), jnp.bool_))


# -------------------------------------------------------------- aggregate

class _NeedSpill(Exception):
    """Internal: the group table outgrew the device budget mid-stream."""


class _AggSpill:
    """Grace-hash spill for group-by (reference: colexec/spillutil +
    spill_threshold.go, re-expressed host-side): when the group table
    would outgrow the device budget, incoming rows AND the current partial
    state are hash-partitioned by group key and parked as npz chunks in a
    temp dir; each partition is then aggregated independently — its group
    table is ~1/P of the total, and partitions have disjoint key sets so
    results stream out per partition."""

    def __init__(self, n_partitions: int = 16):
        import tempfile
        self.P = n_partitions
        self.dir = tempfile.mkdtemp(prefix="mo_agg_spill_")
        self.raw_chunks: List[List[str]] = [[] for _ in range(self.P)]
        self.state_chunks: List[List[str]] = [[] for _ in range(self.P)]
        self._seq = 0

    def _path(self) -> str:
        import os
        self._seq += 1
        return os.path.join(self.dir, f"c{self._seq}.npz")

    def _partitions(self, kdata, kvalid) -> np.ndarray:
        from matrixone_tpu.ops import hash as mohash
        h = mohash.hash_columns(list(kdata), list(kvalid))
        # second-level mix so partition bits are independent of the group
        # bits used inside each partition's sort
        return np.asarray(jax.device_get((h >> 17) % np.uint64(self.P)),
                          dtype=np.int64)

    def add_raw(self, kdata, kvalid, mask, values) -> None:
        """Park one input batch (keys + pre-evaluated agg args), compressed
        to live rows. `values[j]` is a DeviceColumn or None (count(*))."""
        live = np.asarray(jax.device_get(mask))
        if not live.any():
            return
        parts = self._partitions(kdata, kvalid)
        kd = [np.asarray(jax.device_get(a)) for a in kdata]
        kv = [np.asarray(jax.device_get(a)) for a in kvalid]
        vals = [(np.asarray(jax.device_get(v.data)),
                 np.asarray(jax.device_get(v.validity)))
                if v is not None else None for v in values]
        for p in range(self.P):
            rows = np.nonzero(live & (parts == p))[0]
            if not len(rows):
                continue
            blob = {}
            for i, (d, v) in enumerate(zip(kd, kv)):
                blob[f"k{i}_d"], blob[f"k{i}_v"] = d[rows], v[rows]
            for j, dv in enumerate(vals):
                if dv is not None:
                    blob[f"a{j}_d"], blob[f"a{j}_v"] = \
                        dv[0][rows], dv[1][rows]
            path = self._path()
            np.savez(path, **blob)
            self.raw_chunks[p].append(path)

    def add_state(self, state, aggs) -> None:
        """Park a partial group table (keys + per-agg partial fields)."""
        present = np.asarray(jax.device_get(state["present"]))
        if not present.any():
            return
        parts = self._partitions(state["keys"], state["kvalid"])
        kd = [np.asarray(jax.device_get(a)) for a in state["keys"]]
        kv = [np.asarray(jax.device_get(a)) for a in state["kvalid"]]
        partials = [{f: np.asarray(jax.device_get(arr))
                     for f, arr in part.items()}
                    for part in state["partials"]]
        for p in range(self.P):
            rows = np.nonzero(present & (parts == p))[0]
            if not len(rows):
                continue
            blob = {}
            for i, (d, v) in enumerate(zip(kd, kv)):
                blob[f"k{i}_d"], blob[f"k{i}_v"] = d[rows], v[rows]
            for j, part in enumerate(partials):
                for f, arr in part.items():
                    blob[f"p{j}_{f}"] = arr[rows]
            path = self._path()
            np.savez(path, **blob)
            self.state_chunks[p].append(path)

    def iter_raw(self, p: int, nkeys: int, naggs: int):
        """Yield (kdata, kvalid, mask, values) per parked chunk, padded to
        the jit bucket. values[j] = (data, validity) np pair or None."""
        for path in self.raw_chunks[p]:
            z = np.load(path)
            n = z["k0_d"].shape[0]
            padded = bucket_length(n)
            pad = padded - n

            def _pad(a):
                if not pad:
                    return jnp.asarray(a)
                fill = np.zeros((pad,) + a.shape[1:], a.dtype)
                return jnp.asarray(np.concatenate([a, fill]))
            kdata = [_pad(z[f"k{i}_d"]) for i in range(nkeys)]
            kvalid = [_pad(z[f"k{i}_v"]) for i in range(nkeys)]
            mask = jnp.asarray(np.arange(padded) < n)
            values = []
            for j in range(naggs):
                if f"a{j}_d" in z:
                    values.append((_pad(z[f"a{j}_d"]), _pad(z[f"a{j}_v"])))
                else:
                    values.append(None)
            yield kdata, kvalid, mask, values

    def iter_state(self, p: int, nkeys: int, aggs):
        """Yield parked partial states as state dicts (padded)."""
        for path in self.state_chunks[p]:
            z = np.load(path)
            n = z["k0_d"].shape[0]
            padded = bucket_length(n)
            pad = padded - n

            def _pad(a):
                if not pad:
                    return jnp.asarray(a)
                fill = np.zeros((pad,) + a.shape[1:], a.dtype)
                return jnp.asarray(np.concatenate([a, fill]))
            keys = [_pad(z[f"k{i}_d"]) for i in range(nkeys)]
            kvalid = [_pad(z[f"k{i}_v"]) for i in range(nkeys)]
            present = jnp.asarray(np.arange(padded) < n)
            partials = []
            for j in range(len(aggs)):
                part = {}
                prefix = f"p{j}_"
                for f in z.files:
                    if f.startswith(prefix):
                        part[f[len(prefix):]] = _pad(z[f])
                partials.append(part)
            yield {"keys": keys, "kvalid": kvalid, "present": present,
                   "partials": partials, "n": jnp.asarray(n, jnp.int32)}

    def cleanup(self) -> None:
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)


class AggOp(Operator):
    """Streaming group-by: per-batch partial agg folded into a device-
    resident group table (colexec/group + mergegroup, re-expressed).

    The group table grows adaptively (quantized ×4 so the jit cache stays
    small — the reference grows its hash table the same way); past
    `max_device_groups` it Grace-spills to host (see _AggSpill)."""

    step_span = "op.aggregate"

    def __init__(self, node: P.Aggregate, child: Operator,
                 max_groups: int = 4096,
                 max_device_groups: int = 1 << 21,
                 spill_partitions: int = 16):
        self.node = node
        self.child = child
        self.schema = node.schema
        self.max_groups = max_groups
        self.max_device_groups = max(max_groups, max_device_groups)
        self.spill_partitions = spill_partitions
        self._spill: Optional[_AggSpill] = None

    def _grow(self, needed: int, allow_spill: bool) -> None:
        nxt = self.max_groups
        while nxt < needed:
            nxt *= 4
        nxt = min(nxt, self.max_device_groups)
        if nxt < needed:
            if allow_spill:
                raise _NeedSpill
            raise EvalError(
                f"group count {needed} exceeds the device budget "
                f"({self.max_device_groups}) even within one spill "
                f"partition; raise spill_partitions ({self.spill_partitions})")
        self.max_groups = nxt

    def execute(self) -> Iterator[ExecBatch]:
        if not self.node.group_keys:
            yield from self._scalar_agg()
            return
        yield from self._grouped_agg()

    # ---- scalar (no GROUP BY)
    def _scalar_agg(self):
        states = [None] * len(self.node.aggs)
        tracker = _AggDictTracker(self.node.aggs)
        for ex in self.child.execute():
            tracker.observe(ex)
            for i, a in enumerate(self.node.aggs):
                states[i] = _scalar_step_host(a, ex, states[i])
        yield self._scalar_result(states, tracker)

    def _scalar_result(self, states, tracker) -> ExecBatch:
        """Finalize scalar-agg states -> the single output batch (shared
        by the pull loop above and the fused-fragment path, which folds
        the per-batch `_scalar_step` into one traced program)."""
        cols, n1 = {}, jnp.asarray(1, jnp.int32)
        out_dicts: Dict[str, list] = {}
        for (name, dtype), a, st in zip(self.node.schema[len(self.node.group_keys):],
                                        self.node.aggs, states):
            col = _scalar_final(a, st, dtype)
            d = tracker.dicts.get(a.out_name)
            if d is not None and dtype.is_varlen:
                col = _rank_to_code(col, d, dtype)
                out_dicts[name] = d
            cols[name] = col
        db = DeviceBatch(columns=cols, n_rows=n1)
        return ExecBatch(batch=db, dicts=out_dicts,
                         mask=jnp.ones((1,), jnp.bool_))

    # ---- grouped
    def _grouped_agg(self, seed=None, seed_dicts=None):
        """`seed`/`seed_dicts`: a partial group-table state handed over
        by a fused fragment that had to degrade mid-stream (a key
        dictionary grew); the remaining batches continue on the general
        path with the fused partials already folded in."""
        nkeys = len(self.node.group_keys)
        key_dicts: List[Optional[List[str]]] = \
            list(seed_dicts) if seed_dicts is not None else [None] * nkeys
        if not hasattr(self, "_agg_tracker") or seed is None:
            self._agg_tracker = _AggDictTracker(self.node.aggs)
        try:
            yield from self._grouped_agg_inner(nkeys, key_dicts,
                                               seed=seed)
        finally:
            if self._spill is not None:     # exception escaped mid-spill
                self._spill.cleanup()
                self._spill = None

    def _grouped_agg_inner(self, nkeys, key_dicts, seed=None):
        state = seed   # dict: keys:[arrays], kvalid:[arrays], partials per agg
        dense = None       # dense accumulator (no hash, no sort)
        # a seeded state is already in general form: the dense fast path
        # cannot absorb it, so it stays off for the remaining stream
        dense_checked = seed is not None
        for ex in self.child.execute():
            self._agg_tracker.observe(ex)
            keys = [eval_expr(k, ex) for k in self.node.group_keys]
            for i, (k_ast, k) in enumerate(zip(self.node.group_keys, keys)):
                d = _expr_dict(k_ast, ex)
                if d is not None:
                    key_dicts[i] = d
            kdata = [_broadcast_full(k, ex.padded_len).data for k in keys]
            kvalid = [_broadcast_full(k, ex.padded_len).validity
                      for k in keys]
            values = [None if (a.func == "count" and a.arg is None)
                      else _agg_value(a, ex) for a in self.node.aggs]
            if not dense_checked:
                dense_checked = True
                dense = self._dense_init(ex)
            if dense is not None:
                if self._dense_fits(dense, ex):
                    self._dense_step(dense, kdata, kvalid, ex.mask, values)
                    continue
                # a key dictionary grew mid-stream (concurrent insert /
                # union arm) or an integer key left the range the slots
                # were laid out for: the dense key space is stale — convert
                # the partials to a standard group table and continue general
                state = self._dense_to_state(dense)
                dense = None
            if self._spill is not None:
                self._spill.add_raw(kdata, kvalid, ex.mask, values)
                continue
            try:
                part = self._partial_vals(kdata, kvalid, ex.mask, values,
                                          allow_spill=True)
                state = part if state is None else \
                    self._merge(state, part, allow_spill=True)
            except _NeedSpill:
                self._spill = _AggSpill(self.spill_partitions)
                if state is not None:
                    self._spill.add_state(state, self.node.aggs)
                    state = None
                self._spill.add_raw(kdata, kvalid, ex.mask, values)
        if dense is not None:
            yield self._finalize(self._dense_to_state(dense), key_dicts)
            return
        if self._spill is None:
            if state is None:
                state = self._empty_state()
            yield self._finalize(state, key_dicts)
            return
        # spill drain: each partition has a disjoint key set
        spill = self._spill
        naggs = len(self.node.aggs)
        for p in range(spill.P):
            pstate = None
            for kdata, kvalid, mask, vals in spill.iter_raw(
                    p, nkeys, naggs):
                values = self._revive_values(vals)
                part = self._partial_vals(kdata, kvalid, mask, values,
                                          allow_spill=False)
                pstate = part if pstate is None else \
                    self._merge(pstate, part, allow_spill=False)
            for st in spill.iter_state(p, nkeys, self.node.aggs):
                pstate = st if pstate is None else \
                    self._merge(pstate, st, allow_spill=False)
            if pstate is not None and int(jax.device_get(pstate["n"])):
                yield self._finalize(pstate, key_dicts)

    # ---- dense fast path: every group key has a bounded code space (a
    # dictionary, a bool, or an integer column whose range its producer
    # observed: `ExecBatch.ranges`), so the group id is a mixed-radix
    # number over the key spaces instead of hash+argsort, with additive
    # aggregates.  ONE ladder (init / fits / step / to_state), two folds
    # chosen by the slot count.  Few slots (the Q1 shape: GROUP BY two
    # dict-coded columns): the deduplicated partial lanes fold as fused
    # masked sums (ops/agg.dense_lane_partials); cross-chunk merge is an
    # elementwise add of (G,)-sized partials — no re-grouping sort.  Many
    # (the star-join shape: 1,000 brands x 7 years), or a key that is an
    # integer range: a batch folds in with ONE compiled program of
    # scatter-adds (`_wide_fold`) into one int64 accumulator; no group
    # count to wait for, the one wait is at the end: which slots hold rows.
    WIDE_SLOTS_MAX = 1 << 23       # int64 cells of the accumulator: 64 MB

    def _key_spaces(self, ex) -> Optional[List[tuple]]:
        """Per key (size, lo, hi): a dictionary's length (lo and hi None),
        or the observed range of a plain integer column with the size
        rounded up to a power of two (a stable shape across parameter
        draws); None when a key has no bounded code space (numeric keys
        nobody observed, computed strings without a dict)."""
        out = []
        for k in self.node.group_keys:
            d = _expr_dict(k, ex)
            if d is not None:
                out.append((max(len(d), 1), None, None))
            elif k.dtype.oid == TypeOid.BOOL:
                out.append((2, None, None))
            elif (isinstance(k, BoundCol) and k.dtype.is_integer
                    and k.name in ex.ranges):
                lo, hi = (int(v) for v in ex.ranges[k.name])
                out.append((1 << max(hi - lo, 0).bit_length(), lo, hi))
            else:
                return None
        return out

    def _dense_fits(self, dense, ex) -> bool:
        """Does this batch's code space fit the slots laid out?  A
        dictionary must be the same length; an integer range must lie
        inside [lo, lo + size)."""
        spaces = self._key_spaces(ex)
        if spaces is None:
            return False
        for (size, lo, hi), wsize, wlo in zip(spaces, dense["sizes"],
                                              dense["los"]):
            if (lo is None) != (wlo is None):
                return False
            if lo is None and size != wsize:
                return False
            if lo is not None and (lo < wlo or hi >= wlo + wsize):
                return False
        return True

    @staticmethod
    def _dense_fields(a: AggCall) -> List[tuple]:
        """(class, field) layout of one aggregate's partial state —
        shared by the per-chunk step and the state converter so the two
        can never disagree on stack order."""
        if a.func == "count":
            return [("int", "count")]
        if a.func in ("sum", "avg"):
            cls = "float" if a.arg.dtype.is_float else "int"
            return [(cls, "sum"), ("int", "count")]
        return [("float", "sum"), ("float", "sumsq"), ("int", "count")]

    def _dense_init(self, ex, scatter_ok: bool = True) -> Optional[dict]:
        """The accumulator for this stream's key space, or None (the
        general path).  `scatter_ok` False keeps to the masked-sum layout
        (a shard's partial state is merged field by field)."""
        if os.environ.get("MO_DENSE_GROUPS") == "0":
            return None
        dense_funcs = {"count", "sum", "avg"} | STDDEV_AGGS
        for a in self.node.aggs:
            # min/max/bit partials don't merge additively; distinct
            # needs per-group key sets — all take the general path
            if a.distinct or a.func not in dense_funcs:
                return None
        spaces = self._key_spaces(ex)
        if spaces is None:
            return None
        sizes = tuple(s for s, _lo, _hi in spaces)
        los = tuple(lo for _s, lo, _hi in spaces)
        _strides, g = A.dense_slot_strides(sizes)
        fields = [cf for a in self.node.aggs for cf in self._dense_fields(a)]
        n_fields = 1 + len(fields)
        if all(lo is None for lo in los) and g * n_fields <= 4096 \
                and g <= int(os.environ.get("MO_DENSE_GROUPS_MAX", "256")):
            # the masked-sum family unrolls G x fields reductions at
            # trace time, hence the cap on the XLA graph size; its
            # accumulators live at FULL (NULL-slotted) granularity;
            # all-valid chunks compute in the compact key space and
            # scatter into the matching full slots
            partials = []
            for a in self.node.aggs:
                partials.append({f: jnp.zeros((g,), jnp.int64 if c == "int"
                                              else jnp.float64)
                                 for c, f in self._dense_fields(a)})
            return {"sizes": sizes, "los": los, "partials": partials,
                    "rows": jnp.zeros((g,), jnp.int64)}
        if not scatter_ok or g * n_fields > self.WIDE_SLOTS_MAX \
                or any(c != "int" for c, _f in fields):
            return None                  # one int64 accumulator holds all
        return {"sizes": sizes, "los": los,
                "los_dev": jnp.asarray(np.asarray(
                    [lo or 0 for lo in los], np.int64)),
                "acc": jnp.zeros((g, n_fields), jnp.int64),
                "outside": jnp.zeros((), jnp.bool_)}

    def _dense_step(self, dense, kdata, kvalid, mask, values) -> None:
        if "acc" in dense:               # many slots: the scatter fold
            fields, _at, _n = self._wide_fields()
            dense["acc"], dense["outside"] = _wide_fold(
                tuple(kdata), tuple(kvalid), mask,
                tuple(None if v is None else v.data for v in values),
                tuple(None if v is None else v.validity for v in values),
                dense["los_dev"], dense["acc"], dense["outside"],
                sizes=dense["sizes"], fields=fields)
            return
        # ONE fused host sync answers every 'no NULLs here?' question for
        # the chunk: all-valid keys shrink the key space (no NULL slots)
        # and all-valid agg args collapse their count field into the
        # shared rows lane
        checks = list(kvalid)
        vidx = {}
        for v in values:
            if v is not None and id(v.validity) not in vidx:
                vidx[id(v.validity)] = len(checks)
                checks.append(v.validity)
        flags = np.asarray(jax.device_get(
            jnp.asarray([jnp.all(c) for c in checks])))
        keys_allvalid = bool(flags[:len(kvalid)].all())
        with_null = not keys_allvalid
        # build deduplicated lanes: plain-column agg args share their
        # DeviceColumn object (eval_expr returns the batch column), so
        # sum(l_quantity) and avg(l_quantity) collapse to ONE lane;
        # counts over all-valid args collapse into the rows lane
        int_vals, int_masks, float_vals, float_masks = [], [], [], []
        lane_of = {}                    # dedupe key -> ("int"|"float", idx)
        fieldmap = []                   # per agg: [(field, lane-or-"rows")]
        for a, v in zip(self.node.aggs, values):
            allv = v is None or bool(flags[vidx[id(v.validity)]])
            mkey = "rows" if allv else id(v.validity)
            mval = None if allv else v.validity
            x = None
            fm = []
            for cls, field in self._dense_fields(a):
                if field == "count" and mkey == "rows":
                    fm.append((field, "rows"))
                    continue
                if cls == "float" and field != "count" \
                        and a.func in STDDEV_AGGS and x is None:
                    x = _float_of(v)
                val = (None if field == "count"
                       else x * x if field == "sumsq"
                       else x if x is not None else v.data)
                key = (cls, field == "sumsq",
                       None if field == "count" else id(v.data), mkey)
                lane = lane_of.get(key)
                if lane is None:
                    if cls == "int":
                        lane = ("int", len(int_vals))
                        int_vals.append(val)
                        int_masks.append(mval)
                    else:
                        lane = ("float", len(float_vals))
                        float_vals.append(val)
                        float_masks.append(mval)
                    lane_of[key] = lane
                fm.append((field, lane))
            fieldmap.append(fm)
        ints, floats, rows = A.dense_lane_partials(
            tuple(kdata), tuple(kvalid), mask,
            tuple(int_vals), tuple(int_masks),
            tuple(float_vals), tuple(float_masks),
            sizes=dense["sizes"], with_null=with_null)
        # scatter the chunk's compact-space results into the full-space
        # accumulators (identity when the chunk used NULL slots)
        pos = self._dense_positions(dense, with_null)
        for fm, part in zip(fieldmap, dense["partials"]):
            for field, lane in fm:
                add = (rows if lane == "rows"
                       else ints[lane[1]] if lane[0] == "int"
                       else floats[lane[1]])
                part[field] = part[field].at[pos].add(
                    add.astype(part[field].dtype))
        dense["rows"] = dense["rows"].at[pos].add(rows)

    def _dense_positions(self, dense, with_null: bool):
        """Full-space slot of each compact-space slot (cached)."""
        key = ("pos", with_null)
        pos = dense.get(key)
        if pos is None:
            sizes = dense["sizes"]
            strides_c, g_eff = A.dense_slot_strides(
                sizes, null_slots=with_null)
            strides_f, _g_full = A.dense_slot_strides(sizes)
            pos = np.zeros(g_eff, np.int32)
            for slot in range(g_eff):
                full, rem = 0, slot
                for s, stc, stf in zip(sizes, strides_c, strides_f):
                    digit = rem // stc
                    rem = rem % stc
                    full += digit * stf
                pos[slot] = full
            pos = jnp.asarray(pos)
            dense[key] = pos
        return pos

    def _dense_to_state(self, dense) -> dict:
        """Dense accumulator -> the standard state dict. `present` is
        scattered over the G slots (not front-packed); every consumer —
        _merge's re-group, _finalize's output mask, the session's
        mask-compacting _to_host — works off the mask, so that's fine.
        (The scatter fold's many slots are front-packed instead.)"""
        if "acc" in dense:
            return self._wide_to_state(dense)
        sizes = dense["sizes"]
        strides, g = A.dense_slot_strides(sizes)
        present = dense["rows"] > 0
        slots = jnp.arange(g, dtype=jnp.int32)
        keys, kvalid = [], []
        for k_ast, s, st in zip(self.node.group_keys, sizes, strides):
            code = (slots // st) % (s + 1)
            valid = code < s
            keys.append(code.astype(jnp.int32 if k_ast.dtype.is_varlen
                                    else k_ast.dtype.jnp_dtype))
            kvalid.append(valid)
        n = jnp.sum(present.astype(jnp.int32))
        return {"keys": keys, "kvalid": kvalid, "present": present,
                "partials": [dict(p) for p in dense["partials"]],
                "n": n}

    def _wide_fields(self):
        """Per aggregate its fields' names, and the column of each in the
        accumulator (the row count takes the last)."""
        fields = tuple(tuple(f for _c, f in self._dense_fields(a))
                       for a in self.node.aggs)
        at, col = [], 0
        for fs in fields:
            at.append({f: col + i for i, f in enumerate(fs)})
            col += len(fs)
        return fields, at, col

    #: fewest lanes of the state the wide path hands on: how many groups a
    #: statement's constants leave must not be a new shape downstream
    WIDE_STATE_LANES = 4096

    def _wide_to_state(self, wide) -> dict:
        """The filled slots as the standard state dict, front-packed: the
        slots' row counts come to the host (the path's one wait), the few
        filled ones are gathered."""
        from matrixone_tpu.container.device import bucket_length
        rows, outside = jax.device_get((_wide_rows(wide["acc"]),
                                        wide["outside"]))
        M.device_wait.inc(site="agg_slots")
        if bool(outside):
            raise EvalError("grouped aggregate: a group key lies outside "
                            "the range its producer observed")
        filled = np.flatnonzero(np.asarray(rows) > 0)
        n = len(filled)
        cap = max(self.WIDE_STATE_LANES, bucket_length(n))
        slot = np.zeros(cap, np.int64)
        slot[:n] = filled
        strides, _g = A.dense_slot_strides(wide["sizes"])
        keys, kvalid = [], []
        for k, size, lo, st in zip(self.node.group_keys, wide["sizes"],
                                   wide["los"], strides):
            code = (slot // st) % (size + 1)
            kvalid.append(jnp.asarray(code < size))
            keys.append(jnp.asarray((code + (lo or 0)).astype(
                np.int32 if k.dtype.is_varlen else k.dtype.np_dtype)))
        got = _wide_take(wide["acc"], slot.astype(np.int32))
        _fields, at, _n = self._wide_fields()
        partials = [{f: got[col] for f, col in cols.items()}
                    for cols in at]
        present = jnp.asarray(np.arange(cap) < n)
        return {"keys": keys, "kvalid": kvalid, "present": present,
                "partials": partials, "n": jnp.asarray(n, jnp.int32)}

    def _revive_values(self, vals):
        """Spilled (data, validity) np pairs -> DeviceColumns (dtype is
        reconstructed from the array dtype; only used for agg math)."""
        out = []
        for dv in vals:
            if dv is None:
                out.append(None)
            else:
                d, v = jnp.asarray(dv[0]), jnp.asarray(dv[1])
                out.append(DeviceColumn(d, v, dt.from_jnp(d.dtype)))
        return out

    def _partial_vals(self, kdata, kvalid, mask, values, allow_spill: bool):
        while True:
            mg = self.max_groups
            gi = A.group_ids(kdata, kvalid, mask, mg)
            ng = int(jax.device_get(gi.num_groups))
            M.device_wait.inc(site="agg_groups")
            if ng <= mg:
                break
            self._grow(ng, allow_spill)
        rep_k, rep_v = A.gather_keys(kdata, kvalid, gi.rep_rows)
        present = jnp.arange(mg, dtype=jnp.int32) < gi.num_groups
        partials = []
        for a, v in zip(self.node.aggs, values):
            partials.append(_grouped_step(a, gi, v, mask, mg))
        return {"keys": rep_k, "kvalid": rep_v, "present": present,
                "partials": partials, "n": gi.num_groups}

    def _merge(self, s1, s2, allow_spill: bool = False):
        """Merge two partial group tables by concatenating their rows and
        re-grouping (mergegroup)."""
        keys = [jnp.concatenate([a, b]) for a, b in zip(s1["keys"], s2["keys"])]
        kvalid = [jnp.concatenate([a, b]) for a, b in zip(s1["kvalid"], s2["kvalid"])]
        mask = jnp.concatenate([s1["present"], s2["present"]])
        while True:
            mg = self.max_groups
            gi = A.group_ids(keys, kvalid, mask, mg)
            ng = int(jax.device_get(gi.num_groups))
            M.device_wait.inc(site="agg_groups")
            if ng <= mg:
                break
            self._grow(ng, allow_spill)
        rep_k, rep_v = A.gather_keys(keys, kvalid, gi.rep_rows)
        present = jnp.arange(mg, dtype=jnp.int32) < gi.num_groups
        partials = []
        for a, p1, p2 in zip(self.node.aggs, s1["partials"], s2["partials"]):
            partials.append(_grouped_merge(a, p1, p2, gi, mask, mg))
        return {"keys": rep_k, "kvalid": rep_v, "present": present,
                "partials": partials, "n": gi.num_groups}

    def _empty_state(self):
        mg = self.max_groups
        keys, kvalid = [], []
        for k in self.node.group_keys:
            keys.append(jnp.zeros((mg,), k.dtype.jnp_dtype if not
                                  k.dtype.is_varlen else jnp.int32))
            kvalid.append(jnp.zeros((mg,), jnp.bool_))
        partials = [_grouped_empty(a, mg) for a in self.node.aggs]
        return {"keys": keys, "kvalid": kvalid,
                "present": jnp.zeros((mg,), jnp.bool_),
                "partials": partials, "n": jnp.asarray(0, jnp.int32)}

    def _finalize(self, state, key_dicts) -> ExecBatch:
        nkeys = len(self.node.group_keys)
        cols: Dict[str, DeviceColumn] = {}
        dicts: Dict[str, List[str]] = {}
        for i, ((name, dtype), k) in enumerate(zip(self.node.schema[:nkeys],
                                                   self.node.group_keys)):
            cols[name] = DeviceColumn(state["keys"][i], state["kvalid"][i],
                                      k.dtype)
            if key_dicts[i] is not None:
                dicts[name] = key_dicts[i]
        for (name, dtype), a, part in zip(self.node.schema[nkeys:],
                                          self.node.aggs, state["partials"]):
            col = _grouped_final(a, part, dtype)
            d = self._agg_tracker.dicts.get(a.out_name)
            if d is not None and dtype.is_varlen:
                col = _rank_to_code(col, d, dtype)
                dicts[name] = d
            cols[name] = col
        db = DeviceBatch(columns=cols, n_rows=state["n"])
        return ExecBatch(batch=db, dicts=dicts, mask=state["present"])

    # ---- distributed partials (parallel/dist_query.py shard executor)
    def partial_state(self):
        """Run the grouped accumulation loop but stop BEFORE finalize and
        hand back the raw partial group table for a cross-shard merge.
        Unlike the host-peer fragment path this keeps the dense fast
        path live (its partials psum across shards).  Returns
        (kind, payload, key_dicts, tracker):

          kind "dense"   -> payload = the dense accumulator dict
          kind "general" -> payload = state dict (keys/kvalid/present/
                            partials/n) sized to self.max_groups
          kind "empty"   -> payload None (this shard saw no rows)

        Spill is disabled: a shard whose group table exceeds the device
        budget raises _NeedSpill and the caller degrades the whole query
        to single-device execution."""
        key_dicts: List[Optional[list]] = [None] * len(self.node.group_keys)
        tracker = _AggDictTracker(self.node.aggs)
        state = None
        dense = None
        dense_checked = False
        for ex in self.child.execute():
            tracker.observe(ex)
            keys = [eval_expr(k, ex) for k in self.node.group_keys]
            for i, (k_ast, _k) in enumerate(zip(self.node.group_keys,
                                                keys)):
                d = _expr_dict(k_ast, ex)
                if d is not None:
                    key_dicts[i] = d
            kdata = [_broadcast_full(k, ex.padded_len).data for k in keys]
            kvalid = [_broadcast_full(k, ex.padded_len).validity
                      for k in keys]
            values = [None if (a.func == "count" and a.arg is None)
                      else _agg_value(a, ex) for a in self.node.aggs]
            if not dense_checked:
                dense_checked = True
                dense = self._dense_init(ex, scatter_ok=False)
            if dense is not None:
                if self._dense_fits(dense, ex):
                    self._dense_step(dense, kdata, kvalid, ex.mask,
                                     values)
                    continue
                state = self._dense_to_state(dense)
                dense = None
            part = self._partial_vals(kdata, kvalid, ex.mask, values,
                                      allow_spill=False)
            state = part if state is None else \
                self._merge(state, part, allow_spill=False)
        if dense is not None:
            return "dense", dense, key_dicts, tracker
        if state is not None:
            return "general", state, key_dicts, tracker
        return "empty", None, key_dicts, tracker

    def partial_scalar_state(self):
        """Scalar (no GROUP BY) counterpart of partial_state: per-agg
        partial tuples plus the string-dict tracker."""
        states = [None] * len(self.node.aggs)
        tracker = _AggDictTracker(self.node.aggs)
        for ex in self.child.execute():
            tracker.observe(ex)
            for i, a in enumerate(self.node.aggs):
                states[i] = _scalar_step_host(a, ex, states[i])
        return states, tracker


def _broadcast_full(col: DeviceColumn, n: int) -> DeviceColumn:
    if col.data.shape[0] == n:
        return col
    return DeviceColumn(jnp.broadcast_to(col.data, (n,) + col.data.shape[1:]),
                        jnp.broadcast_to(col.validity, (n,)), col.dtype)


# agg kernels: per-batch partial, merge, finalize -------------------------

#: the wide dense fold packs a batch's live rows into lanes / this many
#: lanes before it scatters them, when they fit (a star join leaves 1-3%
#: of its lanes alive, and a scatter-add costs the chip by the lane, 47 ns:
#: packed, the fold fell from 2.1 to 0.45 s of a 5 s slice; 16 itself was
#: not swept)
_WIDE_PACK = 16
_PACK_ROW = 1024


def _live_lanes(mask, cap: int):
    """Where the first `cap` live lanes of `mask` are, by gathers alone:
    -> (src [cap] lane numbers, n_live).  Lanes are taken as rows of
    `_PACK_ROW`: a running count inside each row and over the rows'
    totals, then for every output position a binary search for its row
    and one inside the row.  No sort, no scatter, and no scan longer than
    a row (the chip's compiler takes half a minute over a scan of 2^20)."""
    rows = mask.shape[0] // _PACK_ROW
    within = jnp.cumsum(mask.reshape(rows, _PACK_ROW).astype(jnp.int32),
                        axis=1)
    row_tot = within[:, -1]
    row_end = jnp.cumsum(row_tot)
    j = jnp.arange(cap, dtype=jnp.int32)
    r = jnp.minimum(jnp.searchsorted(row_end, j, side="right"),
                    rows - 1).astype(jnp.int32)
    k = j - (row_end[r] - row_tot[r])          # rank inside the row
    flat = within.reshape(-1)
    lo = jnp.zeros((cap,), jnp.int32)
    hi = jnp.full((cap,), _PACK_ROW - 1, jnp.int32)
    for _ in range(_PACK_ROW.bit_length() - 1):
        mid = (lo + hi) // 2
        right = flat[r * _PACK_ROW + mid] <= k
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(right, hi, mid)
    return r * _PACK_ROW + jnp.minimum(lo, _PACK_ROW - 1), row_end[-1]


@jax.jit
def _wide_rows(acc):
    """The row count of every slot of the wide dense accumulator."""
    return acc[:, -1]


@jax.jit
def _wide_take(acc, slots):
    """The accumulator's columns at `slots`, one array a column."""
    got = acc[slots]
    return tuple(got[:, i] for i in range(acc.shape[1]))


@functools.partial(jax.jit, static_argnames=("sizes", "fields"),
                   donate_argnames=("acc",))
def _wide_fold(kdata, kvalid, mask, vdata, vvalid, los, acc, outside, *,
               sizes, fields):
    """One batch folded into the wide dense accumulator
    (`AggOp._wide_step`): `acc` is int64 [slots, fields + 1], every
    aggregate's fields side by side and the row count last.  The mixed-
    radix slot of every live row, then ONE scatter-add of all fields.  A
    NULL key takes its key's extra slot; a masked row takes no slot.  A
    batch whose live rows fit lanes / `_WIDE_PACK` is packed first
    (`_live_lanes`) and only the packed lanes are scattered.  `outside`
    remembers a valid key outside its code space (the producer's range
    was wrong): the caller refuses the sums."""
    strides, g = A.dense_slot_strides(sizes)
    slot = jnp.zeros(mask.shape, jnp.int32)
    for data, valid, lo, size, st in zip(kdata, kvalid, los, sizes,
                                         strides):
        code = data.astype(jnp.int64) - lo
        outside = outside | jnp.any(mask & valid
                                    & ((code < 0) | (code >= size)))
        code = jnp.where(valid, jnp.clip(code, 0, size - 1), size)
        slot = slot + code.astype(jnp.int32) * st
    slot = jnp.where(mask, slot, g)                # dropped by the scatter
    cols = []
    for fs, data, valid in zip(fields, vdata, vvalid):
        live = mask if valid is None else mask & valid
        for f in fs:
            cols.append(live.astype(jnp.int64) if f == "count"
                        else jnp.where(live, data, 0).astype(jnp.int64))
    cols.append(mask.astype(jnp.int64))
    updates = jnp.stack(cols, axis=1)              # [lanes, fields + 1]

    def scatter_all(acc):
        return acc.at[slot].add(updates, mode="drop")

    lanes = mask.shape[0]
    if lanes % _PACK_ROW or lanes < (_PACK_ROW << 6):
        return scatter_all(acc), outside
    cap = lanes // _WIDE_PACK
    src, n_live = _live_lanes(mask, cap)

    def scatter_packed(acc):
        at = jnp.where(jnp.arange(cap, dtype=jnp.int32) < n_live,
                       slot[src], g)
        return acc.at[at].add(updates[src], mode="drop")

    return jax.lax.cond(n_live <= cap, scatter_packed, scatter_all,
                        acc), outside


def _agg_value(a: AggCall, ex: ExecBatch):
    if a.func in ("min", "max") and a.arg.dtype.is_varlen:
        # aggregate over collation ranks so min/max follow string order,
        # not dictionary insertion order; finalize maps rank -> string.
        # (_sort_key_col evaluates the expression itself: one eval only)
        if _expr_dict(a.arg, ex) is None:
            raise EvalError(
                f"{a.func}() over computed strings without a dictionary "
                f"is not supported yet")
        return _broadcast_full(_sort_key_col(a.arg, ex), ex.padded_len)
    col = eval_expr(a.arg, ex)
    return _broadcast_full(col, ex.padded_len)


def _rank_to_code(col: DeviceColumn, d: list, dtype) -> DeviceColumn:
    """Invert collation rank back to a dictionary code (string min/max
    finalize; shared by the scalar and grouped paths)."""
    order = np.argsort(np.asarray(d, dtype=object))
    code = jnp.asarray(order.astype(np.int32))[
        jnp.clip(col.data.astype(jnp.int32), 0, len(d) - 1)]
    return DeviceColumn(code, col.validity, dtype)


class _AggDictTracker:
    """Captures the dictionary behind each string min/max argument and
    REJECTS mid-stream growth: collation ranks are only comparable across
    batches when the dictionary is frozen (a union arm or concurrent
    insert growing it would silently corrupt results otherwise)."""

    def __init__(self, aggs):
        self.watch = [a for a in aggs
                      if a.func in ("min", "max") and a.arg is not None
                      and a.arg.dtype.is_varlen]
        self.dicts: Dict[str, list] = {}
        self._sizes: Dict[str, int] = {}

    def observe(self, ex: ExecBatch):
        for a in self.watch:
            d = _expr_dict(a.arg, ex)
            if d is None:
                continue
            prev = self.dicts.get(a.out_name)
            if prev is None:
                self.dicts[a.out_name] = d
                self._sizes[a.out_name] = len(d)
            elif prev is not d or len(d) != self._sizes[a.out_name]:
                raise EvalError(
                    f"{a.func}() over strings from a growing dictionary "
                    f"(union / multi-source) is not supported yet")


from matrixone_tpu.sql.parser import BIT_AGGS, STDDEV_AGGS  # one registry

_BIT_IDENT = {"bit_and": -1, "bit_or": 0, "bit_xor": 0}
_BIT_UFUNC = {"bit_and": np.bitwise_and, "bit_or": np.bitwise_or,
              "bit_xor": np.bitwise_xor}


def _host_bit_reduce(func: str, data, gids, mask, mg: int):
    """Grouped bitwise reduce: XLA has no segment and/or/xor, and the
    identity values make host ufunc.at both exact and merge-transparent
    (identity rows vanish under the operator)."""
    d = np.asarray(jax.device_get(data)).astype(np.int64)
    g = np.asarray(jax.device_get(gids))
    m = np.asarray(jax.device_get(mask))
    out = np.full(mg, _BIT_IDENT[func], np.int64)
    _BIT_UFUNC[func].at(out, g[m], d[m])
    return jnp.asarray(out)


def _grouped_step(a: AggCall, gi, col: Optional[DeviceColumn],
                  row_mask, mg: int):
    """Per-batch partial for one aggregate over PRE-EVALUATED values
    (col = _agg_value(...) or a revived spill chunk; None for count(*))."""
    if a.func == "count" and a.arg is None:
        return {"count": A.seg_count(gi.gids, row_mask, mg)}
    m = row_mask & col.validity
    if a.func == "count":
        return {"count": A.seg_count(gi.gids, m, mg)}
    if a.func == "sum":
        return {"sum": A.seg_sum(col.data, gi.gids, m, mg),
                "count": A.seg_count(gi.gids, m, mg)}
    if a.func == "avg":
        return {"sum": A.seg_sum(col.data.astype(jnp.float64)
                                 if col.dtype.is_float else col.data,
                                 gi.gids, m, mg),
                "count": A.seg_count(gi.gids, m, mg)}
    if a.func == "min":
        return {"min": A.seg_min(col.data, gi.gids, m, mg),
                "count": A.seg_count(gi.gids, m, mg)}
    if a.func == "max":
        return {"max": A.seg_max(col.data, gi.gids, m, mg),
                "count": A.seg_count(gi.gids, m, mg)}
    if a.func in STDDEV_AGGS:
        x = _float_of(col)
        return {"sum": A.seg_sum(x, gi.gids, m, mg),
                "sumsq": A.seg_sum(x * x, gi.gids, m, mg),
                "count": A.seg_count(gi.gids, m, mg)}
    if a.func in BIT_AGGS:
        return {"bits": _host_bit_reduce(a.func, col.data, gi.gids, m,
                                         mg),
                "count": A.seg_count(gi.gids, m, mg)}
    raise EvalError(f"unsupported aggregate {a.func}")


def _float_of(col: DeviceColumn):
    x = col.data.astype(jnp.float64)
    if col.dtype.oid == TypeOid.DECIMAL64:
        x = x / (10.0 ** col.dtype.scale)
    return x


def _grouped_merge(a: AggCall, p1, p2, gi, mask, mg: int):
    out = {}
    for field, vals in _concat_fields(p1, p2).items():
        m = mask
        if field in ("sum", "count", "sumsq"):
            out[field] = A.seg_sum(vals, gi.gids, m, mg)
        elif field == "min":
            out[field] = A.seg_min(vals, gi.gids, m, mg)
        elif field == "max":
            out[field] = A.seg_max(vals, gi.gids, m, mg)
        elif field == "bits":
            out[field] = _host_bit_reduce(a.func, vals, gi.gids, m, mg)
    return out


def _concat_fields(p1, p2):
    return {k: jnp.concatenate([p1[k], p2[k]]) for k in p1}


def _grouped_empty(a: AggCall, mg: int):
    z64 = jnp.zeros((mg,), jnp.int64)
    if a.func == "count" and a.arg is None:
        return {"count": z64}
    vt = a.arg.dtype.jnp_dtype
    if a.func == "count":
        return {"count": z64}
    if a.func == "sum":
        return {"sum": jnp.zeros((mg,), vt if a.arg.dtype.is_float else jnp.int64),
                "count": z64}
    if a.func == "avg":
        return {"sum": jnp.zeros((mg,), jnp.float64 if a.arg.dtype.is_float
                                 else jnp.int64), "count": z64}
    if a.func in ("min", "max"):
        return {a.func: jnp.zeros((mg,), vt), "count": z64}
    if a.func in STDDEV_AGGS:
        zf = jnp.zeros((mg,), jnp.float64)
        return {"sum": zf, "sumsq": zf, "count": z64}
    if a.func in BIT_AGGS:
        return {"bits": jnp.full((mg,), _BIT_IDENT[a.func], jnp.int64),
                "count": z64}
    raise EvalError(a.func)


def _grouped_final(a: AggCall, part, dtype: DType) -> DeviceColumn:
    valid = part["count"] > 0
    if a.func == "count":
        return DeviceColumn(part["count"], jnp.ones_like(valid), dt.INT64)
    if a.func == "sum":
        s = part["sum"]
        if dtype.oid == TypeOid.DECIMAL64:
            s = s.astype(jnp.int64)
        return DeviceColumn(s.astype(dtype.jnp_dtype), valid, dtype)
    if a.func == "avg":
        s = part["sum"].astype(jnp.float64)
        if a.arg.dtype.oid == TypeOid.DECIMAL64:
            s = s / (10.0 ** a.arg.dtype.scale)
        c = jnp.maximum(part["count"], 1).astype(jnp.float64)
        return DeviceColumn(s / c, valid, dt.FLOAT64)
    if a.func in ("min", "max"):
        return DeviceColumn(part[a.func], valid, dtype)
    if a.func in STDDEV_AGGS:
        c = part["count"].astype(jnp.float64)
        mean = part["sum"] / jnp.maximum(c, 1.0)
        var_pop = jnp.maximum(
            part["sumsq"] / jnp.maximum(c, 1.0) - mean * mean, 0.0)
        if a.func in ("stddev_samp", "var_samp"):
            var = var_pop * c / jnp.maximum(c - 1.0, 1.0)
            ok = part["count"] > 1
        else:
            var = var_pop
            ok = part["count"] > 0
        out = var if a.func in ("variance", "var_pop", "var_samp") \
            else jnp.sqrt(var)
        return DeviceColumn(out, ok, dt.FLOAT64)
    if a.func in BIT_AGGS:
        # MySQL: the neutral value, never NULL (an all-NULL group keeps
        # the identity — bit_and -> all ones)
        bits = part["bits"].astype(jnp.uint64)
        return DeviceColumn(bits, jnp.ones_like(valid), dt.UINT64)
    raise EvalError(a.func)


def _scalar_step_host(a: AggCall, ex: ExecBatch, state):
    """Per-batch scalar partial including the host-side families
    (bitwise aggregates reduce via numpy ufuncs).  The pull loop uses
    this; fused fragments trace `_scalar_step`, which must stay pure —
    the fusion planner never fuses BIT_AGGS."""
    if a.func in BIT_AGGS:
        col = _agg_value(a, ex)
        m = ex.mask & col.validity
        d = np.asarray(jax.device_get(col.data)).astype(np.int64)
        mm = np.asarray(jax.device_get(m))
        v = _BIT_UFUNC[a.func].reduce(d[mm]) if mm.any() \
            else _BIT_IDENT[a.func]
        c = A.scalar_count(m)
        if state is None:
            return (jnp.asarray(np.int64(v)), c)
        merged = _BIT_UFUNC[a.func](
            np.int64(jax.device_get(state[0])), np.int64(v))
        return (jnp.asarray(merged), state[1] + c)
    return _scalar_step(a, ex, state)


def _scalar_step(a: AggCall, ex: ExecBatch, state):
    if a.func == "count" and a.arg is None:
        v = A.scalar_count(ex.mask)
        return v if state is None else state + v
    col = _agg_value(a, ex)
    m = ex.mask & col.validity
    if a.func == "count":
        v = A.scalar_count(m)
        return v if state is None else state + v
    if a.func in ("sum", "avg"):
        s = A.scalar_sum(col.data.astype(jnp.float64)
                         if (a.func == "avg" and col.dtype.is_float)
                         else col.data, m)
        c = A.scalar_count(m)
        if state is None:
            return (s, c)
        return (state[0] + s, state[1] + c)
    if a.func == "min":
        v = A.scalar_min(col.data, m)
        c = A.scalar_count(m)
        return (v, c) if state is None else (jnp.minimum(state[0], v),
                                             state[1] + c)
    if a.func == "max":
        v = A.scalar_max(col.data, m)
        c = A.scalar_count(m)
        return (v, c) if state is None else (jnp.maximum(state[0], v),
                                             state[1] + c)
    if a.func in STDDEV_AGGS:
        x = _float_of(col)
        s = A.scalar_sum(x, m)
        s2 = A.scalar_sum(x * x, m)
        c = A.scalar_count(m)
        if state is None:
            return (s, s2, c)
        return (state[0] + s, state[1] + s2, state[2] + c)
    raise EvalError(a.func)


def _scalar_final(a: AggCall, state, dtype: DType) -> DeviceColumn:
    one = jnp.ones((1,), jnp.bool_)
    if a.func == "count":
        v = jnp.zeros((), jnp.int64) if state is None else state
        return DeviceColumn(v[None].astype(jnp.int64), one, dt.INT64)
    if a.func in BIT_AGGS:
        v = (jnp.asarray(_BIT_IDENT[a.func], jnp.int64) if state is None
             else state[0])
        return DeviceColumn(v[None].astype(jnp.uint64), one, dt.UINT64)
    if a.func in STDDEV_AGGS:
        if state is None:
            return DeviceColumn.const_null(dt.FLOAT64)
        s, s2, c = state
        cf = jnp.maximum(c.astype(jnp.float64), 1.0)
        mean = s / cf
        var_pop = jnp.maximum(s2 / cf - mean * mean, 0.0)
        if a.func in ("stddev_samp", "var_samp"):
            var = var_pop * cf / jnp.maximum(cf - 1.0, 1.0)
            ok = c > 1
        else:
            var = var_pop
            ok = c > 0
        out = var if a.func in ("variance", "var_pop", "var_samp") \
            else jnp.sqrt(var)
        return DeviceColumn(out[None], ok[None], dt.FLOAT64)
    if state is None:
        return DeviceColumn.const_null(dtype)
    if a.func == "sum":
        s, c = state
        return DeviceColumn(s[None].astype(dtype.jnp_dtype), (c > 0)[None], dtype)
    if a.func == "avg":
        s, c = state
        sf = s.astype(jnp.float64)
        if a.arg.dtype.oid == TypeOid.DECIMAL64:
            sf = sf / (10.0 ** a.arg.dtype.scale)
        return DeviceColumn((sf / jnp.maximum(c, 1))[None], (c > 0)[None],
                            dt.FLOAT64)
    v, c = state
    return DeviceColumn(v[None], (c > 0)[None], dtype)


class UnionOp(Operator):
    """UNION ALL: stream children, renaming to the union schema and
    re-encoding string columns into a union-wide dictionary (children's
    dictionaries are per-table and must not collide)."""

    step_span = "op.union"

    def __init__(self, node, children: List[Operator]):
        self.node = node
        self.children = children
        self.schema = node.schema
        self._union_dicts: Dict[str, List[str]] = {}
        self._union_lut: Dict[str, Dict[str, int]] = {}

    def _remap_strings(self, name: str, col: DeviceColumn, src_dict):
        d = self._union_dicts.setdefault(name, [])
        lut = self._union_lut.setdefault(name, {})
        remap = np.empty(max(len(src_dict), 1), np.int32)
        for i, s_ in enumerate(src_dict):
            if s_ not in lut:
                lut[s_] = len(d)
                d.append(s_)
            remap[i] = lut[s_]
        data = jnp.asarray(remap)[jnp.clip(col.data, 0, len(remap) - 1)]
        return DeviceColumn(data, col.validity, col.dtype)

    def execute(self) -> Iterator[ExecBatch]:
        names = [n for n, _ in self.schema]
        for child in self.children:
            child_names = [n for n, _ in child.schema]
            for ex in child.execute():
                cols = {}
                for out_name, (cn, (on, out_t)) in zip(
                        names, zip(child_names, self.schema)):
                    col = ex.batch.columns[cn]
                    if out_t.is_varlen:
                        src = ex.dicts.get(cn, [])
                        col = self._remap_strings(out_name, col, src)
                        col = DeviceColumn(col.data, col.validity, out_t)
                    elif col.dtype.jnp_dtype != out_t.jnp_dtype \
                            and out_t.is_numeric:
                        from matrixone_tpu.ops import scalar as S
                        col = S.cast(col, out_t)
                    cols[out_name] = col
                db = DeviceBatch(columns=cols, n_rows=ex.batch.n_rows)
                yield ExecBatch(
                    batch=db,
                    dicts={n: self._union_dicts[n]
                           for n in self._union_dicts},
                    mask=ex.mask)


# ------------------------------------------------------------- sort / topk

def _collation_ranks(d: List[str]) -> np.ndarray:
    """code -> collation rank of a dictionary: the sort follows string
    order, not the dictionary's insertion order."""
    ranks = np.empty(len(d), dtype=np.int32)
    ranks[np.argsort(np.asarray(d, dtype=object))] = np.arange(len(d))
    return ranks


def _sort_key_col(expr: BoundExpr, ex: ExecBatch) -> DeviceColumn:
    """Evaluate an ORDER BY key; dictionary-coded strings are translated
    code -> collation rank."""
    col = _broadcast_full(eval_expr(expr, ex), ex.padded_len)
    d = _expr_dict(expr, ex)
    if d is not None and col.dtype.is_varlen:
        rank_data = jnp.asarray(_collation_ranks(d))[
            jnp.clip(col.data, 0, len(d) - 1)]
        return DeviceColumn(rank_data, col.validity, dt.INT32)
    return col


def _host_sort_key(expr: BoundExpr, hex_: ExecBatch):
    """`_sort_key_col` over an ExecBatch of numpy arrays whose keys are
    plain columns (vm/result.py checks it) -> (data, validity)."""
    col = hex_.batch.columns[expr.name]
    n = hex_.padded_len
    data = np.broadcast_to(col.data, (n,) + col.data.shape[1:])
    valid = np.broadcast_to(col.validity, (n,))
    d = hex_.dicts.get(expr.name)
    if d is not None and col.dtype.is_varlen:
        data = _collation_ranks(d)[np.clip(data, 0, len(d) - 1)]
    return data, valid


def _host_order(node, hex_: ExecBatch) -> np.ndarray:
    """The live lanes of `hex_` (numpy arrays) in the ORDER BY of `node`
    (a Sort or TopK plan node), ties in lane order: `SortOp.order`'s."""
    keys = [_host_sort_key(k, hex_) for k in node.keys]
    order = msort.host_sort_indices([d for d, _ in keys],
                                    [v for _, v in keys],
                                    node.descendings, hex_.mask)
    return order[:int(np.count_nonzero(hex_.mask))]


def _concat_batches(batches: List[ExecBatch], schema) -> ExecBatch:
    if len(batches) == 1:
        return batches[0]
    names = [n for n, _ in schema]
    cols = {}
    for n in names:
        datas, valids = [], []
        for ex in batches:
            c = _broadcast_full(ex.batch.columns[n], ex.padded_len)
            datas.append(c.data)
            valids.append(c.validity)
        first = batches[0].batch.columns[n]
        cols[n] = DeviceColumn(jnp.concatenate(datas),
                               jnp.concatenate(valids), first.dtype)
    mask = jnp.concatenate([ex.mask for ex in batches])
    n_rows = sum([ex.batch.n_rows for ex in batches])
    dicts = {}
    for ex in batches:
        dicts.update(ex.dicts)
    db = DeviceBatch(columns=cols, n_rows=n_rows.astype(jnp.int32))
    return ExecBatch(batch=db, dicts=dicts, mask=mask)


class SortOp(Operator):
    """ORDER BY.  `order` runs on the device; `host_rows` is the same
    order in numpy, which vm/result.py takes for a statement's last Sort
    over a small input."""

    step_span = "op.sort"

    def __init__(self, node: P.Sort, child: Operator):
        self.node = node
        self.child = child
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        batches = list(self.child.execute())
        if not batches:
            return
        yield self.order(_concat_batches(batches, self.schema))

    def order(self, ex: ExecBatch) -> ExecBatch:
        cols = [_sort_key_col(k, ex) for k in self.node.keys]
        order = msort.sort_indices([c.data for c in cols],
                                   [c.validity for c in cols],
                                   self.node.descendings, ex.mask)
        n_out = jnp.sum(ex.mask.astype(jnp.int32))
        out = F.gather(ex.batch, order, n_out)
        return ExecBatch(batch=out, dicts=ex.dicts, mask=out.row_mask())

    def host_rows(self, hex_: ExecBatch) -> np.ndarray:
        """The lanes of `order`'s output rows, in order."""
        return _host_order(self.node, hex_)


class TopKOp(Operator):
    """ORDER BY ... LIMIT: `order` on the device, `host_rows` the same in
    numpy (see SortOp)."""

    step_span = "op.topk"

    def __init__(self, node: P.TopK, child: Operator):
        self.node = node
        self.child = child
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        batches = list(self.child.execute())
        if not batches:
            return
        yield self.order(_concat_batches(batches, self.schema))

    def order(self, ex: ExecBatch) -> ExecBatch:
        want = self.node.k + self.node.offset
        if len(self.node.keys) == 1:
            key = _sort_key_col(self.node.keys[0], ex)
            k = min(want, ex.padded_len)
            idx, count = msort.top_k_indices(key.data, key.validity,
                                             self.node.descendings[0],
                                             ex.mask, k)
            out = F.gather(ex.batch, idx, jnp.minimum(count, k))
            ex2 = ExecBatch(batch=out, dicts=ex.dicts, mask=out.row_mask())
            # top_k gives the right SET; restore exact ORDER via sort of k rows
            key2 = _sort_key_col(self.node.keys[0], ex2)
            order = msort.sort_indices([key2.data], [key2.validity],
                                       [self.node.descendings[0]], ex2.mask)
            out2 = F.gather(ex2.batch, order, out.n_rows)
        else:
            cols = [_sort_key_col(kx, ex) for kx in self.node.keys]
            order = msort.sort_indices([c.data for c in cols],
                                       [c.validity for c in cols],
                                       self.node.descendings, ex.mask)
            n_out = jnp.minimum(jnp.sum(ex.mask.astype(jnp.int32)), want)
            out2 = F.gather(ex.batch, order[:max(bucket_length(want), 1)],
                            n_out)
        if self.node.offset:
            out2 = _apply_offset(out2, self.node.offset, self.node.k)
        return ExecBatch(batch=out2, dicts=ex.dicts, mask=out2.row_mask())

    def host_rows(self, hex_: ExecBatch) -> np.ndarray:
        """The lanes of `order`'s output rows, in order: the sort cut to
        k + offset, the offset dropped.  `lax.top_k` picks the same rows,
        ties to the lower lane, but for the order of -0.0 and 0.0, which
        SQL leaves open, and the device's sentinel ties (ROADMAP B-M14, a
        defect of the device path)."""
        return _host_order(self.node, hex_)[
            :self.node.k + self.node.offset][self.node.offset:]


def _apply_offset(db: DeviceBatch, offset: int, k: Optional[int]) -> DeviceBatch:
    n = db.padded_len
    idx = jnp.arange(n, dtype=jnp.int32) + offset
    idx = jnp.clip(idx, 0, n - 1)
    remaining = jnp.maximum(db.n_rows - offset, 0)
    if k is not None:
        remaining = jnp.minimum(remaining, k)
    return F.gather(db, idx, remaining)


class SampleOp(Operator):
    """Random sampling (reference: colexec/sample). PERCENT is a streaming
    per-row Bernoulli mask; N ROWS is a single-pass reservoir expressed
    TPU-style as top-N over per-row random keys — the same top_k kernel
    TopK uses, so no per-row host loop and a bounded device footprint."""

    step_span = "op.sample"

    def __init__(self, node: P.Sample, child: Operator):
        self.node = node
        self.child = child
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        rng = np.random.default_rng(self.node.seed)
        if self.node.percent is not None:
            p = self.node.percent / 100.0
            for ex in self.child.execute():
                u = jnp.asarray(rng.random(ex.padded_len,
                                           dtype=np.float32))
                ex.mask = ex.mask & (u < p)
                yield ex
            return
        n = self.node.n_rows
        schema_k = list(self.schema) + [("__sample_key", dt.FLOAT32)]
        winners = None        # running k-row reservoir: O(k + batch) device
        for ex in self.child.execute():
            u = rng.random(ex.padded_len, dtype=np.float32)
            key = jnp.where(ex.mask, jnp.asarray(u), jnp.float32(np.inf))
            kcol = DeviceColumn(key, jnp.ones_like(ex.mask), dt.FLOAT32)
            ex.batch.columns["__sample_key"] = kcol
            merged = ex if winners is None else _concat_batches(
                [winners, ex], schema_k)
            key = merged.batch.columns["__sample_key"]
            k = min(n, merged.padded_len)
            idx, count = msort.top_k_indices(key.data, key.validity, False,
                                             merged.mask, k)
            out = F.gather(merged.batch, idx, jnp.minimum(count, k))
            winners = ExecBatch(batch=out, dicts=dict(merged.dicts),
                                mask=out.row_mask())
        if winners is None:
            return
        del winners.batch.columns["__sample_key"]
        yield winners


class FillOp(Operator):
    """Null-fill of grouped output (reference: colexec/fill). Materializes
    the (small, post-aggregate) child on host, orders rows by the first
    group key, and fills NULLs in non-key columns: PREV carries the last
    non-null value forward, LINEAR interpolates between the surrounding
    non-null values on the order axis, VALUE writes a constant."""

    step_span = "op.fill"

    def __init__(self, node: P.Fill, child: Operator):
        self.node = node
        self.child = child
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        from matrixone_tpu.container import device as dev
        batches = list(self.child.execute())
        if not batches:
            return
        ex = _concat_batches(batches, self.schema)
        cols = [_broadcast_full(ex.batch.columns[name], ex.padded_len)
                for name, _ in self.schema]
        mask, got = jax.device_get((ex.mask,
                                    [(c.data, c.validity) for c in cols]))
        M.device_wait.inc(site="fill")
        mask = np.asarray(mask)
        host, val = {}, {}
        for (name, _), (data, valid) in zip(self.schema, got):
            host[name] = np.asarray(data)[mask]
            val[name] = np.asarray(valid)[mask]
        ocol = self.node.order_col
        odtype = dict(self.schema)[ocol]
        if odtype.is_varlen:
            # order by decoded strings, not dict codes (insertion order)
            d = ex.dicts.get(ocol, [])
            decoded = np.array([d[c] if 0 <= c < len(d) else ""
                                for c in host[ocol]], dtype=object)
            order = np.argsort(decoded, kind="stable")
            # LINEAR has no numeric axis over strings: use row positions
            x = np.arange(len(order), dtype=np.float64)
        else:
            order = np.argsort(host[ocol], kind="stable")
            x = host[ocol][order].astype(np.float64)
        keyset = set(self.node.key_cols)
        for name, dtype in self.schema:
            if name in keyset:
                host[name] = host[name][order]
                val[name] = val[name][order]
                continue
            a = host[name][order].copy()
            v = val[name][order].copy()
            miss = ~v
            if miss.any():
                if self.node.mode == "value":
                    if dtype.is_varlen:
                        raise EvalError("FILL(VALUE) on string column")
                    cv = self.node.const
                    if dtype.oid == TypeOid.DECIMAL64:
                        cv = round(cv * 10 ** dtype.scale)
                    a[miss] = np.asarray(cv).astype(a.dtype)
                    v[:] = True
                elif self.node.mode == "prev":
                    idx = np.where(v, np.arange(len(a)), -1)
                    idx = np.maximum.accumulate(idx)
                    ok = idx >= 0
                    a[ok] = a[np.maximum(idx[ok], 0)]
                    v = ok
                elif self.node.mode == "linear":
                    if dtype.is_varlen:
                        raise EvalError("FILL(LINEAR) on string column")
                    good = np.nonzero(v)[0]
                    if len(good) >= 2:
                        interp = np.interp(x, x[good],
                                           a[good].astype(np.float64))
                        a[miss] = interp[miss].astype(a.dtype)
                        v = np.ones_like(v)
                        # outside the known range np.interp clamps —
                        # matches FILL(LINEAR)'s edge-hold behavior
            host[name] = a
            val[name] = v
        dtypes = {n: (dt.INT32 if d.is_varlen else d)
                  for n, d in self.schema}
        db = dev.from_numpy(host, dtypes, val, n_rows=len(order))
        for name, dtype in self.schema:
            if dtype.is_varlen:
                c = db.columns[name]
                db.columns[name] = DeviceColumn(c.data, c.validity, dtype)
        yield ExecBatch(batch=db, dicts=dict(ex.dicts), mask=db.row_mask())


class LimitOp(Operator):
    step_span = "op.limit"

    def __init__(self, node: P.Limit, child: Operator):
        self.node = node
        self.child = child
        self.schema = node.schema

    def execute(self) -> Iterator[ExecBatch]:
        seen = 0
        off = self.node.offset
        n = self.node.n
        for ex in self.child.execute():
            rank = jnp.cumsum(ex.mask.astype(jnp.int64)) + seen
            keep = ex.mask
            if off:
                keep = keep & (rank > off)
            if n is not None:
                keep = keep & (rank <= off + n)
            batch_rows = int(jax.device_get(jnp.sum(ex.mask.astype(jnp.int64))))
            M.device_wait.inc(site="limit")
            seen += batch_rows
            ex.mask = keep
            yield ex
            if n is not None and seen >= off + n:
                return


class DistinctOp(Operator):
    step_span = "op.distinct"

    def __init__(self, node: P.Distinct, child: Operator,
                 max_groups: int = 65536):
        self.node = node
        self.child = child
        self.schema = node.schema
        self.max_groups = max_groups

    def execute(self) -> Iterator[ExecBatch]:
        batches = list(self.child.execute())
        if not batches:
            return
        ex = _concat_batches(batches, self.schema)
        cols = [_broadcast_full(ex.batch.columns[n], ex.padded_len)
                for n, _ in self.schema]
        gi = A.group_ids([c.data for c in cols], [c.validity for c in cols],
                         ex.mask, self.max_groups)
        ng = int(jax.device_get(gi.num_groups))
        M.device_wait.inc(site="distinct")
        if ng > self.max_groups:
            raise EvalError("DISTINCT cardinality exceeds max_groups")
        out = F.gather(ex.batch, gi.rep_rows, gi.num_groups)
        yield ExecBatch(batch=out, dicts=ex.dicts, mask=out.row_mask())
