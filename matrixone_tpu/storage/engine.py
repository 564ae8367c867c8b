"""MVCC storage engine: versioned segments + tombstones + WAL + checkpoint.

Reference analogue, collapsed to one storage service (the reference splits
this across CN disttae / TN TAE / logservice):

  TAE LSM of appendable->sorted objects     -> committed Segment list
  MVCC commit ts + snapshot reads            -> Segment.commit_ts /
     (tae/txn, txn/client)                      tombstone commit_ts filters
  per-txn workspace (disttae/txn.go:89)      -> txn.client.Workspace merged
                                                into reads
  WAL group commit (tae/logstore)            -> storage.wal CRC-framed log
  checkpoint + replay (tae/db/checkpoint)    -> checkpoint() manifest +
                                                objectio objects, open()
                                                replays ckpt + WAL tail
  logtail push to CN readers                 -> on_commit subscriber
                                                callbacks (feeds CDC)

Single-writer commit pipeline (the TN role): conflict check (first-
committer-wins on row deletes), HLC commit ts, WAL append, apply, notify.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import json
import os
import threading

from matrixone_tpu.utils import san
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container import dtypes as dt
from matrixone_tpu.container.batch import Batch
from matrixone_tpu.container.dtypes import DType, TypeOid
from matrixone_tpu.sql.expr import (BoundCol, BoundExpr, BoundFunc,
                                    BoundLiteral)
from matrixone_tpu.storage import arrowio, objectio, wal as walmod
from matrixone_tpu.storage.fileservice import FileService, MemoryFS
from matrixone_tpu.txn.hlc import HLC

Schema = List[Tuple[str, DType]]

ROWID = "__rowid"


def _host_pair(seg: "Segment", col: str):
    """(data, validity) of one column of a segment for a read by row id:
    the RAM arrays of a fresh segment, or what `LazyColumns.host_pair`
    finds for an object-backed one (no upload)."""
    if seg.is_lazy:
        return seg.arrays.host_pair(col)
    return seg.arrays[col], seg.validity[col]



def schema_to_json(schema: Schema) -> list:
    """One canonical (de)serialization for table schemas — WAL records,
    checkpoint manifests, and external-table defs all share it so a new
    DType field only needs threading through here."""
    return [[c, d.oid.value, d.width, d.scale, d.dim] for c, d in schema]


def schema_from_json(rows) -> Schema:
    return [(c, DType(TypeOid(o), width=w, scale=s, dim=dm))
            for c, o, w, s, dm in rows]


@dataclasses.dataclass
class TableMeta:
    name: str
    schema: Schema
    primary_key: List[str]
    auto_increment: Optional[str] = None   # column name (incrservice)
    not_null: List[str] = dataclasses.field(default_factory=list)
    # partitionservice: segments are split per partition on insert
    partition: "object" = None             # Optional[partition.PartitionSpec]


@dataclasses.dataclass
class IndexMeta:
    name: str
    table: str
    columns: List[str]
    algo: str
    options: dict
    index_obj: object = None
    dirty: bool = False        # table changed since build -> lazy rebuild


@dataclasses.dataclass
class Segment:
    seg_id: int
    commit_ts: int                       # committed segments only
    #: RAM dict (fresh commits) OR blockcache.LazyColumns (object-backed
    #: segments fetched on demand through the byte-budgeted cache) —
    #: both are Mapping[str, np.ndarray], so readers never distinguish
    arrays: Dict[str, np.ndarray]        # varchar columns as int32 codes
    validity: Dict[str, np.ndarray]
    n_rows: int
    base_gid: int
    part_id: int = -1                    # -1 = unpartitioned table
    #: object backing (out-of-core): path of the immutable object this
    #: segment was checkpointed to, and its stored per-column zonemaps
    #: {col: [min, max, null_count]} for fetch-free pruning
    obj_path: Optional[str] = None
    zonemaps: Optional[dict] = None

    @property
    def is_lazy(self) -> bool:
        return not isinstance(self.arrays, dict)


@dataclasses.dataclass
class MergeFence:
    """Snapshot fence: the COMPLETE pre-merge view of one table, pinned
    when merge_table rewrote it (reference: tae keeps merged-away objects
    until GC proves no snapshot/consumer can reach them).  `segments` is
    the full live segment list at the catalog swap (original commit_ts
    preserved), `tombstones` likewise — so AS OF reads below merge_ts and
    delta replays across it stay exact instead of truncating.  Gid ranges
    are never reused (next_gid survives the merge), so a fenced gid
    resolves to exactly one historical segment.  Fences are released
    oldest-first by Engine.gc_fences once nothing can reach them."""
    merge_ts: int
    segments: List[Segment]
    tombstones: List[Tuple[int, np.ndarray]]


class ConflictError(RuntimeError):
    pass


class DuplicateKeyError(RuntimeError):
    pass


class ConstraintError(RuntimeError):
    pass


class MVCCTable:
    """Versioned columnar table; readers see a snapshot, writers buffer in
    a Workspace until the engine commits them."""

    def __init__(self, meta: TableMeta):
        self.meta = meta
        self.segments: List[Segment] = []
        self.tombstones: List[Tuple[int, np.ndarray]] = []  # (commit_ts, gids)
        #: commit TS of the last data change applied to THIS table — the
        #: per-table version the serving result cache keys on (any commit
        #: funnels through apply_segment/apply_tombstones, including WAL
        #: replay and the CN logtail apply, so replicas stay versioned)
        self.last_commit_ts = 0
        #: last merge_table compaction ts (informational; fences below
        #: carry the actual replayable history across merges)
        self.last_merge_ts = 0
        #: snapshot fences, ascending merge_ts: each merge pins the full
        #: pre-merge view so AS OF reads and delta consumers below it
        #: stay exact (released by Engine.gc_fences, oldest first)
        self.fences: List[MergeFence] = []
        #: merge_ts of the NEWEST RELEASED fence — the degrade floor:
        #: a delta resume at or below it lost its history to GC and must
        #: re-seed/rebuild; anything above replays exactly-once
        self.delta_floor = 0
        self.next_gid = 0
        self.next_seg = 0
        self.dicts: Dict[str, List[str]] = {
            c: [] for c, d in meta.schema if d.is_varlen}
        self._dict_idx: Dict[str, Dict[str, int]] = {c: {} for c in self.dicts}
        self.next_auto = 1
        # PK dedup (reference: colexec/fuzzyfilter): a bloom over existing
        # keys answers "definitely new" cheaply; only bloom-positive
        # suspects pay the exact membership check
        self._pk_bloom = None
        self._pk_col: Optional[str] = None
        self._pk_cols: List[str] = []     # composite: hashed key columns
        sd = dict(meta.schema)

        def keyable(d):
            # integer columns directly; varchar via its table-global
            # dictionary codes (stable ints)
            return d is not None and (d.is_integer or d.is_varlen)
        if len(meta.primary_key) == 1:
            if keyable(sd.get(meta.primary_key[0])):
                self._pk_col = meta.primary_key[0]
        elif len(meta.primary_key) > 1:
            if all(keyable(sd.get(c)) for c in meta.primary_key):
                self._pk_cols = list(meta.primary_key)

    @property
    def enforced_key(self) -> List[str]:
        """The declared primary key where `check_pk_unique` holds every
        commit to it, else []: a key over a column that is neither
        integer nor varlen (DATE, DECIMAL, TIMESTAMP) is declared and not
        checked, so nothing may be derived from it."""
        return [self._pk_col] if self._pk_col else list(self._pk_cols)

    def allocate_auto(self, n: int) -> np.ndarray:
        """Allocate n auto_increment values (reference: pkg/incrservice
        cached range allocator — single-process form). Serialized by the
        engine's commit lock so concurrent inserts never collide."""
        with self.engine._commit_lock:
            base = self.next_auto
            self.next_auto += n
        return np.arange(base, base + n, dtype=np.int64)

    def observe_auto(self, values: np.ndarray) -> None:
        if len(values):
            with self.engine._commit_lock:
                self.next_auto = max(self.next_auto,
                                     int(values.max()) + 1)

    @property
    def schema(self) -> Schema:
        return self.meta.schema

    @property
    def n_rows(self) -> int:
        """Committed row count net of tombstones (latest snapshot)."""
        total = sum(s.n_rows for s in self.segments)
        dead = sum(len(g) for _, g in self.tombstones)
        return total - dead

    # -------------------------------------------------------- dict encode
    # Both encoders run under the engine commit lock (reentrant): the
    # check-then-append on the dictionary must not interleave between a
    # session thread and a concurrent committer / the CN logtail
    # consumer — two strings sharing one code is silent data corruption.
    def encode_strings_list(self, col: str, values) -> np.ndarray:
        with self.engine._commit_lock:
            lut, d = self._dict_idx[col], self.dicts[col]
            out = np.zeros(len(values), dtype=np.int32)
            for i, s in enumerate(values):
                if s is None:
                    continue
                code = lut.get(s)
                if code is None:
                    code = len(d)
                    lut[s] = code
                    d.append(s)
                out[i] = code
            return out

    def encode_dict_encoded(self, col: str, de) -> np.ndarray:
        """Remap a batch-local `arrowio.DictEncoded` into table-global
        codes: O(cats) Python under the commit lock, O(n) numpy — the
        vectorized inverse of `to_dict_encoded` (replaces the per-row
        string decode the CN commit path used to pay)."""
        if not de.cats:
            return np.zeros(len(de.codes), np.int32)
        enc = self.encode_strings_list(col, de.cats)
        return np.asarray(enc, np.int32)[np.asarray(de.codes, np.int64)]

    def remap_codes(self, col: str, codes: np.ndarray, cats: List[str]
                    ) -> np.ndarray:
        with self.engine._commit_lock:
            lut, d = self._dict_idx[col], self.dicts[col]
            remap = np.empty(len(cats), dtype=np.int32)
            for i, s in enumerate(cats):
                code = lut.get(s)
                if code is None:
                    code = len(d)
                    lut[s] = code
                    d.append(s)
                remap[i] = code
            return remap[np.asarray(codes, dtype=np.int64)]

    def batch_to_arrays(self, batch: Batch):
        arrays, validity = {}, {}
        for col, dtype in self.meta.schema:
            vec = batch.columns[col]
            validity[col] = vec.valid_mask().copy()
            if dtype.is_varlen:
                arrays[col] = self.encode_strings_list(
                    col, vec.strings.to_pylist())
            else:
                arrays[col] = np.asarray(vec.data, dtype=dtype.np_dtype)
        return arrays, validity

    # ------------------------------------------------------------ pk dedup
    def pk_key_values(self, arrays: Dict[str, np.ndarray]
                      ) -> Optional[np.ndarray]:
        """The (possibly synthetic) int64 key array for PK checking: the
        column itself (varchar via dict codes), or the splitmix-combined
        hash of a composite key — composite hash matches are verified
        against the REAL tuples in check_pk_unique before rejecting."""
        from matrixone_tpu import native
        if self._pk_col is not None:
            if self._pk_col not in arrays:
                return None
            return np.asarray(arrays[self._pk_col], np.int64)
        if self._pk_cols and all(c in arrays for c in self._pk_cols):
            h = None
            with np.errstate(over="ignore"):
                for c in self._pk_cols:
                    hc = native.hash64(np.asarray(arrays[c], np.int64))
                    h = hc if h is None else native._splitmix_np(
                        h ^ (hc + np.uint64(0x9E3779B97F4A7C15)
                             + (h << np.uint64(6)) + (h >> np.uint64(2))))
            return h.view(np.int64)
        return None

    def check_pk_unique(self, arrays: Dict[str, np.ndarray],
                        extra_deletes: Optional[np.ndarray] = None,
                        validity: Optional[np.ndarray] = None) -> None:
        """Raise DuplicateKeyError if the batch collides with existing live
        PK values or contains internal duplicates (fuzzyfilter analogue).
        NULL primary keys are rejected outright (PK implies NOT NULL)."""
        new = self.pk_key_values(arrays)
        if new is None:
            return
        c = self._pk_col or "+".join(self._pk_cols)
        if validity is not None and not validity.all():
            raise DuplicateKeyError(
                f"primary key {self.meta.name!r}.{c} cannot be NULL")
        uniq, counts = np.unique(new, return_counts=True)
        if (counts > 1).any():
            shown = (int(uniq[counts > 1][0]) if self._pk_col is not None
                     and not dict(self.meta.schema)[c].is_varlen
                     else "")
            raise DuplicateKeyError(
                f"duplicate key {shown} within the insert batch for "
                f"{self.meta.name!r}.{c}".replace("key  ", "key "))
        if self._pk_bloom is None:
            self._rebuild_pk_bloom()
        hit = self._pk_bloom.probe_int64(new)
        suspects = new[hit]
        if len(suspects) == 0:
            return
        # a key can only be met in a segment whose range of the key's first
        # column holds it: keys past every loaded one (an append of new
        # orders) fetch and hash no column of the segments below them
        lead = self._pk_col or self._pk_cols[0]
        lead_vals = None
        if not dict(self.meta.schema)[lead].is_varlen:
            lead_vals = np.asarray(arrays[lead], np.int64)[hit]
        dead = self._dead_gids(None, extra_deletes)
        for seg in self.segments:
            if lead_vals is not None and \
                    _key_range_excludes(seg, lead, lead_vals):
                continue
            vals = self.pk_key_values(seg.arrays)
            # vectorized: one alive mask per segment, one membership pass
            gids = np.arange(seg.base_gid, seg.base_gid + seg.n_rows)
            alive = ~np.isin(gids, dead) if len(dead) else \
                np.ones(seg.n_rows, bool)
            live_vals = vals[alive]
            collide = suspects[np.isin(suspects, live_vals)]
            for k in collide:
                if self._pk_col is not None:
                    shown = int(k)
                    if dict(self.meta.schema)[c].is_varlen:
                        d = self.dicts.get(c, [])
                        if 0 <= int(k) < len(d):
                            shown = repr(d[int(k)])
                    raise DuplicateKeyError(
                        f"duplicate key {shown} for "
                        f"{self.meta.name!r}.{c}")
                # composite keys are routed by HASH: verify the real tuple
                # before rejecting (a 2^-64 collision must not block an
                # unrelated insert)
                in_row = int(np.nonzero(new == k)[0][0])
                seg_rows = np.nonzero(alive & (vals == k))[0]
                for r in seg_rows:
                    if all(int(seg.arrays[cc][r]) == int(arrays[cc][in_row])
                           for cc in self._pk_cols):
                        shown = tuple(int(seg.arrays[cc][r])
                                      for cc in self._pk_cols)
                        raise DuplicateKeyError(
                            f"duplicate key {shown} for "
                            f"{self.meta.name!r}.{c}")

    def _rebuild_pk_bloom(self) -> None:
        from matrixone_tpu import native
        n_live = sum(s.n_rows for s in self.segments)
        # headroom so incremental adds don't saturate immediately
        cap = max(n_live * 2, 4096)
        bloom = native.BloomFilter(cap)
        for seg in self.segments:
            vals = self.pk_key_values(seg.arrays)
            if vals is not None:
                bloom.add_int64(vals)
        self._pk_bloom = bloom
        self._pk_bloom_cap = cap
        self._pk_bloom_items = n_live

    def _pk_bloom_add(self, arrays: Dict[str, np.ndarray]) -> None:
        if self._pk_bloom is None:
            return
        vals = self.pk_key_values(arrays)
        if vals is None:
            return
        self._pk_bloom_items += len(vals)
        if self._pk_bloom_items > self._pk_bloom_cap:
            self._pk_bloom = None   # saturated: lazy rebuild with headroom
            return
        self._pk_bloom.add_int64(vals)

    # ----------------------------------------------------------- segments
    def make_segment(self, arrays, validity, commit_ts: int) -> Segment:
        n = len(next(iter(arrays.values())))
        seg = Segment(seg_id=self.next_seg, commit_ts=commit_ts,
                      arrays=arrays, validity=validity, n_rows=n,
                      base_gid=self.next_gid)
        self.next_seg += 1
        self.next_gid += n
        return seg

    def apply_segment(self, seg: Segment) -> None:
        # the single version funnel (commits, WAL replay, CN logtail,
        # trace recorder): PR-4's result-cache correctness pins on every
        # mutation here running under the engine commit lock
        san.mutating(self)
        self.segments.append(seg)
        self.last_commit_ts = max(self.last_commit_ts, seg.commit_ts)

    def insert_segments(self, arrays, validity, commit_ts: int
                        ) -> List[Segment]:
        """Apply an insert batch, splitting rows per partition so each
        segment holds exactly one partition (partitionservice role —
        pruning becomes a structural per-segment skip). Shared by the
        commit pipeline and WAL replay so both produce the same layout."""
        from matrixone_tpu.storage.partition import split_by_partition
        if self.meta.partition is None:
            seg = self.make_segment(arrays, validity, commit_ts)
            self.apply_segment(seg)
            return [seg]
        segs = []
        for pid, pa, pv in split_by_partition(self.meta.partition,
                                              arrays, validity):
            seg = self.make_segment(pa, pv, commit_ts)
            seg.part_id = pid
            self.apply_segment(seg)
            segs.append(seg)
        return segs

    def apply_tombstones(self, commit_ts: int, gids: np.ndarray) -> None:
        if len(gids):
            san.mutating(self)
            self.tombstones.append((commit_ts, np.asarray(gids, np.int64)))
            self.last_commit_ts = max(self.last_commit_ts, commit_ts)

    # --------------------------------------------------------------- read
    def _view_at(self, snapshot_ts: Optional[int]):
        """(segments, tombstones) source lists for a read at snapshot_ts.
        A fence's segments ARE the complete table state at its merge
        point, so a historical read below any fence uses the oldest such
        fence and then applies the ordinary commit_ts <= ts filtering —
        AS OF reads stay bit-identical across a background merge."""
        if snapshot_ts is None:
            return self.segments, self.tombstones
        for f in self.fences:              # ascending merge_ts
            if snapshot_ts < f.merge_ts:
                return f.segments, f.tombstones
        return self.segments, self.tombstones

    def _gid_fence_segment(self, gid: int) -> Optional[Segment]:
        """Owning segment of a gid that no live segment covers (the row
        was compacted away): gid ranges are never reused, so exactly one
        fenced segment can hold it.  Delta replays decode deletes of
        pre-merge rows through this fallback."""
        for f in reversed(self.fences):
            for s in f.segments:           # ascending base_gid
                if s.base_gid > gid:
                    break
                if gid < s.base_gid + s.n_rows:
                    return s
        return None

    def _dead_gids(self, snapshot_ts: Optional[int],
                   extra_deletes: Optional[np.ndarray],
                   tombstones: Optional[list] = None) -> np.ndarray:
        src = self.tombstones if tombstones is None else tombstones
        parts = [g for ts, g in src
                 if snapshot_ts is None or ts <= snapshot_ts]
        if extra_deletes is not None and len(extra_deletes):
            parts.append(np.asarray(extra_deletes, np.int64))
        if not parts:
            return np.zeros(0, np.int64)
        return np.concatenate(parts)

    def iter_chunks(self, columns: List[str], batch_rows: int,
                    filters: Optional[List[BoundExpr]] = None,
                    qualified_names: Optional[List[str]] = None,
                    snapshot_ts: Optional[int] = None,
                    extra_segments: Optional[List[Segment]] = None,
                    extra_deletes: Optional[np.ndarray] = None,
                    only_part: Optional[int] = None
                    ) -> Iterator[tuple]:
        """Yield (arrays, validity, dicts, n, live) merging committed
        segments visible at snapshot_ts with txn-local segments/deletes.
        A chunk keeps its sliced length `n` whatever was deleted from it:
        `live` is None, or a host bool [n] that is False for its dead
        rows, and belongs in the consumer's row mask (`ScanOp`); a reader
        that walks rows on the host takes each chunk through `live_rows`."""
        from matrixone_tpu.utils import metrics as M, motrace
        want_rowid = ROWID in columns
        data_cols = [c for c in columns if c != ROWID]
        src_segs, src_tombs = self._view_at(snapshot_ts)
        dead = self._dead_gids(snapshot_ts, extra_deletes, src_tombs)
        dead_filter = None
        if len(dead) > 0:
            # tombstones as a compressed bitmap built ONCE per scan: a
            # chunk's gids are a contiguous range, so the per-chunk
            # membership test is one container walk instead of an
            # np.isin sort (reference: cgo/croaring.c docfilter role)
            from matrixone_tpu import native
            dead_filter = native.RoaringBitmap(dead)
        segs = [s for s in src_segs
                if snapshot_ts is None or s.commit_ts <= snapshot_ts]
        segs = segs + list(extra_segments or [])
        qmap = dict(zip(qualified_names or columns, columns))
        allowed_parts = None
        if self.meta.partition is not None and filters:
            from matrixone_tpu.storage import partition as partmod
            allowed_parts = partmod.prune(self.meta.partition, filters,
                                          qmap)
        for seg in segs:
            if allowed_parts is not None and seg.part_id >= 0 \
                    and seg.part_id not in allowed_parts:
                continue
            # co-partitioned shard read (vm/operators._hash_route): only
            # this partition's segments; part-less segments still flow
            # and are row-filtered by the caller's hash mask
            if only_part is not None and seg.part_id >= 0 \
                    and seg.part_id != only_part:
                continue
            # object-backed segments: prune on STORED zonemaps before any
            # column fetch — an excluded segment costs zero object-store
            # bytes (readutil block-list prune analogue)
            if filters and seg.zonemaps is not None and \
                    _seg_zonemap_excludes(filters, seg.zonemaps,
                                          seg.n_rows, qmap):
                M.scan_chunks.inc(-(-seg.n_rows // batch_rows),
                                  outcome="pruned_segment")
                continue
            for start in range(0, seg.n_rows, batch_rows):
                end = min(start + batch_rows, seg.n_rows)
                # the span ends before the yield (motrace's rule for
                # generators): the consumer's work is not the scan's
                with motrace.span("scan.chunk", table=self.meta.name,
                                  cols=len(data_cols)):
                    chunk = self._read_chunk(
                        seg, start, end, data_cols, want_rowid,
                        dead_filter, filters, qmap)
                if chunk is not None:
                    yield chunk

    def _read_chunk(self, seg, start: int, end: int, data_cols,
                    want_rowid: bool, dead_filter, filters, qmap):
        """One chunk of one segment: the tombstone test of its row ids,
        column lookups (which fetch, decode and upload what the block
        cache misses), the slice (`_chunk_columns`: one program for all
        the device-resident columns of the chunk, a view of a numpy one)
        and the chunk's own zonemap check.  The columns stay as they were
        sliced: the dead rows go up as `live`, and the check reads min/max
        over dead rows too (an object's summary is kept with the object
        and must not depend on the snapshot; a range over more rows than
        are visible can only prune less).  -> (arrays, validity, dicts, n,
        live), or None when the chunk has nothing to scan."""
        from matrixone_tpu.utils import metrics as M, motrace
        live = None
        if dead_filter is not None:
            live = ~dead_filter.test_range(seg.base_gid + start,
                                           seg.base_gid + end)
            if not live.any():
                M.scan_chunks.inc(outcome="all_dead")
                return None
            if live.all():
                live = None
        arrays, validity = _chunk_columns(seg, start, end, data_cols)
        if filters:
            with motrace.span("scan.zonemap"):
                pruned = _zonemap_excludes(
                    filters, arrays, validity, qmap,
                    dict(self.meta.schema),
                    kept=seg.arrays.chunk_summaries if seg.is_lazy else None,
                    rows=(start, end))
                motrace.annotate(pruned=pruned)
            if pruned:
                M.scan_chunks.inc(outcome="pruned_chunk")
                return None
        n = end - start
        if want_rowid:
            arrays[ROWID] = np.arange(seg.base_gid + start,
                                      seg.base_gid + end, dtype=np.int64)
            validity[ROWID] = np.ones(n, np.bool_)
        n_live = n if live is None else int(live.sum())
        M.scan_chunks.inc(outcome="scanned")
        M.scan_chunk_rows.inc(n_live, state="live")
        if n_live < n:
            M.scan_chunk_rows.inc(n - n_live, state="dead")
        M.scan_chunks_backing.inc(
            backing="object" if seg.is_lazy else "memory")
        motrace.annotate(rows=n_live)
        return arrays, validity, self.dicts, n, live

    def scan_is_cold(self, columns: List[str]) -> bool:
        """True when a scan of `columns` would miss the decoded-column
        cache for at least one object-backed segment — ScanOp enables
        its read-ahead stage only then (a warm scan should not pay a
        prefetch thread)."""
        cols = [c for c in columns if c != ROWID]
        for seg in self.segments:
            if seg.is_lazy and seg.arrays.cold_columns(cols):
                return True
        return False

    def visible_gids(self, gids: np.ndarray,
                     snapshot_ts: Optional[int] = None,
                     extra_deletes: Optional[np.ndarray] = None) -> np.ndarray:
        """Filter gids to rows visible at the snapshot."""
        gids = np.asarray(gids, np.int64)
        return gids[self.visible_mask(gids, snapshot_ts, extra_deletes)]

    def visible_mask(self, gids: np.ndarray,
                     snapshot_ts: Optional[int] = None,
                     extra_deletes: Optional[np.ndarray] = None) -> np.ndarray:
        """True for each gid whose row is visible at the snapshot: owning
        segment committed <= ts and not tombstoned (incl. txn-local
        deletes).  Host arithmetic only."""
        gids = np.asarray(gids, np.int64)
        if len(gids) == 0:
            return np.zeros(0, np.bool_)
        src_segs, src_tombs = self._view_at(snapshot_ts)
        bases = np.array([s.base_gid for s in src_segs], np.int64)
        seg_ts = np.array([s.commit_ts for s in src_segs], np.int64)
        si = np.searchsorted(bases, gids, side="right") - 1
        ok = si >= 0
        if snapshot_ts is not None:
            ok = ok & (seg_ts[np.clip(si, 0, None)] <= snapshot_ts)
        dead = self._dead_gids(snapshot_ts, extra_deletes, src_tombs)
        if len(dead):
            ok = ok & ~np.isin(gids, dead)
        return ok

    def fetch_rows(self, gids: np.ndarray, columns: List[str]):
        """Host gather of rows by global id (vector- and fulltext-index
        result fetch, mview and CDC delta decode).  Returns (arrays,
        validity) as host arrays in gid order, duplicates included.

        What it reads: the gids are grouped by owning segment, and each
        (segment, column) is taken ONCE and its rows gathered with one
        indexing operation.  An object-backed segment's column comes from
        whichever cache tier holds it (host tier first; a device-tier
        array costs one gather and one copy of the gathered rows back),
        else from one decode that is admitted to the host tier alone and
        only where it fits: nothing is uploaded, and a column larger than
        the tier (a 250,000 x 768 vector column) is read without turning
        everything else out.  Gids a merge compacted out of the live list
        resolve through the snapshot fences (gid ranges are never
        reused)."""
        gids = np.asarray(gids, np.int64)
        segs, owner = self._owners(gids)
        order = np.argsort(owner, kind="stable")
        cuts = np.searchsorted(owner[order], np.arange(len(segs) + 1))
        schema = dict(self.meta.schema)
        arrays, validity = {}, {}
        for c in columns:
            out_a = None
            out_v = np.zeros(len(gids), np.bool_)
            for seg, lo, hi in zip(segs, cuts[:-1], cuts[1:]):
                at = order[lo:hi]                # positions in the output
                off = gids[at] - seg.base_gid
                data, valid = _host_pair(seg, c)
                part = np.asarray(data[off])
                if out_a is None:
                    out_a = np.empty((len(gids),) + part.shape[1:],
                                     part.dtype)
                out_a[at] = part
                out_v[at] = np.asarray(valid[off])
            if out_a is None:
                dtype = schema[c]
                shape = (0, dtype.dim) if dtype.is_vector else (0,)
                out_a = np.zeros(shape, np.int32 if dtype.is_varlen
                                 else dtype.np_dtype)
            arrays[c], validity[c] = out_a, out_v
        return arrays, validity

    def _owners(self, gids: np.ndarray):
        """-> (segments, owner): the distinct segments that own `gids`
        and, for each gid, the index of its segment in that list.  Live
        segments by array arithmetic over their gid ranges; a gid no live
        segment covers (compacted away) through the fences."""
        live = self.segments
        bases = np.array([s.base_gid for s in live], np.int64)
        ends = bases + np.array([s.n_rows for s in live], np.int64)
        si = np.searchsorted(bases, gids, side="right") - 1
        fenced = (si < 0) | (gids >= ends[np.clip(si, 0, None)]) \
            if len(live) else np.ones(len(gids), np.bool_)
        every = list(live)
        at = {}                          # id(fenced segment) -> its index
        owner = si.astype(np.int64)
        for i in np.flatnonzero(fenced):
            seg = self._gid_fence_segment(int(gids[i]))
            if seg is None:
                raise KeyError(f"gid {int(gids[i])} not found in "
                               f"{self.meta.name!r} (live or fenced)")
            if id(seg) not in at:
                at[id(seg)] = len(every)
                every.append(seg)
            owner[i] = at[id(seg)]
        used = np.unique(owner)
        return [every[u] for u in used], np.searchsorted(used, owner)

    def read_texts(self, col: str):
        """Decoded visible strings (+ gids) for a varchar column
        (fulltext index build)."""
        dead = self._dead_gids(None, None)
        texts, gids = [], []
        d = self.dicts[col]
        for seg in self.segments:
            g = np.arange(seg.base_gid, seg.base_gid + seg.n_rows,
                          dtype=np.int64)
            keep = ~np.isin(g, dead) if len(dead) else np.ones(
                seg.n_rows, np.bool_)
            codes = seg.arrays[col]
            val = seg.validity[col]
            for i in np.nonzero(keep)[0]:
                texts.append(d[int(codes[i])] if val[i] else None)
                gids.append(int(g[i]))
        return texts, np.asarray(gids, np.int64)

    def read_column_f32(self, col: str):
        """Dense f32 matrix of VISIBLE rows (tombstones excluded) plus the
        gid of each matrix row — index builds must not index deleted rows,
        and search results map back to rows via the gids.  Read on the
        host: an object-backed segment's column comes from the host tier
        or one decode (`LazyColumns.host_pair`), never up to the device
        and back, and each segment is copied straight into the result."""
        d = dict(self.meta.schema)[col].dim
        dead = self._dead_gids(None, None)
        keeps, gids = [], []
        for seg in self.segments:
            g = np.arange(seg.base_gid, seg.base_gid + seg.n_rows,
                          dtype=np.int64)
            keep = ~np.isin(g, dead) if len(dead) else None
            if keep is not None and keep.all():
                keep = None
            keeps.append(keep)
            gids.append(g if keep is None else g[keep])
        if not gids:
            return np.zeros((0, d), np.float32), np.zeros(0, np.int64)
        out = np.empty((sum(len(g) for g in gids), d), np.float32)
        lo = 0
        for seg, keep, g in zip(self.segments, keeps, gids):
            m = np.asarray(_host_pair(seg, col)[0])
            out[lo:lo + len(g)] = m if keep is None else m[keep]
            lo += len(g)
        return out, np.concatenate(gids)

    # -------------------------------------------------- convenience write
    # (autocommit single-statement writes go through the Engine; these are
    # wired by Engine.attach so callers can stay storage-agnostic)
    engine: "Engine" = None

    def insert_batch(self, batch: Batch) -> int:
        arrays, validity = self.batch_to_arrays(batch)
        return self.engine.commit_write(self.meta.name, arrays, validity)

    def insert_numpy(self, arrays, validity=None, strings=None) -> int:
        strings = strings or {}
        full, val = {}, {}
        n = None
        for col, dtype in self.meta.schema:
            if dtype.is_varlen:
                codes, cats = strings[col]
                arr = self.remap_codes(col, codes, cats)
            else:
                arr = np.asarray(arrays[col], dtype=dtype.np_dtype)
            if n is None:
                n = len(arr)
            full[col] = arr
            v = None if validity is None else validity.get(col)
            val[col] = v.copy() if v is not None else np.ones(n, np.bool_)
        return self.engine.commit_write(self.meta.name, full, val)


def live_rows(chunk):
    """A chunk of `iter_chunks` for a reader that walks rows on the host
    (the catalogs, RESTORE, the crash checker): (arrays, validity, dicts,
    n) with the chunk's dead rows dropped by numpy, so that no program's
    shape follows a count of live rows.  A device-resident column is
    fetched whole first: a reader of a large table belongs on `ScanOp`,
    which takes `live` in its row mask."""
    arrays, validity, dicts, n, live = chunk
    if live is not None:
        arrays = {c: np.asarray(a)[live] for c, a in arrays.items()}
        validity = {c: np.asarray(v)[live] for c, v in validity.items()}
        n = int(live.sum())
    return arrays, validity, dicts, n


def _zm_predicates(filters, qmap):
    """Extract (raw_col, op, col_expr, lit) zonemap-usable predicates."""
    out = []
    for f in filters:
        if not (isinstance(f, BoundFunc) and f.op in
                ("lt", "le", "gt", "ge", "eq") and len(f.args) == 2):
            continue
        a, b = f.args
        if isinstance(a, BoundCol) and isinstance(b, BoundLiteral):
            col, lit, op = a, b, f.op
        elif isinstance(b, BoundCol) and isinstance(a, BoundLiteral):
            col, lit = b, a
            op = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le",
                  "eq": "eq"}[f.op]
        else:
            continue
        if col.dtype.is_varlen:
            continue
        out.append((qmap.get(col.name, col.name), op, col, lit))
    return out


def _zm_normalize_lit(col, lit):
    """Literal in the column's STORED units (decimals live scaled);
    None when the comparison can't ride the zonemap."""
    lv = lit.value
    if col.dtype.oid == TypeOid.DECIMAL64:
        lit_scale = (lit.dtype.scale
                     if lit.dtype.oid == TypeOid.DECIMAL64 else 0)
        if lit.dtype.oid == TypeOid.DECIMAL64 or lit.dtype.is_integer:
            lv = lv * 10 ** (col.dtype.scale - lit_scale)
        else:
            return None   # float vs decimal column: kernel decides
    elif lit.dtype.oid == TypeOid.DECIMAL64:
        # decimal literal vs non-decimal column: compare in real units
        lv = lv / 10 ** lit.dtype.scale
    return lv if isinstance(lv, (int, float)) else None


def _zm_range_excludes(op, lo, hi, lv) -> bool:
    if op == "lt":
        return not (lo < lv)
    if op == "le":
        return not (lo <= lv)
    if op == "gt":
        return not (hi > lv)
    if op == "ge":
        return not (hi >= lv)
    return not (lo <= lv <= hi)   # eq


@functools.partial(jax.jit, static_argnames=("length",))
def _slice_rows(cols, start, *, length: int):
    """Rows [start, start + length) of every array of `cols` in one
    program.  `start` is traced and `length` static, so that one program
    serves every full chunk of a segment length and column set and a
    second its ragged tail."""
    return [jax.lax.dynamic_slice_in_dim(a, start, length) for a in cols]


def _chunk_columns(seg, start: int, end: int, data_cols):
    """-> (arrays, validity): rows [start, end) of `data_cols`, no pad.
    The device-resident arrays among them (an object-backed segment served
    by the block cache's device tier) are sliced by ONE dispatch, data
    and validity of every column together, or taken as they are where the
    chunk is its whole segment; a numpy array gives a view."""
    from matrixone_tpu.utils import metrics as M
    arrays, validity = {}, {}
    for c in data_cols:
        # data, then validity, column by column: the order of the block
        # cache's lookups is the order of its LRU, and the tiers are in
        # an equilibrium under it (PERF.md section 6, PR 32)
        arrays[c] = seg.arrays[c]
        validity[c] = seg.validity[c]
    resident = [(d, c) for d in (arrays, validity) for c in data_cols
                if isinstance(d[c], jax.Array)]
    if resident and (start, end) != (0, seg.n_rows):
        cut = _slice_rows(tuple(d[c] for d, c in resident),
                          np.int32(start), length=end - start)
        M.scan_slice_dispatch.inc(how="chunk")
        for (d, c), a in zip(resident, cut):
            d[c] = a
    for d in (arrays, validity):
        for c, a in d.items():
            if not isinstance(a, jax.Array):
                d[c] = a[start:end]
    return arrays, validity


@jax.jit
def _summarize_on_device(datas, vals):
    """(n_valid, min, max over the valid rows) of each column of a chunk,
    in one program; min and max are None for a column that is not 1-d."""
    from matrixone_tpu.ops.agg import _reduce_fill
    out = []
    for d, v in zip(datas, vals):
        lo = hi = None
        if d.ndim == 1:
            lo = jnp.min(jnp.where(v, d, _reduce_fill(d.dtype, True)))
            hi = jnp.max(jnp.where(v, d, _reduce_fill(d.dtype, False)))
        out.append((jnp.sum(v), lo, hi))
    return out


def _summarize_on_host(data: np.ndarray, valid: np.ndarray):
    n_valid = int(np.count_nonzero(valid))
    if n_valid == 0 or data.ndim != 1:
        return n_valid, None, None
    vals = data if n_valid == len(data) else data[valid]
    return n_valid, vals.min().item(), vals.max().item()


def _chunk_summaries(cols, arrays, validity, kept, rows):
    """-> {col: ((n_valid, min, max), source)} for a chunk's predicate
    columns: what `_zonemap_excludes` needs of each, as Python scalars in
    the column's stored units, and where it came from (the `source` of
    `mo_scan_zonemap_checks_total`).

    `kept` is the summary store of the chunk's immutable object
    (`blockcache._ObjectSource.chunk_summaries`; None for a RAM segment)
    and `rows` the chunk's (start, end) in it: an entry found there costs
    a dictionary read.  The columns it lacks are summarized from the
    arrays in hand, numpy ones on the host, device-resident ones together
    in one program and one fetch, and stored.  Scan threads may fill one
    key at once (the prefetch thread of a cold scan beside another
    statement's own): they computed it from the same object, and
    `setdefault` keeps one."""
    from matrixone_tpu.utils import metrics as M
    found, resident = {}, []
    for c in cols:
        got = None if kept is None else kept.get((c,) + rows)
        if got is not None:
            found[c] = (got, "memo")
        elif isinstance(arrays[c], jax.Array):
            resident.append(c)
        else:
            found[c] = (_summarize_on_host(arrays[c],
                                           np.asarray(validity[c])), "host")
    if resident:
        fetched = jax.device_get(_summarize_on_device(
            tuple(arrays[c] for c in resident),
            tuple(validity[c] for c in resident)))
        M.device_wait.inc(site="zonemap")
        for c, (n_valid, lo, hi) in zip(resident, fetched):
            found[c] = ((int(n_valid), None if lo is None else lo.item(),
                         None if hi is None else hi.item()), "device")
    if kept is not None:
        for c, (summary, source) in found.items():
            if source != "memo":
                kept.setdefault((c,) + rows, summary)
    return found


def _zonemap_excludes(filters, arrays, validity, qmap, schema,
                      kept=None, rows=None) -> bool:
    """Chunk-level prune on the chunk's own values: a predicate excludes
    the chunk where its column has no valid row, or where the range of
    the valid rows cannot satisfy it.  For a chunk of an object-backed
    segment (`kept`, `rows`: see `_chunk_summaries`) the numbers are
    computed once, on first use, and kept with the immutable object:
    every later statement checks host scalars, with no program and no
    wait.  A RAM segment's (and an external table's) numpy chunk is
    summarized on the host each time, uncached."""
    from matrixone_tpu.utils import metrics as M
    preds = [p for p in _zm_predicates(filters, qmap) if p[0] in arrays]
    found = _chunk_summaries(dict.fromkeys(p[0] for p in preds),
                             arrays, validity, kept, rows)
    for raw, op, col, lit in preds:
        (n_valid, lo, hi), source = found[raw]
        M.scan_zonemap_checks.inc(source=source)
        if n_valid == 0:
            return True
        if lo is None:
            continue
        lv = _zm_normalize_lit(col, lit)
        if lv is None:
            continue
        if _zm_range_excludes(op, lo, hi, lv):
            return True
    return False


def _key_range_excludes(seg: Segment, col: str, keys: np.ndarray) -> bool:
    """No key of `keys` lies inside the segment's [min, max] of integer
    column `col`: the stored zonemap of an object-backed segment, the
    array's own range for one in RAM; unknown means False."""
    if not seg.n_rows:
        return True
    if seg.zonemaps is not None:
        zm = seg.zonemaps.get(col)
        if zm is None or zm[0] is None or zm[1] is None:
            return False
        lo, hi = zm[0], zm[1]
    elif seg.is_lazy:
        return False
    else:
        a = seg.arrays[col]
        lo, hi = a.min(), a.max()
    return not ((keys >= lo) & (keys <= hi)).any()


def _seg_zonemap_excludes(filters, zonemaps, n_rows, qmap) -> bool:
    """Segment-level prune on STORED zonemaps — decides whether to fetch
    an object's column bytes at all (readutil/reader.go:600 block-list
    prune analogue). zonemaps: {col: [min, max, null_count]}."""
    if not zonemaps:
        return False
    for raw, op, col, lit in _zm_predicates(filters, qmap):
        zm = zonemaps.get(raw)
        if zm is None:
            continue
        lo, hi, nulls = zm[0], zm[1], zm[2]
        if lo is None or hi is None:
            if nulls >= n_rows:
                return True    # all-NULL column can satisfy no comparison
            continue
        lv = _zm_normalize_lit(col, lit)
        if lv is None:
            continue
        if _zm_range_excludes(op, lo, hi, lv):
            return True
    return False


class Engine:
    """Catalog + single-writer commit service + WAL + checkpoint/replay."""

    def __init__(self, fs: Optional[FileService] = None, wal=None):
        from matrixone_tpu import bootstrap as _bootstrap
        self.fs = fs if fs is not None else MemoryFS()
        #: rolling-upgrade stamp (pkg/bootstrap/versions role): fresh
        #: engines are born current; _load_checkpoint overwrites with
        #: the data dir's recorded version and open() migrates up
        self.catalog_version = _bootstrap.CATALOG_VERSION
        # wal: anything with append/truncate/replay — the local CRC log by
        # default, logservice.replicated.ReplicatedLog for the multi-
        # process log role (reference: logservice client behind tae/logstore)
        self.wal = wal if wal is not None else walmod.WalWriter(self.fs)
        self.hlc = HLC()
        self.tables: Dict[str, MVCCTable] = {}
        self.indexes: Dict[str, IndexMeta] = {}
        # RLock: the commit pipeline calls table helpers (observe_auto)
        # that take the lock themselves, and the CN logtail consumer
        # applies whole commit groups under it — same-thread
        # re-acquisition must not deadlock
        self._commit_lock = san.rlock("Engine._commit_lock", category="commit")
        self._subscribers: List[Callable] = []   # logtail analogue
        #: catalog-shape generation: bumped on every DDL (create/drop
        #: table, index, snapshot, partition change). Serving caches key
        #: on it so plans and results never outlive the schema they were
        #: built against; replicas bump via the same methods during
        #: WAL/logtail apply.
        self.ddl_gen = 0
        #: bumped by ANALYZE TABLE (sql/stats.py) — cached plans whose
        #: join order predates a stats refresh re-optimize
        self.stats_gen = 0
        self._ckpt_ts = 0
        self.snapshots: Dict[str, int] = {}      # Git-for-data named points
        self.stages: Dict[str, str] = {}         # CREATE STAGE name -> url
        self.publications: Dict[str, List[str]] = {}   # pub -> tables
        self.sources: set = set()                # SOURCE-marked tables
        self.dynamic_tables: Dict[str, str] = {}  # name -> defining SELECT
        #: last FULLY applied commit: readers snapshot here so a commit
        #: mid-apply (tombstones in, segments not yet) can never tear a read
        self.committed_ts = self.hlc.now()
        from matrixone_tpu.lockservice import LockService
        self.locks = LockService()     # pessimistic mode (pkg/lockservice)
        from matrixone_tpu.vectorindex.cache import IndexCache
        self.index_cache = IndexCache()   # budgeted device-index residency
        self.active_txns = 0           # open explicit txns (merge guard)
        self._pending_merge_records: Dict[str, int] = {}   # name -> merge ts
        #: serializes merge_table's capture->rewrite->swap pipeline (one
        #: merge in flight per engine; commits never take it, so there is
        #: no ordering edge with the commit lock)
        self._merge_lock = san.lock("Engine._merge_lock")
        #: delta-consumer watermark registry (merge_sched GC): consumer
        #: key -> (table, pull-callable returning its watermark ts or
        #: None).  A fence stays pinned while any registered consumer of
        #: its table sits below the merge point.
        self._watermarks: Dict[str, Tuple[str, Callable]] = {}
        #: materialized-view maintenance (matrixone_tpu/mview): flag set
        #: when a system_mview catalog table appears; the service spins
        #: up lazily on the first commit after that
        self._has_mview_catalog = False
        self._mview_service = None
        #: last restart's recovery report (Engine.open fills it; a fresh
        #: engine never recovered anything)
        self.recovery_summary: Optional[dict] = None

    # ----------------------------------------------------------- catalog
    def create_table(self, meta: TableMeta, if_not_exists=False,
                     log=True) -> None:
        if meta.name in self.tables:
            if if_not_exists:
                return
            raise ValueError(f"table {meta.name} already exists")
        t = MVCCTable(meta)
        t.engine = self
        san.guard(t, self._commit_lock, name=f"MVCCTable[{meta.name}]")
        self.tables[meta.name] = t
        self.ddl_gen += 1
        if meta.name == "system_mview" \
                or meta.name.endswith("$system_mview"):
            self._has_mview_catalog = True
        if log:
            self.wal.append({"op": "create_table", "name": meta.name,
                             "ts": self.hlc.now(),
                             "pk": meta.primary_key,
                             "auto": meta.auto_increment,
                             "not_null": meta.not_null,
                             "partition": (meta.partition.to_json()
                                           if meta.partition is not None
                                           else None),
                             "schema": schema_to_json(meta.schema)})

    def drop_table(self, name: str, if_exists=False, log=True) -> None:
        if name not in self.tables:
            if if_exists:
                return
            raise ValueError(f"no such table {name}")
        t = self.tables[name]
        release = getattr(t, "release_cache", None)
        if release is not None:       # external tables free their cache
            release()
        for seg in getattr(t, "segments", []):
            if seg.obj_path is not None:      # free block-cache budget
                from matrixone_tpu.storage import blockcache
                blockcache.CACHE.drop_path(seg.obj_path)
        del self.tables[name]
        self.ddl_gen += 1
        self.sources.discard(name)
        self.dynamic_tables.pop(name, None)
        # publications must not reference dropped tables (a subscriber
        # would abort on the missing table); empty publications vanish
        for pub, tabs in list(self.publications.items()):
            if name in tabs:
                tabs.remove(name)
                if not tabs:
                    del self.publications[pub]
        for k, v in list(self.indexes.items()):
            if v.table == name:
                del self.indexes[k]
                self.index_cache.drop(k)    # free device residency + budget
        if log:
            self.wal.append({"op": "drop_table", "name": name,
                             "ts": self.hlc.now()})

    def create_external(self, meta: TableMeta, location: str, fmt: str,
                        log: bool = True, if_not_exists: bool = False,
                        snapshot=None):
        """Register an external (scan-in-place, read-only) table —
        colexec/external + iceberg roles; see storage/external.py."""
        from matrixone_tpu.storage.external import ExternalTable
        if meta.name in self.tables:
            if if_not_exists:
                return
            raise ValueError(f"table {meta.name} already exists")
        t = ExternalTable(meta, location, fmt, engine=self,
                          snapshot=snapshot)
        self.tables[meta.name] = t
        self.ddl_gen += 1
        if log:
            self.wal.append({"op": "create_external", "name": meta.name,
                             "ts": self.hlc.now(), "snapshot": snapshot,
                             "location": location, "fmt": fmt,
                             "schema": schema_to_json(meta.schema)})

    def create_publication(self, name: str, tables: List[str],
                           log: bool = True) -> None:
        """Durable named table set for cross-cluster sharing (reference:
        mo_pubs; see matrixone_tpu.publication)."""
        for t in tables:
            tab = self.get_table(t)       # must exist
            if getattr(tab, "is_external", False):
                raise ValueError(
                    f"cannot publish external table {t!r}")
        self.publications[name] = list(tables)
        # publications are catalog shape: SHOW PUBLICATIONS / subscriber
        # binds must not serve a cached pre-publication view
        self.ddl_gen += 1
        if log:
            self.wal.append({"op": "create_publication", "name": name,
                             "tables": list(tables), "ts": self.hlc.now()})

    def drop_publication(self, name: str, log: bool = True) -> None:
        if name not in self.publications:
            raise ValueError(f"no such publication {name}")
        del self.publications[name]
        self.ddl_gen += 1
        if log:
            self.wal.append({"op": "drop_publication", "name": name,
                             "ts": self.hlc.now()})

    def mark_source(self, name: str, log: bool = True) -> None:
        self.sources.add(name)
        self.ddl_gen += 1      # SOURCE flag changes stream-DDL binding
        if log:
            self.wal.append({"op": "mark_source", "name": name,
                             "ts": self.hlc.now()})

    def register_dynamic(self, name: str, sql: str,
                         log: bool = True) -> None:
        self.dynamic_tables[name] = sql
        self.ddl_gen += 1
        if log:
            self.wal.append({"op": "create_dynamic", "name": name,
                             "sql": sql, "ts": self.hlc.now()})

    def create_stage(self, name: str, url: str, log: bool = True) -> None:
        """Durable named external location (pkg/stage analogue)."""
        self.stages[name] = url
        # stage URLs are resolved at bind time: a cached plan built
        # against the old mapping would scan the wrong location
        self.ddl_gen += 1
        if log:
            self.wal.append({"op": "create_stage", "name": name,
                             "url": url, "ts": self.hlc.now()})

    def drop_stage(self, name: str, log: bool = True) -> None:
        if name not in self.stages:
            raise ValueError(f"no such stage {name}")
        del self.stages[name]
        self.ddl_gen += 1
        if log:
            self.wal.append({"op": "drop_stage", "name": name,
                             "ts": self.hlc.now()})

    def alter_partition_drop(self, table: str, part: str,
                             log: bool = True) -> None:
        """Remove a RANGE partition definition (rows are tombstoned by the
        caller via a normal delete commit; this only shrinks the spec)."""
        t = self.get_table(table)
        spec = t.meta.partition
        if spec is None or part not in spec.names:
            return
        pid = spec.names.index(part)
        spec.names.pop(pid)
        spec.bounds.pop(pid)
        self.ddl_gen += 1
        # part_ids above the dropped slot shift down; the dropped slot's
        # segments (all rows tombstoned by the caller) become unpartitioned
        # so they are never structurally pruned against the new layout
        for seg in t.segments:
            if seg.part_id == pid:
                seg.part_id = -1
            elif seg.part_id > pid:
                seg.part_id -= 1
        if log:
            self.wal.append({"op": "alter_partition_drop", "table": table,
                             "part": part, "ts": self.hlc.now()})

    def get_table(self, name: str) -> MVCCTable:
        if name not in self.tables:
            raise ValueError(f"no such table {name}")
        return self.tables[name]

    def get_table_meta(self, name: str) -> TableMeta:
        return self.get_table(name).meta

    def register_index(self, meta: IndexMeta) -> None:
        """Catalog an index meta (sessions go through this rather than
        mutating `indexes` directly, so tenant scoping can intercept)."""
        self.indexes[meta.name] = meta
        self.ddl_gen += 1

    def indexes_on(self, table: str) -> List[IndexMeta]:
        return [ix for ix in self.indexes.values() if ix.table == table]

    # --------------------------------------------------- snapshots / PITR
    def create_snapshot(self, name: str) -> int:
        """Named point-in-time (reference: frontend CREATE SNAPSHOT +
        TAE snapshot reads, docs arXiv 2604.03927)."""
        ts = self.hlc.now()
        self.snapshots[name] = ts
        self.ddl_gen += 1
        self.wal.append({"op": "create_snapshot", "name": name, "ts": ts})
        return ts

    def drop_snapshot(self, name: str) -> None:
        self.snapshots.pop(name, None)
        self.ddl_gen += 1
        self.wal.append({"op": "drop_snapshot", "name": name,
                         "ts": self.hlc.now()})

    def restore_table(self, table: str, ts: int) -> int:
        """RESTORE ... FROM SNAPSHOT: one commit replaces the current
        visible rows with the rows visible at ts (reference:
        frontend/data_branch + clone.go restore path)."""
        t = self.get_table(table)
        # materialize the historical view
        parts_a, parts_v = [], []
        cols = [c for c, _ in t.meta.schema]
        for arrays, validity, _dicts, n in map(live_rows, t.iter_chunks(
                cols, 1 << 20, snapshot_ts=ts)):
            parts_a.append(arrays)
            parts_v.append(validity)
        # all currently-visible rows go away
        current = []
        for arrays, validity, _d, n in map(live_rows, t.iter_chunks(
                [ROWID], 1 << 20)):
            current.append(arrays[ROWID])
        cur_gids = (np.concatenate(current) if current
                    else np.zeros(0, np.int64))
        if parts_a:
            merged = {c: np.concatenate([p[c] for p in parts_a])
                      for c in cols}
            merged_v = {c: np.concatenate([p[c] for p in parts_v])
                        for c in cols}
            inserts = {table: [(merged, merged_v)]}
        else:
            inserts = {}
        return self.commit_txn(None, inserts, {table: cur_gids})

    # ------------------------------------------------------- txn registry
    def txn_opened(self, txn_id: int) -> None:
        """An explicit txn opened against this engine (merge guard).
        On a CN, RemoteCatalog overrides this to ALSO register the txn
        with the TN so merges defer cluster-wide (reference: TAE tracks
        active txns centrally because commit runs there)."""
        with self._commit_lock:
            self.active_txns += 1

    def txn_closed(self, txn_id: int) -> None:
        with self._commit_lock:
            self.active_txns -= 1

    def subscribe(self, fn: Callable) -> None:
        """Register a logtail subscriber: fn(commit_ts, table, kind, payload)
        — kind in ('insert','delete'); feeds CDC/index maintenance."""
        self._subscribers.append(fn)

    def unsubscribe(self, fn: Callable) -> None:
        self._subscribers = [f for f in self._subscribers if f is not fn]

    # -------------------------------------- delta-consumer watermarks
    def register_watermark(self, key: str, table: str,
                           fn: Callable) -> None:
        """Register a delta consumer (CDC task, dynamic-table runtime):
        `fn()` returns the consumer's replay watermark ts (or None while
        unseeded).  gc_fences keeps a table's snapshot fences pinned
        while any registered consumer sits below them, so the consumer
        catches up from cdc.delta_events exactly-once instead of
        rebuilding after a compaction."""
        with self._commit_lock:
            self._watermarks[key] = (table, fn)

    def unregister_watermark(self, key: str) -> None:
        with self._commit_lock:
            self._watermarks.pop(key, None)

    def min_watermark(self, table: str) -> Optional[int]:
        """Lowest registered consumer watermark on `table`; None when no
        consumer constrains it (fences release on snapshots alone)."""
        vals = []
        for tbl, fn in list(self._watermarks.values()):
            if tbl != table:
                continue
            try:
                v = fn()
            except Exception:   # noqa: BLE001 — a dead consumer must
                v = None        # not wedge GC; treat as unconstrained
            if v is not None:
                vals.append(int(v))
        return min(vals) if vals else None

    # ------------------------------------------------------------ commit
    def commit_write(self, table: str, arrays, validity) -> int:
        """Autocommit a single-table insert."""
        return self.commit_txn(
            snapshot_ts=None,
            inserts={table: [(arrays, validity)]}, deletes={})

    def commit_txn(self, snapshot_ts: Optional[int],
                   inserts: Dict[str, list],
                   deletes: Dict[str, np.ndarray]) -> int:
        """The TN commit pipeline (tae/rpc/handle.go:547 HandleCommit):
        conflict check -> commit ts -> WAL -> apply -> logtail notify.
        Returns rows affected."""
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils.fault import INJECTOR
        if INJECTOR.trigger("commit.before") == "fail":
            M.txn_commits.inc(outcome="fault")
            raise RuntimeError("injected commit failure")
        with self._commit_lock:
            # normalize: varchar columns may arrive as batch-local
            # DictEncoded (CN-shipped workspaces) — remap to table-global
            # codes before any constraint check sees them
            for tname, segs in inserts.items():
                t = self.get_table(tname)
                varlen = {c for c, d in t.meta.schema if d.is_varlen}
                for arrays, _validity in segs:
                    for c in varlen & set(arrays):
                        if isinstance(arrays[c], arrowio.DictEncoded):
                            arrays[c] = t.encode_dict_encoded(c, arrays[c])
            # write-write conflict: someone deleted my victim after my
            # snapshot (first-committer-wins)
            if snapshot_ts is not None:
                for tname, gids in deletes.items():
                    t = self.get_table(tname)
                    mine = np.asarray(gids, np.int64)
                    newer = [g for ts, g in t.tombstones if ts > snapshot_ts]
                    if newer and len(np.intersect1d(
                            mine, np.concatenate(newer))):
                        M.txn_commits.inc(outcome="conflict")
                        raise ConflictError(
                            f"write-write conflict on {tname}")
            # NOT NULL constraints (PK columns are implicitly NOT NULL
            # via the uniqueness check's NULL rejection)
            for tname, segs in inserts.items():
                t = self.get_table(tname)
                for col in t.meta.not_null:
                    for _a, v in segs:
                        if col in v and not v[col].all():
                            raise ConstraintError(
                                f"column {tname!r}.{col} cannot be NULL")
            # PK uniqueness before anything durable happens; all of a
            # txn's batches are checked as ONE key set so duplicates across
            # statements in the same txn are caught too
            for tname, segs in inserts.items():
                t = self.get_table(tname)
                extra = deletes.get(tname)
                pk_cols = ([t._pk_col] if t._pk_col else t._pk_cols)
                if pk_cols and segs:
                    have = [(a, v) for a, v in segs
                            if all(c in a for c in pk_cols)]
                    if have:
                        combined = {c: np.concatenate(
                            [np.asarray(a[c], np.int64) for a, _v in have])
                            for c in pk_cols}
                        val = np.concatenate([
                            np.logical_and.reduce(
                                [v[c] for c in pk_cols if c in v])
                            if any(c in v for c in pk_cols)
                            else np.ones(len(next(iter(a.values()))),
                                         np.bool_)
                            for a, v in have])
                        t.check_pk_unique(combined, extra_deletes=extra,
                                          validity=val)
            commit_ts = self.hlc.now()
            affected = 0
            # WAL first; varchar columns are logged dictionary-encoded
            # (batch-local codes + categories) so replay re-encodes them
            # into the (rebuilt) table dictionary without per-row decode
            for tname, segs in inserts.items():
                t = self.get_table(tname)
                varlen = {c for c, d in t.meta.schema if d.is_varlen}
                for arrays, validity in segs:
                    wal_arrays = {}
                    for c, a in arrays.items():
                        if c in varlen:
                            wal_arrays[c] = arrowio.to_dict_encoded(
                                t.dicts[c], a, validity[c])
                        else:
                            wal_arrays[c] = a
                    self.wal.append(
                        {"op": "insert", "table": tname, "ts": commit_ts},
                        walmod.arrays_to_arrow(wal_arrays, validity))
            for tname, gids in deletes.items():
                if len(gids):
                    self.wal.append({"op": "delete", "table": tname,
                                     "ts": commit_ts,
                                     "gids": np.asarray(gids).tolist()})
            self.wal.append({"op": "commit", "ts": commit_ts})
            # apply: deletes BEFORE inserts — an UPDATE is delete+insert at
            # one commit ts, and downstream CDC consumers replaying in
            # event order must remove the old row before the new one lands
            # (insert-first would duplicate-key on a PK mirror)
            for tname, gids in deletes.items():
                t = self.get_table(tname)
                t.apply_tombstones(commit_ts, np.asarray(gids, np.int64))
                affected += len(gids)
                for fn in self._subscribers:
                    fn(commit_ts, tname, "delete", gids)
            for tname, segs in inserts.items():
                t = self.get_table(tname)
                for arrays, validity in segs:
                    for seg in t.insert_segments(arrays, validity,
                                                 commit_ts):
                        t._pk_bloom_add(seg.arrays)
                        affected += seg.n_rows
                        for fn in self._subscribers:
                            fn(commit_ts, tname, "insert", seg)
            touched = set(list(inserts) + list(deletes))
            for tname in touched:
                for ix in self.indexes_on(tname):
                    ix.dirty = True
                # UDF and materialized-view definitions live in ordinary
                # tables but ARE catalog shape: a commit touching
                # system_udf / system_mview is DDL — serving caches must
                # not outlive the function/view set they were planned
                # against (matrixone_tpu/udf, matrixone_tpu/mview)
                from matrixone_tpu.udf.catalog import is_udf_table
                if is_udf_table(tname):
                    self.ddl_gen += 1
                from matrixone_tpu.mview.catalog import is_mview_table
                if is_mview_table(tname):
                    self.ddl_gen += 1
            # max(): a materialized-view maintenance commit nested off a
            # post-commit hook mints a NEWER ts than the commit that
            # triggered it — the read frontier must never retreat
            self.committed_ts = max(self.committed_ts, commit_ts)
            M.txn_commits.inc(outcome="ok")
        # post-commit hooks run OUTSIDE the commit lock: materialized-
        # view delta maintenance commits into this SAME engine from
        # here, and doing that mid-apply would tear reads (committed_ts
        # advancing past half-applied segments) — see mview/maintain.py
        self._notify_post_commit(commit_ts, touched)
        return affected

    def _notify_post_commit(self, commit_ts: int, touched: set) -> None:
        """Drive the materialized-view maintenance funnel after a commit
        fully applied.  Lazy: engines without a system_mview catalog pay
        one attribute read per commit."""
        svc = self._mview_service
        if svc is None:
            if not self._has_mview_catalog:
                return
            from matrixone_tpu.mview.maintain import service_for
            svc = service_for(self)
        inner = getattr(self._commit_lock, "_inner", None)
        if inner is not None and inner._is_owned():
            # a re-entrant caller still holds the commit lock (e.g. a
            # handler that wrapped commit_txn): driving maintenance now
            # would invert MViewService._lock against the commit lock
            # (mosan-caught cycle).  The delta is already queued by the
            # subscriber — the next unlocked commit drains it.
            return
        svc.on_commit(commit_ts, touched)

    # ---------------------------------------------------------- compaction
    def merge_table(self, name: str, min_segments: int = 2,
                    checkpoint: bool = True) -> int:
        """Background merge (reference: tae/db/merge scheduler): rewrite a
        table's visible rows into ONE segment (per partition), snapshot-
        FENCING the pre-merge view so AS OF reads and delta consumers
        below the merge stay exact (the fence is released by gc_fences
        once nothing can reach it).

        Three phases so foreground commits are never wedged:
          capture (brief commit lock: pin the segment/tombstone prefix)
          -> rewrite (NO lock: concat live rows, write the merged object
          durable — captured segments are immutable, commits proceed)
          -> swap (brief commit lock: publish merged segment + fence).

        Returns live rows kept, or -1 (too few segments), -2 (open txns
        — their workspaces hold pre-merge gids), -3 (lost the race: a
        concurrent commit deleted a captured row or replaced the table —
        the rewrite is stale; callers retry, foreground always wins)."""
        from matrixone_tpu.utils.fault import INJECTOR
        with self._merge_lock:
            return self._merge_table_locked(name, min_segments,
                                            checkpoint, INJECTOR)

    def _merge_table_locked(self, name, min_segments, checkpoint,
                            INJECTOR) -> int:
        import time as _time
        from matrixone_tpu.utils import metrics as M
        # --- capture (brief lock): pin the prefix the rewrite covers
        with self._commit_lock:
            if self.active_txns > 0:
                return -2
            t = self.get_table(name)
            if len(t.segments) < min_segments:
                return -1
            cap_segs = list(t.segments)
            cap_tombs = list(t.tombstones)
            cap_gid = t.next_gid
        # --- rewrite (no lock): captured segments/tombstones are
        # immutable once committed; concurrent commits only APPEND
        t0 = _time.perf_counter()
        if INJECTOR.trigger("merge.rewrite"):
            raise RuntimeError("injected fault: merge.rewrite")
        cols = [c for c, _ in t.meta.schema]
        parts_a = {c: [] for c in cols}
        parts_v = {c: [] for c in cols}
        dead = t._dead_gids(None, None, cap_tombs)
        dead_filter = None
        if len(dead):
            from matrixone_tpu import native
            dead_filter = native.RoaringBitmap(dead)
        kept = 0
        for seg in cap_segs:
            keep = ~dead_filter.test_range(
                seg.base_gid, seg.base_gid + seg.n_rows) \
                if dead_filter is not None else np.ones(
                    seg.n_rows, np.bool_)
            if not keep.any():
                continue
            for c in cols:
                parts_a[c].append(np.asarray(seg.arrays[c])[keep])
                parts_v[c].append(np.asarray(seg.validity[c])[keep])
            kept += int(keep.sum())
        arrays = validity = None
        obj_path = zms_json = None
        if kept:
            arrays = {c: np.concatenate(parts_a[c]) for c in cols}
            validity = {c: np.concatenate(parts_v[c]) for c in cols}
            if t.meta.partition is None:
                # write the merged object BEFORE the swap publishes it:
                # the heavy IO runs outside the commit lock, and crash
                # ordering gets a real decision point (rewrite durable
                # -> swap -> manifest).  Partitioned tables re-split at
                # swap and stay RAM until the next checkpoint.
                obj_path, zms_json = self._merge_write_object(
                    name, arrays, validity)
        M.merge_seconds.inc(_time.perf_counter() - t0, phase="rewrite")
        # --- swap (brief lock): publish merged segment + fence history
        t0 = _time.perf_counter()
        if INJECTOR.trigger("merge.swap"):
            raise RuntimeError("injected fault: merge.swap")
        with self._commit_lock:
            if self.tables.get(name) is not t:
                return -3          # dropped/replaced during the rewrite
            if self.active_txns > 0:
                return -2
            if len(t.segments) < len(cap_segs) or any(
                    a is not b for a, b in zip(t.segments, cap_segs)):
                return -3          # prefix rewritten under us (restore)
            new_tombs = t.tombstones[len(cap_tombs):]
            if any(len(g) and int(g.min()) < cap_gid
                   for _, g in new_tombs):
                # a concurrent commit deleted a row the rewrite kept as
                # live — stale rewrite; defer (the scheduler retries)
                return -3
            merge_ts = self.hlc.now()
            # the fence pins the COMPLETE pre-swap view: captured
            # segments plus any committed during the rewrite (those stay
            # live too — windowed delta replay emits them exactly once
            # from whichever side covers their commit_ts)
            fence = MergeFence(merge_ts=merge_ts,
                               segments=list(t.segments),
                               tombstones=list(t.tombstones))
            post = t.segments[len(cap_segs):]
            san.mutating(t)
            t.segments = list(post)
            t.tombstones = list(new_tombs)
            if kept:
                if t.meta.partition is None:
                    seg = t.make_segment(arrays, validity, merge_ts)
                    seg.obj_path = obj_path
                    seg.zonemaps = zms_json
                    t.apply_segment(seg)
                else:
                    # partitioned tables re-split so the merged layout
                    # keeps one-partition-per-segment (structural
                    # pruning invariant)
                    t.insert_segments(arrays, validity, merge_ts)
            t.fences.append(fence)
            t.last_commit_ts = max(t.last_commit_ts, merge_ts)
            t.last_merge_ts = merge_ts
            t._pk_bloom = None     # rebuilt lazily over the merged rows
            self.committed_ts = max(self.committed_ts, merge_ts)
            for ix in self.indexes_on(name):
                ix.dirty = True       # gids changed: indexes must rebuild
            # merge rewrites gids, which invalidates CN replicas built
            # from the logtail — queue the announcement; _checkpoint_locked
            # emits it AFTER the manifest is durable so a consumer
            # resyncing the table reads post-merge state.  Batched-merge
            # callers (checkpoint=False + one checkpoint()) get their
            # records at that later checkpoint — same ordering guarantee.
            self._pending_merge_records[name] = merge_ts
            # durability: the merged state IS the new truth — checkpoint
            # so replay never resurrects pre-merge rows (the fence rides
            # the manifest, so pre-merge history stays reachable)
            if checkpoint:
                self._checkpoint_locked()
        M.merge_seconds.inc(_time.perf_counter() - t0, phase="swap")
        M.merge_rows.inc(kept)
        M.merge_segments.inc(len(cap_segs))
        return kept

    def _merge_write_object(self, name: str, arrays, validity):
        """Write the merged rows as a durable object before the swap
        references them (plant hook: tools/mocrash monkeypatches this to
        re-introduce the swap-before-rewrite-durable ordering bug)."""
        zms = objectio.compute_zonemaps(arrays, validity)
        n = len(next(iter(arrays.values())))
        meta = objectio.ObjectMeta(
            table=name, object_id=f"merge{self.hlc.now()}",
            n_rows=n, commit_ts=0, zonemaps=zms)
        path = objectio.write_object(self.fs, meta, arrays, validity)
        return path, {c: [z.min, z.max, z.null_count]
                      for c, z in zms.items()}

    #: plant hook (tools/mocrash/plants.py): re-introduce the GC-before-
    #: fence-release ordering bug — old objects deleted BEFORE the
    #: fence-free manifest is durable, so a crash in between leaves a
    #: manifest referencing vanished files
    GC_DELETE_BEFORE_FENCE_RELEASE = False

    def gc_fences(self, tables: Optional[List[str]] = None) -> dict:
        """Release snapshot fences nothing can reach: a fence is held
        while any named snapshot or registered consumer watermark of its
        table sits below its merge point; releases go oldest-first so
        the delta floor stays monotone.  Crash ordering: the fence-free
        manifest is made durable FIRST, old object files deleted only
        after — a crash in between leaves unreferenced files (a harmless
        leak), never a reachable-but-deleted object."""
        from matrixone_tpu.utils import metrics as M
        released: List[Tuple[str, MergeFence]] = []
        with self._commit_lock:
            names = list(self.tables) if tables is None else tables
            for name in names:
                t = self.tables.get(name)
                if t is None or not t.fences:
                    continue
                wm = self.min_watermark(name)
                while t.fences:
                    f = t.fences[0]
                    if any(ts < f.merge_ts
                           for ts in self.snapshots.values()):
                        break          # snapshot-pinned
                    if wm is not None and wm < f.merge_ts:
                        break          # a consumer still replays below
                    t.fences.pop(0)
                    t.delta_floor = max(t.delta_floor, f.merge_ts)
                    released.append((name, f))
            if not released:
                return {"released": 0, "objects_deleted": 0}
            # paths still referenced by live segments or surviving
            # fences (post-capture segments are shared) must survive
            live_paths = {s.obj_path for t2 in self.tables.values()
                          for s in t2.segments}
            live_paths |= {s.obj_path for t2 in self.tables.values()
                           for f2 in t2.fences for s in f2.segments}
            dead_paths = sorted(
                {s.obj_path for _, f in released for s in f.segments
                 if s.obj_path is not None} - live_paths)
            if Engine.GC_DELETE_BEFORE_FENCE_RELEASE:
                for p in dead_paths:     # planted bug: delete-first
                    if self.fs.exists(p):
                        self.fs.delete(p)
            if self.fs.exists("meta/manifest.json") or \
                    self._pending_merge_records:
                self._checkpoint_locked()
        from matrixone_tpu.storage import blockcache
        n_del = 0
        for p in dead_paths:
            blockcache.CACHE.drop_path(p)
            if not Engine.GC_DELETE_BEFORE_FENCE_RELEASE \
                    and self.fs.exists(p):
                self.fs.delete(p)
                n_del += 1
        M.merge_fences_released.inc(len(released))
        M.merge_gc_objects.inc(n_del)
        return {"released": len(released), "objects_deleted": n_del}

    # ------------------------------------------------- checkpoint / open
    def checkpoint(self, demote: Optional[bool] = None) -> None:
        """Write all committed state as objectio objects + manifest, then
        truncate the WAL (tae/db/checkpoint/runner.go analogue). Runs under
        the commit lock so a concurrent commit cannot slip between the
        manifest snapshot and the WAL truncation and be lost.

        demote=True turns freshly-durable RAM segments into object-backed
        views served through the blockcache (default: MO_LAZY_SEGMENTS)."""
        with self._commit_lock:
            self._checkpoint_locked(demote=demote)

    def _checkpoint_locked(self, demote: Optional[bool] = None) -> None:
        manifest = {"ckpt_ts": self.hlc.now(), "tables": {},
                    "catalog_version": getattr(self, "catalog_version",
                                               None) or 1,
                    "snapshots": dict(self.snapshots),
                    "stages": dict(self.stages), "externals": {},
                    "publications": {k: list(v) for k, v
                                     in self.publications.items()},
                    "sources": sorted(self.sources),
                    "dynamic_tables": dict(self.dynamic_tables)}
        for name, t in self.tables.items():
            if getattr(t, "is_external", False):
                manifest["externals"][name] = {
                    "location": t.location, "fmt": t.fmt,
                    "snapshot": getattr(t, "snapshot", None),
                    "schema": schema_to_json(t.meta.schema)}
                continue
            objs = []
            for seg in t.segments:
                if seg.obj_path is None:
                    # fresh segment: write its object ONCE; later
                    # checkpoints reuse it (incremental checkpoints —
                    # the reference's ickp; a full-db rewrite per
                    # checkpoint would also defeat out-of-core reads by
                    # pulling every cold block back through the cache)
                    zms = objectio.compute_zonemaps(seg.arrays,
                                                    seg.validity)
                    meta = objectio.ObjectMeta(
                        table=name, object_id=f"seg{seg.seg_id}",
                        n_rows=seg.n_rows, commit_ts=seg.commit_ts,
                        zonemaps=zms)
                    seg.obj_path = objectio.write_object(
                        self.fs, meta, seg.arrays, seg.validity)
                    seg.zonemaps = {c: [z.min, z.max, z.null_count]
                                    for c, z in zms.items()}
                    if demote or (demote is None and os.environ.get(
                            "MO_LAZY_SEGMENTS") == "1"):
                        # demote the freshly-durable segment to an
                        # object-backed view: the WRITER's RAM is then
                        # bounded by the block cache too (the reference
                        # TN flushes memtables to objects the same way)
                        from matrixone_tpu.storage import blockcache
                        cols = [c for c, _ in t.meta.schema]
                        seg.arrays, seg.validity = blockcache.lazy_pair(
                            self.fs, seg.obj_path, cols)
                objs.append({"path": seg.obj_path, "seg_id": seg.seg_id,
                             "base_gid": seg.base_gid,
                             "commit_ts": seg.commit_ts,
                             "part_id": seg.part_id,
                             "n_rows": seg.n_rows,
                             "zonemaps": seg.zonemaps})
            # snapshot fences ride the manifest: pre-merge history stays
            # reachable across restart until gc_fences releases it.
            # Segments shared with the live list (committed during a
            # rewrite) reuse the object just written above; RAM-only
            # fenced segments get their object here, exactly once.
            fences = []
            for f in t.fences:
                fobjs = []
                for seg in f.segments:
                    if seg.obj_path is None:
                        zms = objectio.compute_zonemaps(seg.arrays,
                                                        seg.validity)
                        ometa = objectio.ObjectMeta(
                            table=name, object_id=f"seg{seg.seg_id}",
                            n_rows=seg.n_rows, commit_ts=seg.commit_ts,
                            zonemaps=zms)
                        seg.obj_path = objectio.write_object(
                            self.fs, ometa, seg.arrays, seg.validity)
                        seg.zonemaps = {c: [z.min, z.max, z.null_count]
                                        for c, z in zms.items()}
                    fobjs.append({"path": seg.obj_path,
                                  "seg_id": seg.seg_id,
                                  "base_gid": seg.base_gid,
                                  "commit_ts": seg.commit_ts,
                                  "part_id": seg.part_id,
                                  "n_rows": seg.n_rows,
                                  "zonemaps": seg.zonemaps})
                fences.append({"merge_ts": f.merge_ts, "objects": fobjs,
                               "tombstones": [[ts, g.tolist()]
                                              for ts, g in f.tombstones]})
            manifest["tables"][name] = {
                "schema": schema_to_json(t.meta.schema),
                "pk": t.meta.primary_key,
                "auto": t.meta.auto_increment,
                "not_null": t.meta.not_null,
                "dicts": t.dicts,
                "objects": objs,
                "tombstones": [[ts, g.tolist()] for ts, g in t.tombstones],
                "next_gid": t.next_gid, "next_seg": t.next_seg,
                "next_auto": t.next_auto,
                "partition": (t.meta.partition.to_json()
                              if t.meta.partition is not None else None),
                "fences": fences,
                "delta_floor": t.delta_floor,
            }
        self.fs.write("meta/manifest.json",
                      json.dumps(manifest).encode())
        self.wal.truncate()
        self._ckpt_ts = manifest["ckpt_ts"]
        # announce merges only once their post-merge manifest is durable
        # (CN replicas resync the table from it)
        for nm, ts in self._pending_merge_records.items():
            self.wal.append({"op": "merge_table", "name": nm, "ts": ts})
        self._pending_merge_records = {}

    def close(self) -> None:
        """Orderly shutdown hook: flush the statement recorder's tail
        (flush_every buffering would otherwise silently drop the last
        <64 statements of a session when the process exits).  Idempotent
        and safe to call on an engine that never recorded anything."""
        rec = getattr(self, "stmt_recorder", None)
        if rec is not None:
            rec.flush()

    @classmethod
    def open(cls, fs: FileService, wal=None) -> "Engine":
        """Restart path: load last checkpoint then replay the WAL tail
        (tae/db/replay.go analogue).  Emits a recovery summary — frames
        replayed, torn-tail bytes discarded, checkpoint ts, orphan tmp
        files GC'd — as `eng.recovery_summary`, the `mo_recovery_*`
        metrics and a motrace `engine.recover` span: a restart that
        silently dropped a torn tail or swept crash leftovers must be
        observable (the mocrash sweep asserts on it)."""
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils import motrace
        eng = cls(fs, wal=wal)
        # restart replay is one big commit-group apply: run it under the
        # commit lock like every other writer through the version funnel.
        # Reading the quorum WAL tail does socket I/O — that is the
        # restart protocol itself (nobody else can hold this brand-new
        # engine's lock yet), not a blocking-under-lock hazard
        with motrace.root_span("engine.recover"):
            with eng._commit_lock:
                with san.allow_blocking(
                        "startup WAL replay: quorum reads under the commit "
                        "lock ARE the restart protocol; the engine is not "
                        "yet shared"):
                    eng._load_checkpoint()
                    wal_stats = eng._replay_wal()
            # crash-leftover `*.tmp` files (a writer died between its
            # tmp fsync and the atomic replace) are invisible to
            # readers but leak disk forever — GC them at startup, the
            # one moment no writer can be mid-protocol
            orphans = eng.fs.orphans()
            for p in orphans:
                eng.fs.delete(p)
            eng.recovery_summary = {
                "frames_replayed": wal_stats.get("frames", 0),
                "torn_bytes": wal_stats.get("torn_bytes", 0),
                "ckpt_ts": eng._ckpt_ts,
                "orphans_gcd": len(orphans)}
            M.recovery_frames.inc(wal_stats.get("frames", 0))
            M.recovery_torn_bytes.inc(wal_stats.get("torn_bytes", 0))
            M.recovery_orphans.inc(len(orphans))
            motrace.annotate(**eng.recovery_summary)
        eng.committed_ts = eng.hlc.now()
        # rolling catalog upgrades (pkg/bootstrap/versions role): an
        # old data dir gains the newer system tables in place
        from matrixone_tpu import bootstrap
        bootstrap.upgrade(eng)
        return eng

    @classmethod
    def open_checkpoint(cls, fs: FileService) -> "Engine":
        """CN bootstrap path: base state = last checkpoint manifest +
        objects ONLY — the WAL tail belongs to the TN and reaches a CN as
        the logtail stream, never by reading the log directly
        (disttae/logtail_consumer.go:296 subscribes from the replayed
        checkpoint ts). The replica never appends: its wal is a no-op."""
        eng = cls(fs, wal=_NullWal())
        with eng._commit_lock:
            eng._load_checkpoint()
        eng.committed_ts = max(eng._ckpt_ts, eng.committed_ts)
        return eng

    def _load_checkpoint(self) -> None:
        fs = self.fs
        if not fs.exists("meta/manifest.json"):
            return
        manifest = json.loads(fs.read("meta/manifest.json").decode())
        self._ckpt_ts = manifest.get("ckpt_ts", 0)
        self.catalog_version = manifest.get("catalog_version", 1)
        self.snapshots = dict(manifest.get("snapshots", {}))
        self.stages = dict(manifest.get("stages", {}))
        self.publications = {k: list(v) for k, v in
                             manifest.get("publications", {}).items()}
        self.sources = set(manifest.get("sources", []))
        self.dynamic_tables = dict(manifest.get("dynamic_tables", {}))
        self.hlc.update(self._ckpt_ts)
        for name, ex in manifest.get("externals", {}).items():
            schema = schema_from_json(ex["schema"])
            self.create_external(TableMeta(name, schema, []),
                                 ex["location"], ex["fmt"], log=False,
                                 snapshot=ex.get("snapshot"))
        for name, tm in manifest["tables"].items():
            self._load_manifest_table(name, tm)

    def _load_manifest_table(self, name: str, tm: dict,
                             replace: bool = False) -> None:
        """Materialize one table from its manifest entry (open path; also
        the CN resync path after a TN merge rewrote gids)."""
        from matrixone_tpu.storage.partition import PartitionSpec
        schema = schema_from_json(tm["schema"])
        if replace:
            self.tables.pop(name, None)
        self.create_table(
            TableMeta(name, schema, tm["pk"],
                      auto_increment=tm.get("auto"),
                      not_null=tm.get("not_null", []),
                      partition=PartitionSpec.from_json(
                          tm.get("partition"))),
            log=False)
        t = self.get_table(name)
        t.dicts = {k: list(v) for k, v in tm["dicts"].items()}
        t._dict_idx = {k: {s_: i for i, s_ in enumerate(v)}
                       for k, v in t.dicts.items()}
        cols = [c for c, _ in schema]
        for ob in tm["objects"]:
            # OUT-OF-CORE load: segments reference their objects; column
            # bytes are fetched on demand through the process-wide
            # byte-budgeted BlockCache (VERDICT r4 Missing #1 — the
            # database no longer has to fit in host RAM, and a CN
            # replica holds metadata + whatever the cache keeps warm)
            from matrixone_tpu.storage import blockcache
            zms = ob.get("zonemaps")
            n_rows = ob.get("n_rows")
            if n_rows is None:     # pre-r5 manifest: one header read
                ometa, raw = objectio.read_header_ranged(
                    self.fs, ob["path"])
                n_rows = ometa.n_rows
                zms = {c: [z.min, z.max, z.null_count]
                       for c, z in ometa.zonemaps.items()}
            arrays, validity = blockcache.lazy_pair(
                self.fs, ob["path"], cols)
            seg = Segment(seg_id=ob["seg_id"],
                          commit_ts=ob["commit_ts"],
                          arrays=arrays, validity=validity,
                          n_rows=n_rows,
                          base_gid=ob["base_gid"],
                          part_id=ob.get("part_id", -1),
                          obj_path=ob["path"], zonemaps=zms)
            t.apply_segment(seg)
        t.tombstones = [(ts, np.asarray(g, np.int64))
                        for ts, g in tm["tombstones"]]
        # snapshot fences: pre-merge history loads lazily (object-backed
        # through the block cache) so holding history costs no RAM
        from matrixone_tpu.storage import blockcache as _bc
        for fj in tm.get("fences", []):
            fsegs = []
            for ob in fj["objects"]:
                arrays, validity = _bc.lazy_pair(self.fs, ob["path"],
                                                 cols)
                fsegs.append(Segment(
                    seg_id=ob["seg_id"], commit_ts=ob["commit_ts"],
                    arrays=arrays, validity=validity,
                    n_rows=ob["n_rows"], base_gid=ob["base_gid"],
                    part_id=ob.get("part_id", -1),
                    obj_path=ob["path"], zonemaps=ob.get("zonemaps")))
            t.fences.append(MergeFence(
                merge_ts=fj["merge_ts"], segments=fsegs,
                tombstones=[(ts, np.asarray(g, np.int64))
                            for ts, g in fj["tombstones"]]))
        t.delta_floor = tm.get("delta_floor", 0)
        t.next_gid = tm["next_gid"]
        t.next_seg = tm["next_seg"]
        # incrservice state: older manifests predate the field —
        # fall back to scanning the committed auto column
        if "next_auto" in tm:
            t.next_auto = tm["next_auto"]
        elif t.meta.auto_increment:
            for seg in t.segments:
                t.observe_auto(seg.arrays[t.meta.auto_increment][
                    seg.validity[t.meta.auto_increment]])

    def _replay_wal(self) -> dict:
        stats: dict = {"frames": 0, "torn_bytes": 0}
        ap = WalApplier(self, skip_ts=self._ckpt_ts)
        try:
            frames = self.wal.replay(stats=stats)
        except TypeError:
            # a wal duck predating the stats hook (LogtailHub wrappers,
            # test doubles): replay without the summary, count frames
            frames = self.wal.replay()
        n = 0
        for header, blob in frames:
            ap.apply(header, blob)
            n += 1
        stats.setdefault("frames", n)
        stats["frames"] = max(stats["frames"], n)
        self.hlc.update(ap.max_ts)
        return stats


class _NullWal:
    """WAL of a CN replica: a replica never logs — durability is the TN's
    job; the replica's mutations all ARRIVE from the TN's log."""

    def append(self, header: dict, arrow_blob: bytes = b"") -> None:
        pass

    def truncate(self) -> None:
        pass

    def replay(self, stats=None):
        return iter(())


class WalApplier:
    """Applies WAL-format records to an engine one at a time.

    Shared by the restart replay (`Engine._replay_wal`) and the CN
    logtail consumer (`matrixone_tpu.cluster`): the TN's WAL record
    stream IS the logtail (reference: tae/logtail derives the push
    stream from the commit pipeline, logtail/service/server.go:192).
    Insert/delete records buffer until their commit record; catalog
    records apply immediately. `apply` returns the commit_ts when a
    commit was applied, else None."""

    def __init__(self, eng: "Engine", skip_ts: int = 0):
        self.eng = eng
        self.skip_ts = skip_ts
        self.pending: List[tuple] = []
        self.max_ts = skip_ts

    def apply(self, header: dict, blob: bytes = b""):
        eng = self.eng
        op = header["op"]
        # frames at or before the checkpoint are already materialized in
        # the manifest (crash window between manifest write and WAL
        # truncation) — skip them
        hts = header.get("ts", 0)
        if hts and hts <= self.skip_ts:
            return None
        if op == "create_table":
            from matrixone_tpu.storage.partition import PartitionSpec
            schema = schema_from_json(header["schema"])
            eng.create_table(
                TableMeta(header["name"], schema, header["pk"],
                          auto_increment=header.get("auto"),
                          not_null=header.get("not_null", []),
                          partition=PartitionSpec.from_json(
                              header.get("partition"))),
                log=False, if_not_exists=True)
        elif op == "drop_table":
            eng.drop_table(header["name"], if_exists=True, log=False)
        elif op == "alter_partition_drop":
            eng.alter_partition_drop(header["table"], header["part"],
                                     log=False)
        elif op == "create_external":
            schema = schema_from_json(header["schema"])
            eng.create_external(TableMeta(header["name"], schema, []),
                                header["location"], header["fmt"],
                                log=False, if_not_exists=True,
                                snapshot=header.get("snapshot"))
        # catalog-shape ops route through the Engine methods (log=False)
        # so the replica's ddl_gen advances exactly like the TN's — a
        # direct container write here left CN plan/result caches
        # serving plans pinned to the pre-DDL shape (molint
        # cache-invalidation's replica-path hole, review round 4)
        elif op == "create_stage":
            eng.create_stage(header["name"], header["url"], log=False)
        elif op == "drop_stage":
            if header["name"] in eng.stages:     # replay-idempotent
                eng.drop_stage(header["name"], log=False)
        elif op == "create_publication":
            eng.publications[header["name"]] = list(header["tables"])
            eng.ddl_gen += 1     # direct: the method re-validates
            #                      member tables, which replay skips
        elif op == "drop_publication":
            if header["name"] in eng.publications:   # replay-idempotent
                del eng.publications[header["name"]]
                eng.ddl_gen += 1
        elif op == "mark_source":
            eng.mark_source(header["name"], log=False)
        elif op == "create_dynamic":
            eng.register_dynamic(header["name"], header["sql"],
                                 log=False)
        elif op == "create_snapshot":
            # direct: create_snapshot() mints a fresh ts and appends
            # WAL unconditionally; replay must keep the recorded ts
            eng.snapshots[header["name"]] = header["ts"]
            eng.ddl_gen += 1
        elif op == "drop_snapshot":
            if header["name"] in eng.snapshots:
                del eng.snapshots[header["name"]]
                eng.ddl_gen += 1
        elif op == "insert":
            self.pending.append(("insert", header, blob))
        elif op == "delete":
            self.pending.append(("delete", header, None))
        elif op == "commit":
            ts = header["ts"]
            self.max_ts = max(self.max_ts, ts)
            touched = set()
            # deletes BEFORE inserts, matching commit_txn's apply order
            # (engine.py commit pipeline): an UPDATE is delete+insert at
            # one ts, and CDC consumers hanging off a replica would
            # duplicate-key a PK mirror if the insert fired first
            ordered = ([p for p in self.pending if p[0] == "delete"]
                       + [p for p in self.pending if p[0] == "insert"])
            for kind, h, b in ordered:
                t = eng.get_table(h["table"])
                touched.add(h["table"])
                if kind == "insert":
                    arrays, validity = walmod.arrow_to_arrays(b)
                    for c, a in list(arrays.items()):
                        if isinstance(a, arrowio.DictEncoded):
                            arrays[c] = t.encode_dict_encoded(c, a)
                        elif isinstance(a, list):   # legacy varchar strings
                            arrays[c] = t.encode_strings_list(c, a)
                    for seg in t.insert_segments(arrays, validity, ts):
                        for fn in eng._subscribers:
                            fn(ts, h["table"], "insert", seg)
                    ac = t.meta.auto_increment
                    if ac and ac in arrays:
                        t.observe_auto(arrays[ac][validity[ac]])
                else:
                    gids = np.asarray(h["gids"], np.int64)
                    t.apply_tombstones(ts, gids)
                    for fn in eng._subscribers:
                        fn(ts, h["table"], "delete", gids)
            for tname in touched:
                for ix in eng.indexes_on(tname):
                    ix.dirty = True
                # replicas learn UDF / materialized-view DDL as logtail
                # rows on system_udf / system_mview: bump ddl_gen the
                # same way the TN's commit pipeline does so the CN's
                # plan/result caches invalidate in step (a replica never
                # MAINTAINS a view — the backing rows arrive from the
                # TN's own maintenance commits through this same stream)
                from matrixone_tpu.udf.catalog import is_udf_table
                if is_udf_table(tname):
                    eng.ddl_gen += 1
                from matrixone_tpu.mview.catalog import is_mview_table
                if is_mview_table(tname):
                    eng.ddl_gen += 1
            self.pending = []
            return ts
        return None


#: back-compat alias: older code paths call this a Catalog
Catalog = Engine
