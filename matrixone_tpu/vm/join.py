"""Joins as sort + searchsorted probes — no pointer-chasing hash tables.

Reference analogue: `colexec/hashbuild` + `colexec/join` (and loopjoin for
cross). TPU re-design:

  build:  hash build-side keys -> argsort -> sorted hash array   (one sort)
  probe:  hash probe keys -> searchsorted (log n vectorized binary search)
          -> expand up to `max_matches` consecutive duplicates -> verify
          real key equality (hashes only route; equality decides) -> gather

Duplicate fan-out beyond max_matches is detected on host and the probe
re-runs with a doubled budget — the shape-bucketing trick the rest of the
engine uses, applied to join multiplicity.  Match lanes are laid out
lane-major (`_lanes`).  A build that is unique on one integer key of a
small span skips hash, sort and search: `build_dense_table` +
`expand_probe_dense`, one gather a probe row (chosen by
`ops/kernels.join_lookup`, used by the fused fragments).

Build sides larger than the device budget Grace-spill (reference:
colexec/spillutil/join_spill.go + spill_threshold.go): both sides are
hash-partitioned to host disk by the join key, and each partition joins
with the normal in-memory path — rows with equal keys always share a
partition, so every join kind except cross partitions exactly.

The device math lives in module-level PURE functions (`build_key_columns`,
`build_sorted_hash`, `expand_probe`, `collapse_semi_anti`, ...) shared
verbatim by JoinOp and the fused join fragments (vm/fusion_join.py): the
fused probe program traces the SAME code the per-operator path executes
eagerly, so the two modes cannot diverge.

Dictionary-coded (varchar) join keys translate the PROBE side's codes
into the BUILD side's code space through a host O(distinct) LUT before
hashing — two tables' dictionaries assign codes independently, so a raw
code compare would join by insertion position, not by value.
"""

from __future__ import annotations

import hashlib
import itertools
import os
import tempfile
from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container import dtypes as dt
from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
from matrixone_tpu.ops import filter as F, hash as H
from matrixone_tpu.sql import plan as P
from matrixone_tpu.utils import metrics as M, motrace
from matrixone_tpu.vm.exprs import ExecBatch, eval_expr
from matrixone_tpu.vm.operators import Operator, _broadcast_full, _concat_batches

_NULL_HASH = np.uint64(0xFFFFFFFFFFFFFFFF)


def _probe_scans(op, name: str):
    """Resolve a probe-key column down to the scans that produce it,
    walking only through operators where a pre-filter is always safe
    (Filter: conjunctive; Project: plain column renames)."""
    from matrixone_tpu.sql.expr import BoundCol
    from matrixone_tpu.vm import operators as O
    from matrixone_tpu.vm.fusion import FusedFragmentOp
    if isinstance(op, O.FilterOp):
        return _probe_scans(op.child, name)
    if isinstance(op, FusedFragmentOp):
        # walk the fragment's fused project renames down to its source;
        # the fragment reads runtime_filters off the scan at execute
        # time and folds them into its traced predicate
        src_name = op.resolve_column(name)
        if src_name is None:
            return []
        return _probe_scans(op.child, src_name)
    if isinstance(op, O.ProjectOp):
        for (n, _), e in zip(op.node.schema, op.node.exprs):
            if n == name:
                if isinstance(e, BoundCol):
                    return _probe_scans(op.child, e.name)
                return []
        return []
    if isinstance(op, O.ScanOp):
        if any(n == name for n, _ in op.node.schema):
            return [(op, name)]
    return []


def _maybe_compact(out: ExecBatch) -> ExecBatch:
    """Join outputs carry np*mm lanes but typically few live rows; without
    compaction a chain of joins grows lanes multiplicatively (observed:
    4M-lane batches carrying 40 rows in TPC-H Q2). Compact whenever the
    live fraction drops below 1/4, padding to the jit bucket."""
    from matrixone_tpu.container.device import bucket_length
    lanes = int(out.mask.shape[0])
    if lanes <= 2048:
        return out
    live = int(jax.device_get(jnp.sum(out.mask.astype(jnp.int32))))
    M.device_wait.inc(site="join_compact")
    cap = bucket_length(max(live, 1))
    if cap * 4 > lanes:
        return out
    db = F.compact(out.batch, out.mask, cap)
    return ExecBatch(batch=db, dicts=out.dicts,
                     mask=jnp.arange(cap, dtype=jnp.int32) < db.n_rows)


# =====================================================================
# pure device kernels, shared by JoinOp and vm/fusion_join.py
# =====================================================================

def _str_hash_i64(s) -> np.int64:
    """Stable 64-bit value hash of a dictionary entry (spill routing:
    equal strings must land in equal partitions on BOTH sides)."""
    d = hashlib.blake2b(str(s).encode("utf-8"), digest_size=8).digest()
    return np.int64(np.frombuffer(d, dtype="<u8")[0].astype(np.int64))


def build_key_columns(node, build: ExecBatch):
    """Evaluate the build side's join keys.  Varchar keys stay in their
    own (build) code space widened to int64 — the probe side translates
    into it — and their dictionaries are returned for that translation."""
    from matrixone_tpu.vm.operators import _expr_dict
    bkeys, bdicts = [], []
    for k in node.right_keys:
        c = _broadcast_full(eval_expr(k, build), build.padded_len)
        d = None
        if k.dtype.is_varlen:
            d = _expr_dict(k, build)
            c = DeviceColumn(c.data.astype(jnp.int64), c.validity,
                             c.dtype)
        bkeys.append(c)
        bdicts.append(d)
    return bkeys, bdicts


def probe_key_columns(node, ex: ExecBatch, bkey_dicts):
    """Evaluate the probe side's join keys, translating varchar codes
    into the build side's code space: a probe string present in the
    build dictionary takes the build code, an absent one takes a
    non-colliding id past it.  Exact value equality, O(distinct) host
    work per batch."""
    from matrixone_tpu.vm.operators import _expr_dict
    pkeys = []
    for k, bd in zip(node.left_keys, bkey_dicts):
        c = _broadcast_full(eval_expr(k, ex), ex.padded_len)
        if k.dtype.is_varlen:
            d = _expr_dict(k, ex)
            if d is not None and bd is not None:
                if len(d) == 0:
                    # all-NULL probe column: the empty dictionary has
                    # no codes to translate and no row can match (the
                    # validity mask is already all-false) — any
                    # constant works
                    data = jnp.zeros_like(c.data, jnp.int64)
                else:
                    code_of = {str(s): i for i, s in enumerate(bd)}
                    lut = np.asarray(
                        [code_of.get(str(s), len(bd) + i)
                         for i, s in enumerate(d)], np.int64)
                    data = jnp.asarray(lut)[
                        jnp.clip(c.data, 0, max(len(d) - 1, 0))]
            else:
                # no dictionary to translate through: the two sides'
                # code spaces are incomparable, and matching raw codes
                # would join by insertion position, not value — refuse,
                # matching _eval_compare's discipline for the same case
                from matrixone_tpu.vm.exprs import EvalError
                raise EvalError(
                    "unsupported string comparison: varchar join key "
                    f"{k!r} has no resolvable dictionary")
            c = DeviceColumn(data, c.validity, c.dtype)
        pkeys.append(c)
    return pkeys


def hash_valid_keys(kcols, mask):
    """(row hash, all-keys-valid mask) for one side's key columns; rows
    with any NULL key never match (SQL equi-join semantics)."""
    h = H.hash_columns([k.data for k in kcols],
                       [k.validity for k in kcols])
    valid = mask
    for k in kcols:
        valid = valid & k.validity
    return h, valid


def build_sorted_hash(bkeys, mask):
    """Build finalize: hash + argsort of the build keys -> the sorted
    hash array the probe binary-searches, plus the row order and the
    valid-key mask."""
    bhash, bvalid = hash_valid_keys(bkeys, mask)
    bhash = jnp.where(bvalid, bhash, jnp.uint64(_NULL_HASH))
    order = jnp.argsort(bhash).astype(jnp.int32)
    return bhash[order], order, bvalid


def runtime_filter_specs(node):
    """Static eligibility for the build-side min/max runtime filters:
    [(key index, probe BoundCol)] for the int-like BoundCol probe keys
    whose width/scale agree with the build key so a raw-unit range is
    valid.  Purely dtype-driven, so the fused build fragment can decide
    eligibility before tracing."""
    from matrixone_tpu.sql.expr import BoundCol
    specs = []
    for i, (lk, rk) in enumerate(zip(node.left_keys, node.right_keys)):
        if not isinstance(lk, BoundCol):
            continue
        dtype = lk.dtype
        int_like = dtype.is_integer or dtype.oid in (
            dt.TypeOid.DATE, dt.TypeOid.DECIMAL64)
        if not int_like or dtype.is_varlen:
            continue
        # scales/widths must agree for a raw-unit range to be valid
        if rk.dtype != dtype and not (rk.dtype.is_integer
                                      and dtype.is_integer):
            continue
        if getattr(rk.dtype, "is_vector", False):
            continue
        specs.append((i, lk))
    return specs


def runtime_filter_ranges(specs, bkeys, bvalid):
    """(lo[], hi[], any_valid) build-key ranges for the eligible probe
    keys, in raw units.  Pure — the fused build program returns these
    as traced outputs, the eager path device_gets them."""
    los, his = [], []
    for i, _lk in specs:
        lo, hi = value_range(bkeys[i], bvalid)
        los.append(lo)
        his.append(hi)
    lo = (jnp.stack(los) if los
          else jnp.zeros((0,), jnp.int64))
    hi = (jnp.stack(his) if his
          else jnp.zeros((0,), jnp.int64))
    return lo, hi, jnp.any(bvalid)


def value_range(col: DeviceColumn, mask):
    """(lo, hi) of an integer column's non-NULL live values as int64
    scalars; lo > hi where there is none."""
    live = mask & col.validity
    big = jnp.iinfo(col.data.dtype).max
    return (jnp.min(jnp.where(live, col.data, big)).astype(jnp.int64),
            jnp.max(jnp.where(live, col.data, -big - 1)).astype(jnp.int64))


def _lanes(x, mm: int):
    """Each probe row's value on its `mm` match lanes, LANE-MAJOR: lane j
    of probe row i is element j * np + i, so the expansion is a
    concatenation.  The row-major [np, mm] interleaving it replaces (a
    gather by repeat(arange(np), mm), gathers through [np, mm] indexes)
    made the chip's compiler re-lay every lane array out around a minor
    dimension of 4: over two minutes a probe step at a 2^20-row batch,
    under a second this way (PERF.md section 6, PR 33)."""
    return x if mm == 1 else jnp.concatenate([x] * mm, axis=0)


def expand_probe(node, ex: ExecBatch, build: ExecBatch, sorted_hash,
                 border, phash, pvalid, pkeys, bkeys, mm: int,
                 build_matched=None):
    """One probe batch against a finalized build side: searchsorted ->
    expand `mm` duplicate lanes -> verify true key equality -> gather
    both sides -> residual -> left/full NULL-extension.  Returns
    (out ExecBatch [mm*np lanes, lane-major], overflow bool array,
    build_matched').
    Pure (the overflow flag stays on device): JoinOp device_gets it,
    the fused probe program returns it as a traced output."""
    nb = sorted_hash.shape[0]
    # entry point into the sorted hash run (searchsorted-left)
    start = jnp.searchsorted(sorted_hash, phash).astype(jnp.int32)  # [np]
    # every per-lane array is flat [mm*np], lane-major (see `_lanes`)
    pos = jnp.concatenate([start + j for j in range(mm)])
    pos_c = jnp.clip(pos, 0, nb - 1)
    phash_l = _lanes(phash, mm)
    hash_ok = (sorted_hash[pos_c] == phash_l) & (pos < nb) \
        & _lanes(pvalid, mm)
    cand_rows = border[pos_c]                             # build row ids
    # verify true key equality (hash only routes)
    key_ok = hash_ok
    for pk, bk in zip(pkeys, bkeys):
        pv = _lanes(pk.data, mm)
        bv = bk.data[cand_rows]
        if pv.dtype != bv.dtype:
            ct = jnp.promote_types(pv.dtype, bv.dtype)
            pv, bv = pv.astype(ct), bv.astype(ct)
        key_ok = key_ok & (pv == bv)
    # overflow: a (mm+1)-th duplicate would also match
    extra = jnp.clip(start + mm, 0, nb - 1)
    overflow = jnp.any(
        (sorted_hash[extra] == phash) & (start + mm < nb) & pvalid)
    out, build_matched = emit_lanes(node, ex, build, key_ok, cand_rows, mm,
                                    build_matched)
    return out, overflow, build_matched


def build_dense_table(bkey, bvalid, lo, table_len: int):
    """Direct-address table of a build that is unique on one integer key
    whose values span at most `table_len`: table[key - lo] is the build
    row holding that key, -1 where none does.  One scatter, no hash and
    no sort.  `lo` is a device scalar (the smallest valid key).
    -> (table, dup): `dup` says that two valid rows share a key (fewer
    slots are filled than rows were placed), which the caller holds to
    be impossible and must not let pass in silence."""
    nb = bkey.shape[0]
    slot = jnp.where(bvalid, bkey.astype(jnp.int64) - lo,
                     table_len).astype(jnp.int32)
    table = jnp.full((table_len,), -1, jnp.int32).at[slot].set(
        jnp.arange(nb, dtype=jnp.int32), mode="drop")
    dup = jnp.sum((table >= 0).astype(jnp.int32)) \
        != jnp.sum(bvalid.astype(jnp.int32))
    return table, dup


def expand_probe_dense(node, ex: ExecBatch, build: ExecBatch, table, lo,
                       pkey, pvalid):
    """One probe batch against a direct-address table
    (`build_dense_table`): a subtraction and one gather a probe row, one
    lane a row, nothing to verify and nothing that can overflow.
    -> out ExecBatch [np lanes]."""
    slot = pkey.data.astype(jnp.int64) - lo
    inside = pvalid & (slot >= 0) & (slot < table.shape[0])
    row = table[jnp.clip(slot, 0, table.shape[0] - 1).astype(jnp.int32)]
    out, _ = emit_lanes(node, ex, build, inside & (row >= 0),
                        jnp.maximum(row, 0), 1, None)
    return out


def _probe_columns(node):
    """(probe side's, build side's [(name, dtype)], residual-only names) of
    the columns a probe of `node` builds: those `node.schema` names, which
    `sql/optimize.prune_columns` narrows to what is read above the join,
    plus, for the residual's evaluation alone, what the residual reads.
    By membership, in the PHYSICAL order, never zipped against
    `node.schema`, whose order is the statement's and not the batch's (a
    semi/anti join's schema names its probe side, so its build side is
    built for the residual only)."""
    from matrixone_tpu.sql.expr import columns_used
    out = {n for n, _ in node.schema}
    inside_only = (set(columns_used(node.residual)) - out
                   if node.residual is not None else set())
    built = out | inside_only
    return ([c for c in node.left.schema if c[0] in built],
            [c for c in node.right.schema if c[0] in built], inside_only)


def output_schema(node) -> list:
    """[(name, dtype)] of what a probe of `node` hands up, probe side then
    build side: what it builds less what only its residual reads."""
    left, right, inside_only = _probe_columns(node)
    return [c for c in left + right if c[0] not in inside_only]


def count_build_columns(node) -> None:
    """`mo_join_build_columns_total`, once a join executed: the build
    side's columns that a probe gathers and those it leaves alone."""
    gathered = len(_probe_columns(node)[1])
    M.join_build_columns.inc(gathered, outcome="gathered")
    M.join_build_columns.inc(len(node.right.schema) - gathered,
                             outcome="pruned")


def emit_lanes(node, ex: ExecBatch, build: ExecBatch, match, build_idx,
               mm: int, build_matched):
    """The probe's output from its match lanes: `match` [mm*np] says
    which lane found its key, `build_idx` which build row.  Copies the
    probe side's and gathers the build side's columns of the join's
    output (`output_schema`), applies the residual, NULL-extends for
    left/full.  -> (out ExecBatch, build_matched')."""
    np_ = ex.padded_len
    left, right, inside_only = _probe_columns(node)
    cols = {}
    for name, _ in left:
        c = _broadcast_full(ex.batch.columns[name], np_)
        cols[name] = DeviceColumn(_lanes(c.data, mm),
                                  _lanes(c.validity, mm), c.dtype)
    for name, _ in right:
        c = _broadcast_full(build.batch.columns[name], build.padded_len)
        validity = c.validity[build_idx] & match
        cols[name] = DeviceColumn(c.data[build_idx], validity, c.dtype)
    db = DeviceBatch(columns=cols, n_rows=jnp.sum(match.astype(jnp.int32)))
    out = ExecBatch(batch=db, dicts={**build.dicts, **ex.dicts},
                    mask=match)
    # residual ON predicate filters match lanes BEFORE left-join
    # null-extension: a left row whose matches all fail the residual
    # still emits one null-extended row (MySQL semantics)
    if node.residual is not None:
        pred = eval_expr(node.residual, out)
        out.mask = out.mask & F.predicate_mask(pred, db)
        for name in inside_only:        # read inside, not handed up
            del cols[name]
    if node.kind == "full":
        # record which build rows matched (post-residual, pre-null-
        # extension) — monotonic across overflow re-runs
        build_matched = build_matched.at[build_idx].max(out.mask)
    if node.kind in ("left", "full"):
        matched_any = jnp.any(out.mask.reshape(mm, np_), axis=0)
        null_emit = jnp.concatenate(          # lane 0 carries the NULLs
            [ex.mask & ~matched_any]
            + [jnp.zeros((np_,), jnp.bool_)] * (mm - 1))
        # null-extended lanes: right-side columns must read as NULL
        for name, _ in right:
            if name in cols:
                c = cols[name]
                cols[name] = DeviceColumn(c.data, c.validity & ~null_emit,
                                          c.dtype)
        out.mask = out.mask | null_emit
    out.batch.n_rows = jnp.sum(out.mask.astype(jnp.int32))
    return out, build_matched


def collapse_semi_anti(node, ex: ExecBatch, out_mask, mm: int):
    """Collapse match lanes back onto the probe rows: emit each left
    row once iff it has (semi) / lacks (anti) a surviving match."""
    np_ = ex.padded_len
    matched_any = jnp.any(out_mask.reshape(mm, np_), axis=0)
    keep = (ex.mask & matched_any if node.kind == "semi"
            else ex.mask & ~matched_any)
    db = DeviceBatch(
        columns={n: _broadcast_full(ex.batch.columns[n], np_)
                 for n, _ in node.left.schema},
        n_rows=jnp.sum(keep.astype(jnp.int32)))
    return ExecBatch(batch=db, dicts=dict(ex.dicts), mask=keep)


def stream_build_side(build_iter, budget: int):
    """Pull the build side counting live rows against `budget` ->
    (batches, overflowed).  The padded lane count bounds live rows from
    above, so a build fitting the budget never syncs; past the bound the
    per-batch mask sums are STACKED on device and drained in one fused
    reduction only when the un-synced upper bound could cross — one (or
    a few) host syncs per build finalize instead of one per batch (the
    old per-batch `device_get` serialized every dispatch past the
    bound).  Each drain is a `join.build.livesync` motrace span, which
    is how the regression test counts them."""
    batches: List[ExecBatch] = []
    pending = []
    padded_pending = 0
    live = 0
    overflowed = False
    for ex in build_iter:
        batches.append(ex)
        pending.append(jnp.sum(ex.mask.astype(jnp.int64)))
        padded_pending += int(ex.padded_len)
        if live + padded_pending <= budget:
            continue
        with motrace.span("join.build.livesync", pending=len(pending)):
            live += int(jax.device_get(jnp.sum(jnp.stack(pending))))
            M.device_wait.inc(site="join_livesync")
        pending = []
        padded_pending = 0
        if live > budget:
            overflowed = True
            break
    return batches, overflowed


class _JoinSpill:
    """Host-disk partitions of one join's two sides (Grace). Each stored
    chunk keeps its source batch's dictionaries, so replayed ExecBatches
    are exactly as expressive as the originals."""

    def __init__(self, n_partitions: int):
        self.P = n_partitions
        self.dir = tempfile.mkdtemp(prefix="mo_join_spill_")
        self._chunks: dict = {}          # (side, p) -> [(path, dicts, n)]
        self._seq = 0

    def add(self, side: str, p: int, arrays: dict, validity: dict,
            dicts: dict, n: int) -> None:
        path = os.path.join(self.dir, f"{side}_{p}_{self._seq}.npz")
        self._seq += 1
        payload = {}
        for c, a in arrays.items():
            payload[f"d_{c}"] = a
            payload[f"v_{c}"] = validity[c]
        np.savez(path, **payload)
        self._chunks.setdefault((side, p), []).append(
            (path, dict(dicts), n))

    def chunks(self, side: str, p: int) -> list:
        return self._chunks.get((side, p), [])

    def cleanup(self) -> None:
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)


class _ReplayOp(Operator):
    """Spilled host chunks as an operator (the drain half of Grace)."""

    def __init__(self, chunks: list, schema):
        self.chunks = chunks
        self.schema = schema

    def execute(self) -> Iterator[ExecBatch]:
        from matrixone_tpu.container import device as dev
        for path, dicts, n in self.chunks:
            if n == 0:
                continue
            z = np.load(path)
            arrays, validity, dtypes = {}, {}, {}
            for name, dtype in self.schema:
                arrays[name] = z[f"d_{name}"]
                validity[name] = z[f"v_{name}"]
                dtypes[name] = (dt.INT32 if dtype.is_varlen else dtype)
            db = dev.from_numpy(arrays, dtypes, validity, n_rows=n)
            for name, dtype in self.schema:
                if dtype.is_varlen:
                    c = db.columns[name]
                    db.columns[name] = DeviceColumn(c.data, c.validity,
                                                    dtype)
            yield ExecBatch(batch=db, dicts=dicts, mask=db.row_mask())


def _null_column(dtype, lanes: int) -> DeviceColumn:
    """An all-NULL column of `lanes` lanes (the other side of an outer
    join's unmatched rows)."""
    jt = jnp.int32 if dtype.is_varlen else dtype.jnp_dtype
    shape = (lanes, dtype.dim) if dtype.is_vector else (lanes,)
    return DeviceColumn(jnp.zeros(shape, jt), jnp.zeros((lanes,), jnp.bool_),
                        dtype)


class JoinOp(Operator):
    step_span = "op.join"

    #: build rows beyond which the join Grace-spills both sides
    DEFAULT_BUILD_BUDGET = 1 << 22

    def __init__(self, node: P.Join, left: Operator, right: Operator,
                 max_matches: int = 4, ctx=None,
                 spill_partitions: int = 16):
        self.node = node
        self.left = left
        self.right = right
        self.schema = node.schema
        self.max_matches = max_matches
        self.spill_partitions = spill_partitions
        #: (build ExecBatch, sorted_hash, order, bvalid, bkeys,
        #: bkey_dicts) handed over by a fused join fragment degrading to
        #: this op — its build program already computed the finalize AND
        #: pushed the runtime filters; consumed (and cleared) by the
        #: next execute() iff the build batch is the very same object
        self._prepared_build = None
        #: False on the grace path's partition joins: the join they are
        #: parts of has counted `mo_join_build_columns_total`
        self.counts_columns = True
        self.build_budget = self.DEFAULT_BUILD_BUDGET
        if ctx is not None and ctx.variables:
            self.build_budget = int(ctx.variables.get(
                "join_build_budget", self.build_budget))

    def execute(self) -> Iterator[ExecBatch]:
        if self.counts_columns and self._prepared_build is None:
            # (a fused fragment that hands its build over has counted)
            count_build_columns(self.node)
        # stream the build side counting live rows; past the budget,
        # switch to the Grace path (cross joins have no key to partition
        # by — they stay in-memory whatever the size)
        build_iter = self.right.execute()
        overflowed = False
        if self.node.kind != "cross" and self.node.right_keys:
            build_batches, overflowed = stream_build_side(
                build_iter, self.build_budget)
        else:
            build_batches = list(build_iter)
        if overflowed:
            yield from self._grace(build_batches, build_iter)
            return
        if not build_batches and self.node.kind in ("inner", "semi"):
            return
        build = (_concat_batches(build_batches, self.node.right.schema)
                 if build_batches else None)
        if self.node.kind == "cross":
            yield from self._cross(build)
            return
        if build is None:
            if self.node.kind == "anti":
                # NOT EXISTS against nothing: every left row passes
                yield from self.left.execute()
                return
            # LEFT JOIN with empty right side: all left rows null-extended
            for ex in self.left.execute():
                yield self._null_extend_all(ex)
            return
        # build side: dense-compact masked rows, hash + sort keys
        prep, self._prepared_build = self._prepared_build, None
        if prep is not None and prep[0] is build:
            # fused-fragment degrade handoff: the build finalize already
            # ran as one compiled dispatch and the runtime filters are
            # already on the probe scans — don't redo either
            _, sorted_hash, order, bvalid, bkeys, bkey_dicts = prep
        else:
            bkeys, bkey_dicts = build_key_columns(self.node, build)
            sorted_hash, order, bvalid = build_sorted_hash(bkeys,
                                                           build.mask)
            if self.node.kind in ("inner", "semi"):
                self._push_runtime_filters(bkeys, bvalid)
        if self.node.kind == "full":
            self._build_matched = jnp.zeros(build.padded_len, jnp.bool_)
            self._probe_dicts = {}
        for ex in self.left.execute():
            if self.node.kind == "full":
                self._probe_dicts.update(ex.dicts)
            yield from self._probe(ex, build, sorted_hash, order, bkeys,
                                   bkey_dicts)
        if self.node.kind == "full":
            # FULL OUTER: emit build rows no probe row matched, probe-side
            # columns null-extended (the probe loop already null-extended
            # unmatched probe rows via the shared left-join path)
            unmatched = build.mask & ~self._build_matched
            nb = build.padded_len
            cols = {}
            probe_side = {n for n, _ in self.node.left.schema}
            # probe-side varchar columns are all-NULL here but expressions
            # above the join still resolve them through their dictionary
            dicts = {**self._probe_dicts, **build.dicts}
            for name, dtype in output_schema(self.node):
                if name in probe_side:
                    cols[name] = _null_column(dtype, nb)
                    if dtype.is_varlen:
                        dicts.setdefault(name, [""])
                else:
                    cols[name] = _broadcast_full(build.batch.columns[name],
                                                 nb)
            db = DeviceBatch(columns=cols,
                             n_rows=jnp.sum(unmatched.astype(jnp.int32)))
            yield ExecBatch(batch=db, dicts=dicts, mask=unmatched)

    # ------------------------------------------------------------- grace
    def _grace(self, prefix: List[ExecBatch], rest) -> Iterator[ExecBatch]:
        """Build side over budget: hash-partition BOTH sides to host disk
        by the join key, then run each partition through the normal
        in-memory join (reference: spillutil/join_spill.go)."""
        M.join_spills.inc()
        spill = _JoinSpill(self.spill_partitions)
        try:
            for ex in itertools.chain(prefix, rest):
                self._partition_side(spill, ex, "build",
                                     self.node.right_keys,
                                     self.node.right.schema)
            for ex in self.left.execute():
                self._partition_side(spill, ex, "probe",
                                     self.node.left_keys,
                                     self.node.left.schema)
            for p in range(spill.P):
                sub = JoinOp(
                    self.node,
                    _ReplayOp(spill.chunks("probe", p),
                              self.node.left.schema),
                    _ReplayOp(spill.chunks("build", p),
                              self.node.right.schema),
                    max_matches=self.max_matches)
                # a partition joins in memory; key skew concentrating a
                # partition past the budget would recurse on identical
                # hashes forever, so partitions never re-spill
                sub.build_budget = 1 << 62
                sub.counts_columns = False
                yield from sub.execute()
        finally:
            spill.cleanup()

    def _partition_side(self, spill: _JoinSpill, ex: ExecBatch, side: str,
                        keys, schema) -> None:
        """Route each live row to partition hash(key) % P. NULL-key rows
        ride their hash too: they never match, but left/anti/full joins
        still emit them from within their partition.  Varchar keys route
        by a stable VALUE hash of the string (each side partitions
        independently, so codes cannot agree across sides)."""
        from matrixone_tpu.vm.operators import _expr_dict
        kcols = []
        for k in keys:
            c = _broadcast_full(eval_expr(k, ex), ex.padded_len)
            if k.dtype.is_varlen:
                d = _expr_dict(k, ex)
                if d:
                    lut = np.asarray([_str_hash_i64(s) for s in d],
                                     np.int64)
                    c = DeviceColumn(
                        jnp.asarray(lut)[
                            jnp.clip(c.data, 0, max(len(d) - 1, 0))],
                        c.validity, c.dtype)
                else:
                    # None (unresolvable: the in-memory join inside the
                    # partition raises) or empty (all-NULL: routing is
                    # irrelevant, NULL keys never match)
                    c = DeviceColumn(jnp.zeros_like(c.data, jnp.int64),
                                     c.validity, c.dtype)
            kcols.append(c)
        h = H.hash_columns([k.data for k in kcols],
                           [k.validity for k in kcols])
        part = (h % jnp.uint64(spill.P)).astype(jnp.int32)
        part_np = np.asarray(jax.device_get(part))
        mask_np = np.asarray(jax.device_get(ex.mask))
        host_cols, host_val = {}, {}
        for name, _dtype in schema:
            c = _broadcast_full(ex.batch.columns[name], ex.padded_len)
            host_cols[name] = np.asarray(jax.device_get(c.data))
            host_val[name] = np.asarray(jax.device_get(c.validity))
        for p in range(spill.P):
            rows = mask_np & (part_np == p)
            n = int(rows.sum())
            if n == 0:
                continue
            spill.add(side, p,
                      {name: a[rows] for name, a in host_cols.items()},
                      {name: v[rows] for name, v in host_val.items()},
                      ex.dicts, n)

    def _push_runtime_filters(self, bkeys, bvalid) -> None:
        """Build-side key min/max pushed into probe-side scans before the
        probe starts (reference: runtimeFilterMsg sent hashbuild -> scan).
        Inner/semi only — removing non-matching probe rows early cannot
        change the result. Ranges ride the scan's zonemap pruning, so
        whole chunks outside the build key range are never read."""
        specs = runtime_filter_specs(self.node)
        if not specs:
            return
        lo, hi, any_valid = runtime_filter_ranges(specs, bkeys, bvalid)
        got = jax.device_get((lo, hi, any_valid))
        M.device_wait.inc(site="join_eager")
        self.apply_runtime_filters(specs, np.asarray(got[0]),
                                   np.asarray(got[1]), bool(got[2]))

    def apply_runtime_filters(self, specs, lo_np, hi_np,
                              any_valid: bool) -> None:
        """Inject ge/le runtime filters for the pre-computed build-key
        ranges (shared with the fused build fragment, which computes the
        ranges as traced outputs of the build program)."""
        from matrixone_tpu.sql.expr import BoundCol, BoundFunc, BoundLiteral
        if not any_valid:
            return
        for (_i, lk), lo, hi in zip(specs, lo_np, hi_np):
            dtype = lk.dtype
            lo, hi = int(lo), int(hi)
            if dtype.is_integer:
                info = np.iinfo(dtype.np_dtype)
                lo = max(lo, int(info.min))
                hi = min(hi, int(info.max))
            for scan, name in _probe_scans(self.left, lk.name):
                col = BoundCol(name, dtype)
                scan.runtime_filters.append(
                    BoundFunc("ge", [col, BoundLiteral(lo, dtype)], dt.BOOL))
                scan.runtime_filters.append(
                    BoundFunc("le", [col, BoundLiteral(hi, dtype)], dt.BOOL))

    def _probe(self, ex: ExecBatch, build, sorted_hash, border, bkeys,
               bkey_dicts):
        pkeys = probe_key_columns(self.node, ex, bkey_dicts)
        phash, pvalid = hash_valid_keys(pkeys, ex.mask)
        mm = self.max_matches
        while True:
            bm = getattr(self, "_build_matched", None)
            out, overflow, bm = expand_probe(
                self.node, ex, build, sorted_hash, border, phash,
                pvalid, pkeys, bkeys, mm, bm)
            over = bool(jax.device_get(overflow))
            M.device_wait.inc(site="join_eager")
            if not over:
                if self.node.kind == "full":
                    self._build_matched = bm
                break
            mm *= 2
        if self.node.kind in ("semi", "anti"):
            yield collapse_semi_anti(self.node, ex, out.mask, mm)
            return
        yield _maybe_compact(out)

    def _null_extend_all(self, ex: ExecBatch) -> ExecBatch:
        np_ = ex.padded_len
        probe_side = {n for n, _ in self.node.left.schema}
        cols = {name: (_broadcast_full(ex.batch.columns[name], np_)
                       if name in probe_side else _null_column(dtype, np_))
                for name, dtype in output_schema(self.node)}
        db = DeviceBatch(columns=cols, n_rows=ex.batch.n_rows)
        return ExecBatch(batch=db, dicts=dict(ex.dicts), mask=ex.mask)

    def _cross(self, build):
        if build is None:
            return
        nb = build.padded_len
        for ex in self.left.execute():
            np_ = ex.padded_len
            probe_idx = jnp.repeat(jnp.arange(np_, dtype=jnp.int32), nb)
            build_idx = jnp.tile(jnp.arange(nb, dtype=jnp.int32), (np_,))
            emit = jnp.repeat(ex.mask, nb) & jnp.tile(build.mask, (np_,))
            left, right, inside_only = _probe_columns(self.node)
            cols = {}
            for name, _ in left:
                c = _broadcast_full(ex.batch.columns[name], np_)
                cols[name] = DeviceColumn(c.data[probe_idx],
                                          c.validity[probe_idx], c.dtype)
            for name, _ in right:
                c = _broadcast_full(build.batch.columns[name], nb)
                cols[name] = DeviceColumn(c.data[build_idx],
                                          c.validity[build_idx], c.dtype)
            db = DeviceBatch(columns=cols,
                             n_rows=jnp.sum(emit.astype(jnp.int32)))
            out = ExecBatch(batch=db, dicts={**build.dicts, **ex.dicts},
                            mask=emit)
            if self.node.residual is not None:
                pred = eval_expr(self.node.residual, out)
                out.mask = out.mask & F.predicate_mask(pred, db)
                for name in inside_only:
                    del cols[name]
            yield _maybe_compact(out)
