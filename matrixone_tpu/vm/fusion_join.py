"""Device-resident join fragments: the fusion planner's answer to the
join barrier.

A fusable `JoinOp` (inner / left / semi / anti with traceable keys and
residual) splits into two traced pieces instead of splitting the plan:

  * **build fragment** — the build side's batches placed into one batch
    of a canonical length, then the key columns, the runtime-filter
    min/max ranges, the key's own range and the range of every integer
    column (one dispatch, one fetch of those scalars), then the lookup
    structure `ops/kernels.join_lookup` chooses from what was observed: a
    direct-address table for a build the plan declares unique on one
    integer key of a small span (a dimension keyed 1..N, a date key), the
    sorted hash array otherwise; traced ONCE per (build-side shape
    bucket, dtype signature, key-dictionary content);
  * **probe fragment** — probe key -> table gather, or probe hash ->
    searchsorted -> duplicate-lane expand -> key verify; then gather ->
    the downstream filter/project/agg/topk chain, all ONE compiled
    program per probe batch.  A unique build (`Join.build_unique`) probes
    one lane a row, has no overflow flag to fetch, and its output is not
    compacted, so a star join's levels exchange batches of one shape.

Both pieces call the SAME pure kernels `JoinOp` executes eagerly
(vm/join.py: `build_key_columns`, `build_sorted_hash`, `expand_probe`,
`collapse_semi_anti`) — fused and unfused cannot diverge.  The
degradation ladder is preserved bit-identically: a build side past the
budget, an empty build, a trace failure, tiny probe batches, or
`MO_FUSION_JOIN=0` all land on the original `JoinOp` (including its
Grace spill path); against a build with duplicates, fan-out past
`max_matches` re-runs the SAME probe batch with a doubled lane budget
(the overflow flag is a traced output of the probe program — one host
sync, no extra dispatch).  Spans `join.build`, `join.build.wait`,
`join.probe.dispatch`, `join.probe.wait` and the `mo_join_*` counters say
what a statement's joins cost (PERF.md section 3).
"""

from __future__ import annotations

import functools
import itertools
import time
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
from matrixone_tpu.utils import keys as keyaudit
from matrixone_tpu.vm import exprs as EX
from matrixone_tpu.vm import fusion as FF
from matrixone_tpu.vm import join as J
from matrixone_tpu.vm import operators as O
from matrixone_tpu.vm.exprs import ExecBatch
from matrixone_tpu.vm.operators import Operator

#: join kinds the probe fragment traces; cross has no keys and full
#: carries cross-batch build-matched state the host loop owns
_FUSABLE_KINDS = ("inner", "left", "semi", "anti")


def join_fusable(op) -> bool:
    """Can this operator become a fused build/probe fragment pair?"""
    if not isinstance(op, J.JoinOp) or not FF.join_fusion_enabled():
        return False
    node = op.node
    if node.kind not in _FUSABLE_KINDS or not node.right_keys:
        return False
    probe = FF._ExprInfo()
    for k in list(node.left_keys) + list(node.right_keys):
        if getattr(k.dtype, "is_vector", False):
            return False
        if not FF._analyze_expr(k, probe):
            return False
    if node.residual is not None \
            and not FF._analyze_expr(node.residual, probe):
        return False
    return True


#: fewest lanes of a fused build side.  A dimension whose chunks a filter
#: prunes (one year's dates are one or two of its four segments) must
#: not hand the probe program another build shape with every constant
_MIN_BUILD_LANES = 4096


@functools.partial(jax.jit, static_argnames=("lanes", "dtypes", "tails"))
def _build_zeros(*, lanes, dtypes, tails):
    """An empty build side of `lanes` lanes: (datas, valids, mask, rows)."""
    return (tuple(jnp.zeros((lanes,) + t, d) for d, t in zip(dtypes, tails)),
            tuple(jnp.zeros((lanes,), jnp.bool_) for _ in dtypes),
            jnp.zeros((lanes,), jnp.bool_), jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, donate_argnums=(0,))
def _build_place(acc, datas, valids, mask, n_rows, offset):
    def put(buf, x):
        return jax.lax.dynamic_update_slice_in_dim(buf, x, offset, 0)
    bdatas, bvalids, bmask, bn = acc
    return (tuple(put(b, x) for b, x in zip(bdatas, datas)),
            tuple(put(b, x) for b, x in zip(bvalids, valids)),
            put(bmask, mask), bn + n_rows.astype(jnp.int32))


def _placed(batches: List[ExecBatch], names, lanes: int) -> ExecBatch:
    """`batches` one after the other in ONE batch of `lanes` lanes, the
    rest masked out.  An empty buffer, then one program a batch whose
    offset is a traced scalar: the same two programs whether a filter
    left one chunk or four, and wherever they land."""
    first = [O._broadcast_full(batches[0].batch.columns[n],
                               batches[0].padded_len) for n in names]
    acc = _build_zeros(lanes=lanes,
                       dtypes=tuple(str(c.data.dtype) for c in first),
                       tails=tuple(tuple(c.data.shape[1:]) for c in first))
    offset, dicts, ranges = 0, {}, {}
    for ex in batches:
        cols = [O._broadcast_full(ex.batch.columns[n], ex.padded_len)
                for n in names]
        acc = _build_place(acc, tuple(c.data for c in cols),
                           tuple(c.validity for c in cols), ex.mask,
                           jnp.asarray(ex.batch.n_rows), np.int32(offset))
        offset += ex.padded_len
        dicts.update(ex.dicts)
        ranges.update(ex.ranges)
    datas, valids, mask, n_rows = acc
    db = DeviceBatch(columns={n: DeviceColumn(d, v, c.dtype)
                              for n, c, d, v in zip(names, first, datas,
                                                    valids)},
                     n_rows=n_rows)
    return ExecBatch(batch=db, dicts=dicts, mask=mask, ranges=ranges)


def _at_first_lanes(batches, lanes: int):
    """The probe side's batches, a segment's ragged last chunk padded to
    the first chunk's `lanes` (the padding masked out), so that every
    join level above, the chain and the aggregate compile ONE step a
    statement shape and not one a chunk length: a probe step is seconds
    of a cold run, and a star join has three or four."""
    for ex in batches:
        if lanes // 2 <= ex.padded_len < lanes:
            ex = _placed([ex], list(ex.batch.columns), lanes)
        yield ex


def _canonical_build(batches: List[ExecBatch], schema) -> ExecBatch:
    """The build side's batches as ONE batch of a length that does not
    follow the constants: the bucket of their lanes, `_MIN_BUILD_LANES`
    at least (`_concat_batches` compiles a concatenation for every
    combination of lengths)."""
    from matrixone_tpu.container.device import bucket_length
    total = sum(ex.padded_len for ex in batches)
    lanes = max(_MIN_BUILD_LANES, bucket_length(total))
    if len(batches) == 1 and total == lanes:
        return batches[0]
    return _placed(batches, [n for n, _ in schema], lanes)


def _scanned_table(node) -> str:
    """The table a build side's plan scans, for the `join.build` span's
    tag; a build that is itself a join is tagged with its probe side's."""
    while node is not None:
        if getattr(node, "table", None):
            return node.table
        node = getattr(node, "child", None) or getattr(node, "left", None)
    return "-"


class _IterSource(Operator):
    """Already-pulled batches (plus the rest of an iterator) as an
    operator, so the original JoinOp can re-enter the degradation
    ladder without re-executing its children."""

    def __init__(self, batches, rest, schema):
        self._batches = batches
        self._rest = rest
        self.schema = schema

    def execute(self) -> Iterator[ExecBatch]:
        yield from itertools.chain(self._batches, self._rest)


class FusedJoinProbeOp(FF.FusedFragmentOp):
    """One fragment covering JoinOp + the traceable chain above it.

    `child` is the probe (left) side, `right` the build side — tree
    walkers (EXPLAIN ANALYZE, retarget_tree, runtime-filter resolution)
    traverse both unchanged."""

    step_span = "op.fused_join"

    _allow_scan_defer = False
    _step_prefix = "frag_join"

    def __init__(self, join_op, stages, agg_op, probe_src, build_src,
                 ctx, fragment_id: int, sort_op=None):
        self._join = join_op
        # keep the original operator pointed at the FUSED children so
        # every fallback re-enters the per-operator ladder unchanged
        join_op.left = probe_src
        join_op.right = build_src
        super().__init__(probe_src, stages, agg_op, ctx, fragment_id,
                         sort_op=sort_op)
        self.right = build_src
        self.covered_nodes.add(id(join_op.node))
        self.node_roles[id(join_op.node)] = "join=build+probe"
        # per-execution build state
        self._build_dicts: Dict[str, list] = {}
        self._cur_build: Optional[ExecBatch] = None
        self._bkey_dicts: List[Optional[list]] = []
        self._build_ranges: Dict[str, tuple] = {}

    # ------------------------------------------------- analysis hooks
    def _source_schema(self):
        return self._join.node.schema

    def _source_node(self):
        return self._join.node

    def _analyze_prelude(self, info) -> None:
        node = self._join.node
        info.env_idx = 0
        for k in list(node.left_keys) + list(node.right_keys):
            FF._analyze_expr(k, info)
        if node.residual is not None:
            FF._analyze_expr(node.residual, info)

    def _prelude_sig(self, lift_ids) -> List[tuple]:
        node = self._join.node
        return [("join", node.kind,
                 tuple(FF._expr_sig(k, lift_ids)
                       for k in node.left_keys),
                 tuple(FF._expr_sig(k, lift_ids)
                       for k in node.right_keys),
                 FF._expr_sig(node.residual, lift_ids)
                 if node.residual is not None else None,
                 tuple((nm, FF._tsig(t)) for nm, t in node.left.schema),
                 tuple((nm, FF._tsig(t))
                       for nm, t in node.right.schema),
                 # what the probe hands up: two statements that read
                 # other columns above one join are two programs
                 tuple(nm for nm, _ in J.output_schema(node)))]

    def _prelude_labels(self) -> List[str]:
        return ["JoinBuild", "JoinProbe"]

    def _audit_exprs(self) -> list:
        node = self._join.node
        out = super()._audit_exprs()
        out.extend(node.left_keys)
        out.extend(node.right_keys)
        if node.residual is not None:
            out.append(node.residual)
        return out

    def _initial_validity_colmap(self) -> dict:
        """Join-aware all-valid seed: probe-side columns resolve to the
        probe batch, build-side columns to the (fixed) build batch.  A
        left join NULL-extends build columns, so they are never
        flaggable there; for semi/anti only probe columns exist."""
        jn = self._join.node
        probe_side = {nm for nm, _ in jn.left.schema}
        return {nm: ((frozenset([nm]), True)
                     if nm in probe_side or jn.kind == "inner"
                     else (frozenset(), False))
                for nm, _ in J.output_schema(jn)}

    def _flag_validities(self, ex):
        """Validity arrays for the flag columns, resolved across the two
        sides (probe batch / current build)."""
        probe_cols = ex.batch.columns
        build_cols = (self._cur_build.batch.columns
                      if self._cur_build is not None else {})
        out = []
        for c in self._flag_cols:
            if c in probe_cols:
                out.append(probe_cols[c].validity)
            elif c in build_cols:
                out.append(build_cols[c].validity)
            else:
                return None
        return tuple(out)

    def _batch_flags(self, ex):
        from matrixone_tpu.utils import metrics as M
        node = self._agg_op.node
        flaggable = (self._keys_flaggable
                     or any(p and a.arg is not None
                            for (p, _), a in zip(self._agg_flag_specs,
                                                 node.aggs)))
        if not flaggable or not self._flag_cols:
            return False, tuple(p and a.arg is None
                                for (p, _), a in zip(
                                    self._agg_flag_specs, node.aggs))
        valids = self._flag_validities(ex)
        if valids is None:
            return False, tuple(a.arg is None for a in node.aggs)
        from matrixone_tpu.utils import motrace
        with motrace.span("join.probe.wait"):
            got = np.asarray(jax.device_get(FF._allvalid_flags(valids)))
            M.device_wait.inc(site="join_flags")
        M.fusion_dispatch.inc(kind="step")
        self.last_stats["dispatches"] += 1
        ok = dict(zip(self._flag_cols, (bool(x) for x in got)))
        keys_allvalid = self._keys_flaggable and \
            all(ok[c] for c in self._key_flag_cols)
        agg_flags = tuple(
            a.arg is None or (p and all(ok[c] for c in cs))
            for (p, cs), a in zip(self._agg_flag_specs, node.aggs))
        return keys_allvalid, agg_flags

    # --------------------------------------------------- dict plumbing
    def _dict_envs(self, dicts0):
        merged = dict(self._build_dicts)
        merged.update(dicts0)
        return super()._dict_envs(merged)

    def _out_schema(self, ex):
        for st in reversed(self.stages):
            if st.kind == "project":
                return ([n for n, _ in st.schema],
                        [d for _, d in st.schema])
        # no projection: the stream payload's column ORDER is the
        # probe-chain construction order — the join's output columns of
        # the left schema, then (for inner/left) of the right schema.
        # NOT jn.schema's order: after a CBO side swap the join node's
        # declared order differs from the physical batch order, and a
        # positional zip against it would hand every downstream operator
        # the wrong column under each name
        sch = J.output_schema(self._join.node)
        return ([n for n, _ in sch], [d for _, d in sch])

    def _stream_batch(self, ex, payload, envs, mm) -> ExecBatch:
        out_datas, out_valids, out_mask = payload
        names, dtypes = self._out_schema(ex)
        cols = {nm: DeviceColumn(d, v, t)
                for nm, t, d, v in zip(names, dtypes, out_datas,
                                       out_valids)}
        env_final = envs[-1]
        dicts = {nm: env_final[nm] for nm, t in zip(names, dtypes)
                 if t.is_varlen and env_final.get(nm) is not None}
        db = DeviceBatch(columns=cols,
                         n_rows=jnp.sum(out_mask.astype(jnp.int32)))
        ranges = {}
        if not any(st.kind == "project" for st in self.stages):
            ranges = {nm: r for nm, r in {**ex.ranges,
                                          **self._build_ranges}.items()
                      if nm in cols}
        out = ExecBatch(batch=db, dicts=dicts, mask=out_mask,
                        ranges=ranges)
        # same lane discipline as the per-operator probe: join output
        # lanes are mm*np wide but usually sparse.  One lane a probe row
        # grows nothing, so nothing is compacted: the live count is a
        # wait a batch, and its bucket a new shape a selectivity
        return out if mm == 1 else J._maybe_compact(out)

    # ----------------------------------------------------- execution
    def execute(self):
        from matrixone_tpu.utils import metrics as M
        self.last_stats = {"mode": "none", "dispatches": 0,
                           "trace_ms": 0.0, "cache": "-",
                           "build_dispatches": 0}
        from matrixone_tpu.utils import motrace
        join = self._join
        node = join.node
        # `join.build`: the build side's scan, the build programs'
        # dispatches and the wait for the few scalars the host needs
        with motrace.span("join.build", table=_scanned_table(node.right)):
            build_iter = self.right.execute()
            build_batches, overflowed = J.stream_build_side(
                build_iter, join.build_budget)
            bstate = None
            if not overflowed and build_batches:
                build = _canonical_build(build_batches, node.right.schema)
                # build BEFORE the first probe pull: the build fragment
                # pushes the runtime min/max filters onto the probe
                # scans, and zonemap pruning only sees them for chunks
                # not yet read
                bstate = self._build_state(build)
        if bstate is None:
            # over-budget (Grace spill) or empty build side: the
            # original JoinOp owns every one of those ladders
            M.fusion_exec.inc(mode="fallback")
            self.last_stats["mode"] = "fallback"
            yield from self._orig_join_chain(build_batches, build_iter)
            return
        J.count_build_columns(node)
        probe_iter = self.child.execute()
        first = next(probe_iter, None)
        if first is None or first.padded_len < FF.min_fused_rows():
            # degrade ladders re-enter the ORIGINAL JoinOp with the
            # finalized build state (`_handoff`)
            join._prepared_build = self._handoff(build, bstate)
            mode = "fallback" if first is None else "eager"
            M.fusion_exec.inc(mode=mode)
            self.last_stats["mode"] = mode
            yield from self._orig_join_chain(
                [build], iter(()),
                probe=([] if first is None else [first], probe_iter))
            return
        yield from self._execute_join_fused(build, bstate, first,
                                            probe_iter)

    def _orig_join_chain(self, build_batches, build_rest, probe=None):
        """Run the ORIGINAL JoinOp (+ the original chain above it) over
        the partially-pulled sides — the bit-identical ladder for every
        degradation."""
        join = self._join
        node = join.node
        saved_l, saved_r = join.left, join.right
        join.right = _IterSource(build_batches, build_rest,
                                 node.right.schema)
        if probe is not None:
            join.left = _IterSource(probe[0], probe[1],
                                    node.left.schema)
        if self._orig_bottom is not None:
            self._orig_bottom.child = join
        try:
            top = self._orig_top if self._orig_top is not None else join
            yield from top.execute()
        finally:
            join.left, join.right = saved_l, saved_r

    def _run_program(self, entry, slot, fn_maker, name, args):
        """Compile (once an entry) and dispatch one build-side program;
        a trace failure runs the same function eagerly."""
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils import motrace
        fn = entry["fn"].get(slot)
        if fn is None:
            fn = FF._named(fn_maker(), name)
            entry["fn"][slot] = fn
        if not entry["failed"]:
            if entry["compiled"].get(slot) is None:
                t0 = time.perf_counter()
                with motrace.span("fusion.compile", slot=slot):
                    try:
                        lowered = jax.jit(fn).lower(*args)
                    except Exception:   # noqa: BLE001 — whatever the
                        # tracer rejected, the eager call below computes
                        # the identical result (same function)
                        lowered = None
                        self._note_trace_fail(entry)
                    if lowered is not None:
                        # the device compiler's refusal raises
                        self._note_compiled(entry, slot, lowered.compile(),
                                            t0)
            if not entry["failed"]:
                self.last_stats["build_dispatches"] += 1
                return self._dispatch_entry(entry, slot, args)
        M.fusion_dispatch.inc(kind="eager")
        return fn(*args)

    def _build_state(self, build):
        """Trace (or reuse) the build fragment for this build batch and
        execute it: one dispatch producing the key columns, the runtime-
        filter ranges, the key's own range and the live row count, one
        fetch of those few scalars, and then the lookup structure that
        `ops/kernels.join_lookup` chooses from what was just observed: a
        direct-address table for a unique integer key of a small span, the
        sorted hash array otherwise.  -> dict(lookup, arrays, bvalid,
        bkeys, key)."""
        from matrixone_tpu.ops import kernels as HK
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils import motrace
        node = self._join.node
        self._cur_build = build
        self._build_dicts = dict(build.dicts)
        self._bkey_dicts = [
            O._expr_dict(k, build) if k.dtype.is_varlen else None
            for k in node.right_keys]
        specs = J.runtime_filter_specs(node)
        dense_candidate = (node.build_unique and len(node.right_keys) == 1
                           and node.right_keys[0].dtype.is_integer
                           and node.left_keys[0].dtype.is_integer
                           and node.kind != "full")
        # the build program depends ONLY on the build-key expressions —
        # its lifted-literal inputs (and baked values in the key) come
        # from them, never from the fragment's probe-side chain: two
        # fragments sharing a build side but differing above the probe
        # must share (not corrupt) the compiled build program
        binfo = FF._ExprInfo()
        binfo.env_idx = 0
        for k in node.right_keys:
            FF._analyze_expr(k, binfo)
        lift_lits = list(binfo.lift)
        # array dtype rides the colsig (narrow dict codes make several
        # widths legal per oid; a widened dict must re-trace)
        colsig = tuple((nm, int(c.dtype.oid), str(c.data.dtype),
                        tuple(c.data.shape))
                       for nm, c in build.batch.columns.items())
        # keyed on the BUILD-side inputs alone (key exprs + runtime-
        # filter eligibility + schema/dicts/shape + baked values): two
        # fragments sharing a build side but differing above the probe
        # — or in their terminal — share one compiled build program.
        # binfo.dictdep content rides the key too: a dict-DEPENDENT
        # sub-expression inside a key (LIKE / varchar compare in a CASE
        # key) bakes its lookup table from the build batch's
        # dictionaries at trace time, and only the OUTPUT dicts of
        # varlen keys were keyed before — a mokey-found gap of exactly
        # the PR-7 stale-LUT class
        blids = frozenset(id(x) for x in lift_lits)
        key = ("joinbuild",
               tuple(FF._expr_sig(k, blids) for k in node.right_keys),
               tuple(i for i, _lk in specs), colsig,
               int(build.mask.shape[0]),
               tuple(FF._norm_val(lit.value) for lit in binfo.baked),
               tuple(FF._dict_key(d) for d in self._bkey_dicts),
               tuple(FF._dict_key(FF._static_dict(e, self._build_dicts))
                     for _i, e in binfo.dictdep),
               dense_candidate, FF.ENC.signature())
        entry = FF.CACHE.entry(key)
        if keyaudit.armed():
            keyaudit.audit("vm/fusion_join.py:joinbuild", key, {
                "bkey_dict_content": tuple(
                    tuple(str(s) for s in d) if d is not None else None
                    for d in self._bkey_dicts),
                "dictdep_content": tuple(
                    tuple(str(s) for s in d) if d is not None else None
                    for d in (FF._static_dict(e, self._build_dicts)
                              for _i, e in binfo.dictdep)),
                "baked_values": tuple(FF._norm_val(lit.value)
                                      for lit in binfo.baked),
                "lift_arity": len(lift_lits),
                "rf_spec_indexes": tuple(i for i, _lk in specs),
                "dense_candidate": dense_candidate,
                "encoding_policy": FF.ENC.signature(),
            })
        bschema = tuple((nm, c.dtype)
                        for nm, c in build.batch.columns.items())
        bdicts = self._build_dicts
        int_cols = tuple(nm for nm, t in bschema
                         if t.is_integer and not t.is_varlen)

        def _join_build_step(datas, valids, n_rows, mask, lifted):
            binding = {id(lit): v
                       for lit, v in zip(lift_lits, lifted)}
            with EX.lifted_literal_scope(binding):
                cols = {nm: DeviceColumn(d, v, t)
                        for (nm, t), d, v in zip(bschema, datas,
                                                 valids)}
                bex = ExecBatch(batch=DeviceBatch(columns=cols,
                                                  n_rows=n_rows),
                                dicts=bdicts, mask=mask)
                bkeys, _ = J.build_key_columns(node, bex)
                if dense_candidate:
                    # the lookup is chosen after the key's range is
                    # seen: no hash and no sort in this program
                    _h, bvalid = J.hash_valid_keys(bkeys, bex.mask)
                    sorted_hash = order = None
                    krange = J.value_range(bkeys[0], bvalid)
                else:
                    sorted_hash, order, bvalid = J.build_sorted_hash(
                        bkeys, bex.mask)
                    krange = None
                lo, hi, anyv = J.runtime_filter_ranges(specs, bkeys,
                                                       bvalid)
                # the range of every integer column of the build side:
                # what a dictionary is to a string column, for the
                # grouped aggregate above the join (`ExecBatch.ranges`)
                colrange = tuple(
                    J.value_range(cols[nm], bex.mask) for nm in int_cols)
                return (sorted_hash, order, bvalid,
                        tuple(k.data for k in bkeys),
                        tuple(k.validity for k in bkeys),
                        (lo, hi, anyv, krange,
                         jnp.sum(bvalid.astype(jnp.int32)), colrange))

        args = (tuple(c.data for c in build.batch.columns.values()),
                tuple(c.validity for c in build.batch.columns.values()),
                jnp.asarray(build.batch.n_rows, jnp.int32), build.mask,
                tuple(np.dtype(lit.dtype.np_dtype).type(lit.value)
                      for lit in lift_lits))
        (sorted_hash, order, bvalid, bkdatas, bkvalids,
         scalars) = self._run_program(
            entry, "build", lambda: _join_build_step, "frag_join_build",
            args)
        bkeys = [DeviceColumn(d, v, k.dtype)
                 for d, v, k in zip(bkdatas, bkvalids,
                                    node.right_keys)]
        # the one wait of a build: a handful of scalars
        with motrace.span("join.build.wait"):
            lo, hi, anyv, krange, n_valid, colrange = jax.device_get(
                scalars)
            M.device_wait.inc(site="join_rf")
        M.join_build_rows.inc(int(n_valid))
        self._build_ranges = {
            nm: (int(r[0]), int(r[1]))
            for nm, r in zip(int_cols, colrange) if int(r[0]) <= int(r[1])}
        if specs and node.kind in ("inner", "semi"):
            self._join.apply_runtime_filters(
                specs, np.asarray(lo), np.asarray(hi), bool(anyv))
        state = {"lookup": "sorted", "arrays": (sorted_hash, order),
                 "bvalid": bvalid, "bkeys": bkeys, "key": key}
        if not dense_candidate:
            return state
        span = int(krange[1]) - int(krange[0]) + 1 if bool(anyv) else 1
        nb = int(build.mask.shape[0])
        kdtype = str(bkeys[0].data.dtype)
        if HK.join_lookup(unique=True, int_keys=1, span=span) == "dense":
            from matrixone_tpu.container.device import bucket_length
            table_len = max(_MIN_BUILD_LANES, bucket_length(span))
            klo = np.int64(krange[0]) if bool(anyv) else np.int64(0)
            table, dup = self._run_program(
                FF.CACHE.entry(("jointable", kdtype, nb, table_len)),
                "table",
                lambda: (lambda bkey, bv, lo_: J.build_dense_table(
                    bkey, bv, lo_, table_len)),
                "frag_join_table", (bkeys[0].data, bvalid, klo))
            state.update(lookup="dense", arrays=(table, klo),
                         table_len=table_len, dup=dup)
            return state
        state["arrays"] = self._run_program(
            FF.CACHE.entry(("joinsort", kdtype, nb)), "sort",
            lambda: (lambda bkey, bkv, mask: J.build_sorted_hash(
                [DeviceColumn(bkey, bkv, node.right_keys[0].dtype)],
                mask)[:2]),
            "frag_join_sort",
            (bkeys[0].data, bkeys[0].validity, build.mask))
        return state

    def _handoff(self, build, bstate):
        """The finalized build as the ORIGINAL JoinOp takes it over on a
        degradation: it neither re-runs the build math nor re-pushes the
        runtime filters.  A direct-address build has no sorted hash yet;
        the ladder is rare, so it is made here, eagerly."""
        if bstate["lookup"] == "dense":
            sorted_hash, order, _ = J.build_sorted_hash(bstate["bkeys"],
                                                        build.mask)
        else:
            sorted_hash, order = bstate["arrays"]
        return (build, sorted_hash, order, bstate["bvalid"],
                bstate["bkeys"], list(self._bkey_dicts))

    def _probe_runtime_key(self, ex, envs, mm, build_key, sizes_flags,
                           lookup_sig=("sorted",)):
        cols = ex.batch.columns
        colsig = tuple((nm, int(c.dtype.oid), str(c.data.dtype),
                        tuple(c.data.shape))
                       for nm, c in cols.items())
        baked = tuple(FF._norm_val(lit.value)
                      for lit in self._baked_lits)
        dicts = tuple(FF._dict_key(FF._static_dict(e, envs[i]))
                      for i, e in self._dictdeps)
        # the varchar key-translation LUT depends on BOTH dictionaries
        node = self._join.node
        keydicts = tuple(
            (FF._dict_key(bd),
             FF._dict_key(O._expr_dict(k, ex))
             if k.dtype.is_varlen else None)
            for k, bd in zip(node.left_keys, self._bkey_dicts))
        return (self._plan_sig, colsig, int(ex.mask.shape[0]), baked,
                dicts, sizes_flags, mm, build_key, keydicts, lookup_sig,
                FF.ENC.signature())

    def _make_probe_step(self, trig_schema, bschema, sizes, flags, envs,
                         mm, lookup="sorted"):
        chain = self._make_chain_fn(sizes, flags, envs)
        node = self._join.node
        lift_lits = list(self._lift_lits)
        bkey_dicts = list(self._bkey_dicts)
        bdicts = self._build_dicts
        kinds_collapse = node.kind in ("semi", "anti")

        def _join_probe_step(pdatas, pvalids, p_nrows, pmask, bdatas,
                             bvalids, b_nrows, bmask, lookup_arrays,
                             bkdatas, bkvalids, lifted, seens, carry):
            binding = {id(lit): v
                       for lit, v in zip(lift_lits, lifted)}
            with EX.lifted_literal_scope(binding):
                pcols = {nm: DeviceColumn(d, v, t)
                         for (nm, t), d, v in zip(trig_schema, pdatas,
                                                  pvalids)}
                pex = ExecBatch(batch=DeviceBatch(columns=pcols,
                                                  n_rows=p_nrows),
                                dicts=dict(envs[0]), mask=pmask)
                bcols = {nm: DeviceColumn(d, v, t)
                         for (nm, t), d, v in zip(bschema, bdatas,
                                                  bvalids)}
                build = ExecBatch(batch=DeviceBatch(columns=bcols,
                                                    n_rows=b_nrows),
                                  dicts=bdicts, mask=bmask)
                bkeys = [DeviceColumn(d, v, k.dtype)
                         for d, v, k in zip(bkdatas, bkvalids,
                                            node.right_keys)]
                pkeys = J.probe_key_columns(node, pex, bkey_dicts)
                if lookup == "dense":
                    table, klo = lookup_arrays
                    out = J.expand_probe_dense(
                        node, pex, build, table, klo, pkeys[0],
                        pex.mask & pkeys[0].validity)
                    overflow = jnp.zeros((), jnp.bool_)
                else:
                    sorted_hash, border = lookup_arrays
                    phash, pvalid = J.hash_valid_keys(pkeys, pex.mask)
                    out, overflow, _bm = J.expand_probe(
                        node, pex, build, sorted_hash, border, phash,
                        pvalid, pkeys, bkeys, mm, None)
                if kinds_collapse:
                    oex = J.collapse_semi_anti(node, pex, out.mask, mm)
                else:
                    oex = out
                payload, out_seens = chain(oex, seens, carry)
                counts = jnp.stack([jnp.sum(pex.mask.astype(jnp.int32)),
                                    jnp.sum(out.mask.astype(jnp.int32))])
                return payload, out_seens, overflow, counts

        return _join_probe_step

    def _execute_join_fused(self, build, bstate, first, probe_iter):
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils import motrace
        self.last_stats["mode"] = "fused"
        M.fusion_exec.inc(mode="fused")
        bkeys, build_key = bstate["bkeys"], bstate["key"]
        lookup = bstate["lookup"]
        lookup_sig = (lookup, bstate.get("table_len"))
        unique = self._join.node.build_unique
        counts: list = []        # (device [in, matched] of a batch, mm)
        # device flags that a build held to be unique was not: read once,
        # with the counts, and never expected to be true (`_count_probe`)
        broken: list = [bstate["dup"]] if lookup == "dense" else []
        node = self._agg_op.node if self._agg_op is not None else None
        grouped = self._terminal == "agg_grouped"
        nkeys = len(node.group_keys) if grouped else 0
        key_dicts: List[Optional[list]] = [None] * nkeys
        bschema = tuple((nm, c.dtype)
                        for nm, c in build.batch.columns.items())
        # a build that is unique on the join keys has one match a probe
        # row at most: one lane, and no overflow to look for
        mm = 1 if unique or lookup == "dense" else self._join.max_matches
        carry = None
        if self._terminal == "topk":
            carry = self._init_topk_carry()
        seens: tuple = tuple(np.int64(0) for _ in self._limit_stages)
        trace_sizes: object = ()
        batches = _at_first_lanes(itertools.chain([first], probe_iter),
                                  first.padded_len)
        for ex in batches:
            envs = self._dict_envs(ex.dicts)
            sizes = None
            flags = None
            if grouped:
                for i, k in enumerate(node.group_keys):
                    d = FF._static_dict(k, envs[-1])
                    if d is not None:
                        key_dicts[i] = d
                sizes = self._sizes(envs[-1])
                if trace_sizes == ():
                    trace_sizes = sizes
                if sizes is None or sizes != trace_sizes:
                    M.fusion_exec.inc(mode="degraded")
                    self.last_stats["mode"] = "degraded"
                    # same build-state handoff as the execute() ladders:
                    # the original JoinOp must not redo the finalized
                    # build math or re-push the runtime filters
                    self._join._prepared_build = self._handoff(build,
                                                               bstate)
                    self._count_probe(counts, broken)
                    yield from self._degrade_join_grouped(
                        carry, trace_sizes, key_dicts, build, ex,
                        batches)
                    return
                flags = self._batch_flags(ex)
                if carry is None:
                    carry = self._init_grouped_carry(sizes)
            trig = tuple((nm, c.dtype)
                         for nm, c in ex.batch.columns.items())
            while True:
                key = self._probe_runtime_key(ex, envs, mm, build_key,
                                              (sizes, flags), lookup_sig)
                entry = FF.CACHE.entry(key)
                if keyaudit.armed():
                    deps = self._audit_deps(envs, [], [],
                                            (sizes, flags))
                    deps["keydict_content"] = tuple(
                        (tuple(str(s) for s in bd)
                         if bd is not None else None,
                         tuple(str(s)
                               for s in O._expr_dict(k, ex) or ())
                         if k.dtype.is_varlen else None)
                        for k, bd in zip(self._join.node.left_keys,
                                         self._bkey_dicts))
                    deps["max_matches"] = mm
                    deps["lookup"] = lookup_sig
                    deps["join_output"] = tuple(
                        nm for nm, _ in J.output_schema(self._join.node))
                    keyaudit.audit("vm/fusion_join.py:joinprobe", key,
                                   deps)
                slot = "step"
                if self._terminal == "agg_scalar":
                    slot = "step0" if carry is None else "stepN"
                # (not through `_run_program`: mokey ties a traced
                # closure to its key where both stand in one function)
                fn = entry["fn"].get(slot)
                if fn is None:
                    fn = FF._named(
                        self._make_probe_step(trig, bschema, sizes,
                                              flags, envs, mm, lookup),
                        self._step_name(slot))
                    entry["fn"][slot] = fn
                args = (tuple(c.data
                              for c in ex.batch.columns.values()),
                        tuple(c.validity
                              for c in ex.batch.columns.values()),
                        jnp.asarray(ex.batch.n_rows, jnp.int32),
                        ex.mask,
                        tuple(c.data for c in build.batch.columns
                              .values()),
                        tuple(c.validity for c in build.batch.columns
                              .values()),
                        jnp.asarray(build.batch.n_rows, jnp.int32),
                        build.mask, bstate["arrays"],
                        tuple(k.data for k in bkeys),
                        tuple(k.validity for k in bkeys),
                        self._lifted_values([]), seens, carry)
                out = None
                if not entry["failed"]:
                    compiled = entry["compiled"].get(slot)
                    if compiled is None:
                        t0 = time.perf_counter()
                        with motrace.span("fusion.compile", slot=slot):
                            try:
                                lowered = jax.jit(fn).lower(*args)
                            except Exception:   # noqa: BLE001 — eager
                                # evaluation of the SAME function below
                                # computes the identical result
                                lowered = None
                                self._note_trace_fail(entry)
                            if lowered is not None:
                                # the device compiler's refusal raises
                                compiled = lowered.compile()
                                self._note_compiled(entry, slot,
                                                    compiled, t0)
                    if not entry["failed"]:
                        with motrace.span("join.probe.dispatch"):
                            out = self._dispatch_entry(entry, slot, args)
                if out is None:
                    out = fn(*args)
                    M.fusion_dispatch.inc(kind="eager")
                payload, new_seens, overflow, n_in_matched = out
                counts.append((n_in_matched, mm))
                if mm == 1 and (unique or lookup == "dense"):
                    seens = new_seens        # nothing can overflow
                    broken.append(overflow)
                    break
                with motrace.span("join.probe.wait"):
                    over = bool(jax.device_get(overflow))
                    M.device_wait.inc(site="join_overflow")
                if not over:
                    seens = new_seens
                    break
                # duplicate fan-out past the lane budget: re-run the
                # SAME batch with doubled lanes (the JoinOp ladder)
                M.join_probe_retries.inc()
                mm *= 2
            if self._terminal == "stream":
                yield self._stream_batch(ex, payload, envs, mm)
            else:
                carry = payload
            if self._limits_satisfied(seens):
                if hasattr(probe_iter, "close"):
                    probe_iter.close()
                break
        self._count_probe(counts, broken)
        if self._terminal == "stream":
            return
        yield self._finalize(carry, trace_sizes, key_dicts)

    def _count_probe(self, counts, broken=()) -> None:
        """Probe rows in and matched, and the lanes they were expanded
        to, from the per-batch device scalars: one fetch a join a
        statement, after its last probe step was enqueued.  The same
        fetch brings the `broken` flags of a build the plan held unique
        (a second row of one key in the direct-address table, a second
        match behind a one-lane probe): the engine checks that key at
        every commit, so one that is true is a fault, and the statement
        fails instead of answering short."""
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils import motrace
        if not counts:
            return
        with motrace.span("join.probe.wait"):
            got, bad = jax.device_get(([c for c, _mm in counts],
                                       list(broken)))
            M.device_wait.inc(site="join_stats")
        if any(bool(b) for b in bad):
            raise RuntimeError(
                "fused join: two build rows under one key (or one 64-bit "
                "hash) of a primary key the plan took to be enforced")
        M.join_probe_rows.inc(sum(int(g[0]) for g in got), stage="in")
        M.join_probe_rows.inc(sum(int(g[1]) for g in got), stage="matched")
        M.join_probe_lanes.inc(sum(int(g[0]) * mm
                                   for g, (_c, mm) in zip(got, counts)))
        counts.clear()

    def _degrade_join_grouped(self, carry, sizes, key_dicts, build, ex,
                              rest):
        """A group-key dictionary grew mid-probe-stream (or the key
        space was never dense): convert the fused partials into a
        general group-table state and continue on the ORIGINAL
        JoinOp -> chain, seeded."""
        agg = self._agg_op
        agg._agg_tracker = O._AggDictTracker(agg.node.aggs)
        seed = None
        if carry is not None:
            dense = self._grouped_partials(carry, sizes)
            seed = agg._dense_to_state(dense)
        join = self._join
        node = join.node
        saved_l, saved_r = join.left, join.right
        join.right = _IterSource([build], iter(()), node.right.schema)
        join.left = _IterSource([ex], rest, node.left.schema)
        rewire = self._orig_bottom if self.stages else agg
        saved_child = rewire.child
        rewire.child = join
        try:
            yield from agg._grouped_agg(seed=seed,
                                        seed_dicts=key_dicts)
        finally:
            join.left, join.right = saved_l, saved_r
            rewire.child = saved_child
