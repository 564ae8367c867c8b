"""Delta maintenance: materialized-view state updated from the commit
funnel instead of recomputed.

Wiring (one per engine, `service_for`):

  * a logtail subscriber captures per-commit deltas for tracked source
    tables — insert events keep their Segment (immutable), delete
    events materialize the doomed rows' columns IMMEDIATELY (still
    under the commit lock, before a concurrent merge could compact
    them away);
  * `Engine._notify_post_commit` drives `on_commit` on the committing
    thread AFTER the commit fully applied and the lock released: the
    queue drains in commit order, one thread applying at a time, and
    `on_commit` does not return until every event enqueued before it
    was applied — a writer's next statement always sees its own delta
    in the view (read-your-writes), and two concurrent writers
    serialize through the applying flag;
  * applying one commit's events updates the in-memory partial-agg
    state (the jitted dense tier is ONE compiled dispatch per delta —
    the PR-7 dense-agg step via the shared FragmentCompileCache) and
    lands the changed groups in the backing table as ONE ordinary
    commit, then advances the view watermark: reads are snapshot-
    consistent at that watermark because they are plain MVCC reads of
    the backing table.

Retraction: SUM/COUNT/AVG subtract exactly; a delete touching a group
with MIN/MAX falls back to a per-group recompute from the source at the
commit's snapshot.  A group whose live row count reaches zero leaves
the view (matching GROUP BY semantics).  Any error mid-apply poisons
that view's state (groups=None): the next commit re-initializes it from
a full recompute — self-healing over silently-wrong.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container import dtypes as dt, from_device
from matrixone_tpu.container.dtypes import TypeOid
from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
from matrixone_tpu.ops import agg as A, filter as F
from matrixone_tpu.sql import plan as P
from matrixone_tpu.sql.expr import AggCall, BoundCast
from matrixone_tpu.utils import san
from matrixone_tpu.vm.exprs import ExecBatch, eval_expr

from matrixone_tpu.mview import catalog as mcat
from matrixone_tpu.mview.planner import MaintainSpec, analyze

#: aggregate functions the dense one-dispatch tier handles (the
#: additive subset; MIN/MAX ride the general tier)
_DENSE_FUNCS = frozenset({"count", "sum", "avg"})


def _maintain_fields(a: AggCall) -> List[str]:
    """Partial-state fields of one aggregate (host scalars per group)."""
    if a.func == "count":
        return ["count"]
    if a.func in ("sum", "avg"):
        return ["sum", "count"]
    return [a.func, "count"]        # min / max


def _partial_arg(a: AggCall):
    """The expression whose per-group SUM is this aggregate's additive
    partial — avg over floats casts to f64 first, mirroring the device
    step (_grouped_step) exactly."""
    if a.func == "avg" and a.arg.dtype.is_float:
        return BoundCast(a.arg, dt.FLOAT64)
    return a.arg


class ViewRuntime:
    """One maintained view: spec + partial-agg state + watermark.
    Mutated only under the owning service's maintenance lock."""

    def __init__(self, name: str, spec: MaintainSpec, def_hash: str):
        self.name = name
        self.spec = spec
        self.def_hash = def_hash
        #: key tuple -> {"rows": int, "parts": [per-agg field dict]}
        self.groups: Optional[Dict[tuple, dict]] = None
        self.watermark: Optional[int] = None

    def n_groups(self) -> Optional[int]:
        return None if self.groups is None else len(self.groups)

    def invalidate(self) -> None:
        """Poison the state: the next commit re-initializes from a full
        recompute.  The watermark retreat with it IS the invalidation —
        molint's cache-invalidation checker pins the pairing."""
        san.mutating(self)
        self.groups = None
        self.watermark = None

    def replace_state(self, groups: Dict[tuple, dict], ts: int) -> None:
        san.mutating(self)
        self.groups = groups
        self.watermark = ts

    def merge_delta(self, delta: Dict[tuple, dict], sign: int,
                    ts: int) -> set:
        """Fold one delta's per-group partials into the state with
        `sign` (+1 insert, -1 retract); advance the watermark to `ts`.
        Returns the touched key set (the backing rewrite set)."""
        san.mutating(self)
        spec = self.spec
        touched = set()
        for key, d in delta.items():
            touched.add(key)
            g = self.groups.get(key)
            if g is None:
                g = {"rows": 0,
                     "parts": [dict.fromkeys(_maintain_fields(a), None)
                               for a in spec.aggs]}
                self.groups[key] = g
            g["rows"] += sign * d["rows"]
            for a, part, dp in zip(spec.aggs, g["parts"], d["parts"]):
                for f in _maintain_fields(a):
                    v = dp.get(f)
                    if v is None:
                        continue
                    cur = part.get(f)
                    if f in ("sum", "count"):
                        part[f] = v * sign if cur is None \
                            else cur + sign * v
                    elif f == "min":
                        part[f] = v if cur is None else min(cur, v)
                    else:               # max
                        part[f] = v if cur is None else max(cur, v)
            if g["rows"] <= 0:
                del self.groups[key]
        self.watermark = max(self.watermark, ts)
        return touched


class MViewService:
    """Per-engine maintenance driver (see module docstring)."""

    def __init__(self, engine):
        self.engine = engine
        # queue lock: taken by the subscriber UNDER the commit lock —
        # must never acquire the commit lock itself
        self._qlock = san.lock("MViewService._qlock")
        self._qcv = san.condition(self._qlock)
        self._queue: List[tuple] = []
        self._applying = False
        # maintenance lock: serializes state mutation + backing commits
        self._lock = san.rlock("MViewService._lock")
        self._maint = threading.local()       # re-entrancy guard
        self._views: Dict[str, ViewRuntime] = {}      # event-driven
        self._dynamic: Dict[str, ViewRuntime] = {}    # refresh-driven
        self._sources: frozenset = frozenset()
        self._needed_cols: Dict[str, List[str]] = {}
        #: def_hashes whose init failed: not retried until the
        #: definition changes (drop/recreate) — a permanently broken
        #: view must not wedge every commit into a failing recompute
        self._failed: set = set()
        engine.subscribe(self._on_event)

    # ------------------------------------------------------- event intake
    def _on_event(self, commit_ts: int, table: str, kind: str,
                  payload) -> None:
        """Logtail subscriber — runs under the engine commit lock, so it
        only buffers.  Delete payloads are decoded HERE: the tombstoned
        rows' values are guaranteed still present at notify time."""
        if table not in self._sources:
            return
        if kind == "delete":
            gids = np.asarray(payload, np.int64)
            if len(gids) == 0:
                return
            t = self.engine.get_table(table)
            cols = self._needed_cols.get(table) \
                or [c for c, _ in t.meta.schema]
            arrays, validity = t.fetch_rows(gids, cols)
            payload = (arrays, validity, len(gids))
        with self._qlock:
            self._queue.append((commit_ts, table, kind, payload))

    # ---------------------------------------------------------- the hook
    def on_commit(self, commit_ts: int, touched: set) -> None:
        """Post-commit driver (Engine._notify_post_commit).  Returns
        only when every event enqueued before entry has been applied.
        The triggering commit is already durable — maintenance failures
        must never surface from it (per-view errors poison that view's
        state instead; see _apply_events / _init_pending)."""
        if getattr(self._maint, "active", False):
            return             # nested maintenance commit: outer drains
        self._maint.active = True
        try:
            self._sync_views()
            self._init_pending()
            self._drain_all()
        except Exception:   # noqa: BLE001 — a maintenance-driver crash
            # (registry unreadable mid-drop, source table racing away)
            # must not fail the writer's ALREADY-APPLIED commit; the
            # next commit retries, per-view state stays poisoned-safe
            from matrixone_tpu.utils import metrics as M
            M.mview_apply.inc(tier="error")
        finally:
            self._maint.active = False

    def runtime(self, name: str) -> Optional[ViewRuntime]:
        return self._views.get(name) or self._dynamic.get(name)

    def stats(self) -> dict:
        with self._qlock:
            queued = len(self._queue)
        return {"incremental": sorted(self._views),
                "queued_events": queued,
                "sources": sorted(self._sources)}

    # ------------------------------------------------------ registry sync
    def _sync_views(self) -> None:
        """Diff the system_mview registry (version-cached) against the
        attached runtimes; attach/detach and rebuild the source map."""
        reg = mcat.registry_for(self.engine)
        with self._lock:
            want = {n: d for n, d in reg.items()
                    if d.mode == "incremental"}
            # a dropped definition forgives its failure record, so a
            # drop + recreate (same SQL) retries a failed init
            self._failed &= {d.def_hash for d in reg.values()}
            for n in list(self._views):
                d = want.get(n)
                if d is None or d.def_hash != self._views[n].def_hash:
                    del self._views[n]
            for n, d in want.items():
                if n in self._views or d.def_hash in self._failed:
                    continue
                from matrixone_tpu.sql.parser import parse
                try:
                    sel = parse(d.sql)[0]
                    spec, _why = analyze(sel, self.engine)
                except Exception:       # noqa: BLE001 — a definition
                    spec = None         # that stopped binding (dropped
                    #                     source) simply detaches
                if spec is None:
                    continue
                rt = ViewRuntime(n, spec, d.def_hash)
                san.guard(rt, self._lock, name=f"MViewRuntime[{n}]")
                self._views[n] = rt
            self._rebuild_sources()

    def _rebuild_sources(self) -> None:
        srcs = {}
        for rt in self._views.values():
            srcs.setdefault(rt.spec.source, set()).update(
                rt.spec.scan_columns)
        self._needed_cols = {t: sorted(c) for t, c in srcs.items()}
        self._sources = frozenset(srcs)

    # ----------------------------------------------------- initialization
    def _init_pending(self) -> None:
        for rt in list(self._views.values()):
            if rt.groups is None:
                try:
                    self._init_view(rt)
                except Exception:   # noqa: BLE001 — an unbuildable view
                    # (source dropped mid-flight) must not wedge every
                    # later commit into a failing full recompute:
                    # detach + remember; a definition change
                    # (drop/recreate) re-attaches and retries
                    with self._lock:
                        self._failed.add(rt.def_hash)
                        self._views.pop(rt.name, None)
                        self._rebuild_sources()

    def _init_view(self, rt: ViewRuntime) -> None:
        """Full compute at the current frontier: state + one rewrite
        commit.  Events at or below the captured frontier are skipped by
        the watermark; later ones replay on top."""
        from matrixone_tpu.utils import metrics as M
        t0 = time.perf_counter()
        with self._lock:
            if rt.groups is not None:     # raced another initializer
                return
            ts0 = self.engine.committed_ts
            rt.replace_state(self._compute_groups(rt.spec, ts0), ts0)
            self._rewrite_backing(rt, set(rt.groups), full=True)
        M.mview_apply.inc(tier="init")
        M.mview_apply_seconds.inc(time.perf_counter() - t0, kind="full")

    # ----------------------------------------------------------- draining
    def _drain_all(self) -> None:
        """Apply every queued event; when another thread is applying,
        wait it out so the caller's read-your-writes holds."""
        while True:
            with self._qlock:
                if not self._queue:
                    if not self._applying:
                        return
                    self._qcv.wait(timeout=1.0)
                    continue
                if self._applying:
                    self._qcv.wait(timeout=1.0)
                    continue
                batch, self._queue = self._queue, []
                self._applying = True
            try:
                self._apply_events(batch)
            finally:
                with self._qlock:
                    self._applying = False
                    self._qcv.notify_all()

    def _apply_events(self, events: List[tuple]) -> None:
        """Apply one popped batch, grouped into per-commit runs so a
        view's watermark only advances over FULLY applied commits."""
        i = 0
        while i < len(events):
            ts = events[i][0]
            j = i
            while j < len(events) and events[j][0] == ts:
                j += 1
            run = events[i:j]
            i = j
            for rt in list(self._views.values()):
                mine = [e for e in run if e[1] == rt.spec.source]
                if not mine:
                    continue
                try:
                    with self._lock:
                        self._apply_run(rt, ts, mine)
                except Exception:   # noqa: BLE001 — a failed apply must
                    # never leave silently-wrong state: poison it and
                    # let the next commit re-initialize from scratch
                    with self._lock:
                        rt.invalidate()

    def _apply_run(self, rt: ViewRuntime, ts: int, run: List[tuple]
                   ) -> None:
        """One commit's events for one view (deletes precede inserts by
        funnel order): merge deltas, recompute MIN/MAX-retracted groups,
        rewrite the changed backing rows, advance the watermark."""
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils import motrace
        if rt.groups is None or ts <= rt.watermark:
            return
        t0 = time.perf_counter()
        with motrace.span("mview.apply", view=rt.name,
                          events=len(run)):
            self._apply_run_traced(rt, ts, run, t0, M)

    def _apply_run_traced(self, rt: ViewRuntime, ts: int,
                          run: List[tuple], t0: float, M) -> None:
        touched: set = set()
        recompute: set = set()
        for _ts, _table, kind, payload in run:
            if kind == "insert":
                seg = payload
                delta = self._delta_partials(
                    rt, seg.arrays, seg.validity, seg.n_rows)
                touched |= rt.merge_delta(delta, +1, ts)
            else:
                arrays, validity, n = payload
                delta = self._delta_partials(rt, arrays, validity, n)
                if rt.spec.has_minmax:
                    recompute |= set(delta)
                touched |= rt.merge_delta(delta, -1, ts)
        if recompute:
            # retraction of an extremum is not subtractable: replace the
            # affected groups' state from the source at this snapshot
            live = recompute & set(rt.groups)
            fresh = self._compute_groups(rt.spec, ts, only_keys=live)
            san.mutating(rt)
            for key in live:
                g = fresh.get(key)
                if g is None:
                    rt.groups.pop(key, None)
                else:
                    rt.groups[key] = g
            rt.watermark = max(rt.watermark, ts)
            M.mview_apply.inc(tier="recompute")
        self._rewrite_backing(rt, touched | recompute)
        M.mview_apply_seconds.inc(time.perf_counter() - t0, kind="delta")

    # =================================================== delta evaluation
    def _delta_execbatch(self, spec: MaintainSpec, arrays, validity,
                         n: int) -> ExecBatch:
        from matrixone_tpu.vm.operators import chunk_to_execbatch
        t = self.engine.get_table(spec.source)
        return chunk_to_execbatch(arrays, validity, t.dicts, n,
                                  spec.scan_columns, spec.scan_schema)

    def _delta_partials(self, rt: ViewRuntime, arrays, validity, n: int
                        ) -> Dict[tuple, dict]:
        """Per-group partials of one delta (a segment's rows or the
        decoded rows behind a tombstone): filters + keys + aggregate
        arguments evaluated over a device batch, grouped host-side.
        The dense tier compiles the WHOLE evaluation into one cached
        XLA program (one dispatch per delta)."""
        from matrixone_tpu.utils import metrics as M
        spec = rt.spec
        ex = self._delta_execbatch(spec, arrays, validity, n)
        M.mview_rows.inc(n)
        dense = self._dense_delta(rt, ex)
        if dense is not None:
            M.mview_apply.inc(tier="dense")
            return dense
        M.mview_apply.inc(tier="general")
        return self._general_delta(spec, ex)

    # ---- general tier (host groupby; any maintainable shape)
    def _general_delta(self, spec: MaintainSpec, ex: ExecBatch
                       ) -> Dict[tuple, dict]:
        from matrixone_tpu.vm import operators as O
        for f in spec.filters:
            ex.mask = ex.mask & F.predicate_mask(eval_expr(f, ex),
                                                 ex.batch)
        mask = np.asarray(jax.device_get(ex.mask))
        keys_host = []
        for k in spec.group_keys:
            col = O._broadcast_full(eval_expr(k, ex), ex.padded_len)
            d = O._expr_dict(k, ex)
            keys_host.append((np.asarray(jax.device_get(col.data)),
                              np.asarray(jax.device_get(col.validity)),
                              k.dtype, d))
        vals_host = []
        for a in spec.aggs:
            if a.arg is None:
                vals_host.append(None)
                continue
            col = O._broadcast_full(eval_expr(_partial_arg(a), ex),
                                    ex.padded_len)
            vals_host.append(
                (np.asarray(jax.device_get(col.data)),
                 np.asarray(jax.device_get(col.validity))))
        out: Dict[tuple, dict] = {}
        for i in np.nonzero(mask)[0]:
            key = tuple(_norm_key(dref, int(i), data, valid, dtype)
                        for data, valid, dtype, dref in keys_host)
            g = out.get(key)
            if g is None:
                g = {"rows": 0,
                     "parts": [dict.fromkeys(_maintain_fields(a), None)
                               for a in spec.aggs]}
                out[key] = g
            g["rows"] += 1
            for a, part, vh in zip(spec.aggs, g["parts"], vals_host):
                if a.arg is None:               # count(*)
                    part["count"] = (part["count"] or 0) + 1
                    continue
                data, valid = vh
                if not valid[i]:
                    continue
                part["count"] = (part["count"] or 0) + 1
                v = data[i].item()
                if a.func in ("sum", "avg"):
                    part["sum"] = v if part["sum"] is None \
                        else part["sum"] + v
                elif a.func == "min":
                    part["min"] = v if part["min"] is None \
                        else min(part["min"], v)
                elif a.func == "max":
                    part["max"] = v if part["max"] is None \
                        else max(part["max"], v)
        return out

    # ---- dense tier (one compiled dispatch; the Q1 shape)
    def _dense_delta(self, rt: ViewRuntime, ex: ExecBatch
                     ) -> Optional[Dict[tuple, dict]]:
        from matrixone_tpu.vm import fusion
        from matrixone_tpu.vm import operators as O
        spec = rt.spec
        if any(a.func not in _DENSE_FUNCS for a in spec.aggs):
            return None
        sizes, key_dicts = [], []
        for k in spec.group_keys:
            d = fusion._static_dict(k, ex.dicts)
            if d is not None:
                sizes.append(max(len(d), 1))
                key_dicts.append(d)
            elif k.dtype.oid == TypeOid.BOOL:
                sizes.append(2)
                key_dicts.append(None)
            else:
                return None
        g = 1
        for s in sizes:
            g *= s + 1
        if g > 4096:
            return None               # unroll budget (delta shapes are
        sizes = tuple(sizes)          # dashboards: a handful of groups)
        # content-addressed compile key: view definition, batch shape/
        # dtypes, dense sizes, and the CONTENT of every dictionary an
        # expression may bake into a LUT (grown dict => retrace)
        t = self.engine.get_table(spec.source)
        colsig = tuple((nm, int(c.dtype.oid), tuple(c.data.shape))
                       for nm, c in ex.batch.columns.items())
        dict_keys = tuple(fusion._dict_key(t.dicts.get(c))
                          for c in spec.scan_columns
                          if c in t.dicts)
        key = ("mview", rt.def_hash, colsig, int(ex.mask.shape[0]),
               sizes, dict_keys)
        entry = fusion.CACHE.entry(key)
        from matrixone_tpu.utils import keys as keyaudit
        if keyaudit.armed():
            # full dictionary CONTENT recomputed independently of
            # fusion._dict_key: a length-only regression in the compile
            # key (the PR-7 class) mismatches here on the first
            # colliding hit instead of serving stale delta partials
            keyaudit.audit("mview/maintain.py:mview", key, {
                "scan_dict_content": tuple(
                    (c, tuple(str(s) for s in t.dicts[c]))
                    for c in spec.scan_columns if c in t.dicts),
                "env_dict_content": tuple(
                    sorted((nm, tuple(str(s) for s in d))
                           for nm, d in ex.dicts.items()
                           if d is not None)),
                "sizes": sizes,
                "shape": (len(spec.filters), len(spec.group_keys),
                          len(spec.aggs)),
            })
        fn = entry["fn"].get("step")
        if fn is None:
            trig = tuple((nm, c.dtype)
                         for nm, c in ex.batch.columns.items())
            fn, fieldmap = self._make_dense_step(spec, trig, sizes,
                                                 dict(ex.dicts))
            entry["fn"]["step"] = fn
            entry["fieldmap"] = fieldmap
        fieldmap = entry["fieldmap"]
        datas = tuple(c.data for c in ex.batch.columns.values())
        valids = tuple(c.validity for c in ex.batch.columns.values())
        args = (datas, valids, jnp.asarray(ex.batch.n_rows, jnp.int32),
                ex.mask)
        from matrixone_tpu.utils import metrics as M
        if not entry["failed"]:
            compiled = entry["compiled"].get("step")
            if compiled is None:
                t0 = time.perf_counter()
                try:
                    compiled = jax.jit(fn).lower(*args).compile()
                except Exception:   # noqa: BLE001 — tracer rejection:
                    entry["failed"] = True      # eager fallback below
                    M.fusion_compile.inc(outcome="trace_fail")
                else:
                    entry["compiled"]["step"] = compiled
                    entry["trace_s"] += time.perf_counter() - t0
            if not entry["failed"]:
                out = entry["compiled"]["step"](*args)
                M.fusion_dispatch.inc(kind="step")
                return self._dense_to_groups(spec, out, sizes,
                                             key_dicts, fieldmap)
        out = fn(*args)               # eager: identical math
        M.fusion_dispatch.inc(kind="eager")
        return self._dense_to_groups(spec, out, sizes, key_dicts,
                                     fieldmap)

    def _make_dense_step(self, spec: MaintainSpec, trig_schema, sizes,
                         env0):
        """Build the delta step: filters -> keys -> deduplicated partial
        lanes -> dense_lane_partials, all inside one traceable function
        (jit-compiled when possible, called eagerly otherwise — one
        implementation, so the two modes cannot diverge)."""
        from matrixone_tpu.vm import fusion
        from matrixone_tpu.vm import operators as O
        # static lane layout (mirrors AggOp._dense_step's dedup)
        lane_of: Dict[tuple, tuple] = {}
        int_specs: List[tuple] = []      # (agg_idx|None, field)
        float_specs: List[tuple] = []
        fieldmap: List[List[tuple]] = []  # per agg: (field, lane)
        for ai, a in enumerate(spec.aggs):
            fm = []
            for f in _maintain_fields(a):
                if f == "count":
                    lk = ("count", None if a.arg is None
                          else fusion._dedup_sig(a.arg))
                    cls = "int"
                else:
                    arg = _partial_arg(a)
                    cls = "float" if arg.dtype.is_float else "int"
                    lk = ("sum", cls, fusion._dedup_sig(arg))
                lane = lane_of.get(lk)
                if lane is None:
                    if cls == "int":
                        lane = ("int", len(int_specs))
                        int_specs.append((ai, f))
                    else:
                        lane = ("float", len(float_specs))
                        float_specs.append((ai, f))
                    lane_of[lk] = lane
                fm.append((f, lane))
            fieldmap.append(fm)

        def step(datas, valids, n_rows, mask):
            cols = {nm: DeviceColumn(d, v, t)
                    for (nm, t), d, v in zip(trig_schema, datas, valids)}
            ex = ExecBatch(batch=DeviceBatch(columns=cols,
                                             n_rows=n_rows),
                           dicts=env0, mask=mask)
            for f in spec.filters:
                ex.mask = ex.mask & F.predicate_mask(
                    eval_expr(f, ex), ex.batch)
            n = ex.padded_len
            kdata, kvalid = [], []
            for k in spec.group_keys:
                kc = O._broadcast_full(eval_expr(k, ex), n)
                kdata.append(kc.data)
                kvalid.append(kc.validity)
            val_cache: dict = {}

            def _val(arg):
                sig = fusion._dedup_sig(arg)
                got = val_cache.get(sig)
                if got is None:
                    got = O._broadcast_full(eval_expr(arg, ex), n)
                    val_cache[sig] = got
                return got

            int_vals, int_masks = [], []
            float_vals, float_masks = [], []
            for ai, f in int_specs:
                a = spec.aggs[ai]
                if f == "count":
                    if a.arg is None:
                        int_vals.append(None)
                        int_masks.append(None)
                    else:
                        v = _val(_partial_arg(a))
                        int_vals.append(None)
                        int_masks.append(v.validity)
                else:
                    v = _val(_partial_arg(a))
                    int_vals.append(v.data)
                    int_masks.append(v.validity)
            for ai, f in float_specs:
                v = _val(_partial_arg(spec.aggs[ai]))
                float_vals.append(v.data)
                float_masks.append(v.validity)
            return A.dense_lane_partials(
                tuple(kdata), tuple(kvalid), ex.mask,
                tuple(int_vals), tuple(int_masks),
                tuple(float_vals), tuple(float_masks),
                sizes=sizes, with_null=True)

        return step, fieldmap

    def _dense_to_groups(self, spec: MaintainSpec, out, sizes,
                         key_dicts, fieldmap) -> Dict[tuple, dict]:
        """Dense lanes -> {key tuple: partials}, decoding NULL-slotted
        mixed-radix slots back to key values."""
        ints, floats, rows = (np.asarray(jax.device_get(x))
                              for x in out)
        strides, _g = A.dense_slot_strides(sizes)    # NULL-slotted radix
        groups: Dict[tuple, dict] = {}
        for slot in np.nonzero(rows)[0]:
            key = []
            for k, s, st, d in zip(spec.group_keys, sizes, strides,
                                   key_dicts):
                code = (int(slot) // st) % (s + 1)
                if code >= s:
                    key.append(None)
                elif d is not None:
                    key.append(d[code])
                elif k.dtype.oid == TypeOid.BOOL:
                    key.append(bool(code))
                else:
                    key.append(int(code))
            key = tuple(key)
            parts = []
            for a, fm in zip(spec.aggs, fieldmap):
                part = dict.fromkeys(_maintain_fields(a), None)
                for f, lane in fm:
                    arr = ints if lane[0] == "int" else floats
                    v = arr[lane[1]][slot]
                    part[f] = float(v) if lane[0] == "float" else int(v)
                parts.append(part)
            groups[key] = {"rows": int(rows[slot]), "parts": parts}
        return groups

    # =============================================== full/partial compute
    def _partial_plan(self, spec: MaintainSpec):
        """The partial-aggregate plan: same scan/filters/keys as the
        view, aggregates rewritten to their additive partials so the
        result converts straight into maintenance state.  Runs through
        the ordinary compile_plan pipeline (dense path, fusion and all),
        so the init/recompute numbers are the engine's own."""
        from matrixone_tpu.sql.binder import _agg_result_type
        scan = P.Scan(spec.source, list(spec.scan_columns),
                      list(spec.scan_schema),
                      filters=list(spec.filters))
        paggs: List[AggCall] = [AggCall("count", None, False, dt.INT64,
                                        out_name="_rows")]
        layout: List[dict] = []          # per agg: field -> out index
        from matrixone_tpu.vm import fusion
        seen: Dict[tuple, int] = {}
        for a in spec.aggs:
            fmap = {}
            for f in _maintain_fields(a):
                if f == "count" and a.arg is None:
                    fmap[f] = 0           # count(*) IS the rows lane
                    continue
                arg = a.arg if f in ("count", "min", "max") \
                    else _partial_arg(a)
                func = {"count": "count", "sum": "sum", "min": "min",
                        "max": "max"}[f]
                sk = (func, fusion._dedup_sig(arg))
                idx = seen.get(sk)
                if idx is None:
                    out_t = _agg_result_type(func, arg.dtype)
                    idx = len(paggs)
                    paggs.append(AggCall(func, arg, False, out_t,
                                         out_name=f"_p{idx}"))
                    seen[sk] = idx
                fmap[f] = idx
            layout.append(fmap)
        schema = [(f"_g{i}", k.dtype)
                  for i, k in enumerate(spec.group_keys)] + \
            [(a.out_name, a.dtype) for a in paggs]
        return P.Aggregate(scan, list(spec.group_keys), paggs,
                           schema), layout

    def _compute_groups(self, spec: MaintainSpec, ts: int,
                        only_keys: Optional[set] = None
                        ) -> Dict[tuple, dict]:
        """Full (or key-restricted) partial compute at snapshot `ts`
        through the ordinary operator pipeline — the init / restart-
        rebuild / MIN-MAX-recompute path."""
        from matrixone_tpu.vm.compile import compile_plan
        from matrixone_tpu.vm.process import ExecContext
        node, layout = self._partial_plan(spec)
        ctx = ExecContext(catalog=self.engine, txn=None,
                          variables={"batch_rows": 1 << 20},
                          frozen_ts=ts)
        op = compile_plan(node, ctx)
        nk = len(spec.group_keys)
        groups: Dict[tuple, dict] = {}
        for ex in op.execute():
            db = F.compact(ex.batch, ex.mask, ex.padded_len)
            b = from_device(db, ex.dicts, schema=dict(node.schema))
            n = len(b)
            if n == 0:
                continue
            kcols = []
            for (name, dtype), k in zip(node.schema[:nk],
                                        spec.group_keys):
                vec = b.columns[name]
                if dtype.is_varlen:
                    kcols.append(("s", vec.to_pylist(), None))
                else:
                    kcols.append((dtype, vec.data, vec.valid_mask()))
            pcols = []
            for name, _d in node.schema[nk:]:
                vec = b.columns[name]
                pcols.append((vec.data, vec.valid_mask()))
            for i in range(n):
                key = []
                for ent in kcols:
                    if ent[0] == "s":
                        key.append(ent[1][i])
                    else:
                        dtype, data, valid = ent
                        key.append(_norm_key(None, i, data, valid,
                                             dtype))
                key = tuple(key)
                if only_keys is not None and key not in only_keys:
                    continue
                rows = int(pcols[0][0][i])
                parts = []
                for a, fmap in zip(spec.aggs, layout):
                    part = dict.fromkeys(_maintain_fields(a), None)
                    for f, idx in fmap.items():
                        data, valid = pcols[idx]
                        if not valid[i]:
                            continue
                        v = data[i].item()
                        part[f] = float(v) if isinstance(v, float) \
                            else int(v)
                    parts.append(part)
                groups[key] = {"rows": rows, "parts": parts}
        return groups

    # ------------------------------------------------- backing rewrites
    def _rewrite_backing(self, rt: ViewRuntime, keys: set,
                         full: bool = False) -> None:
        """Land the changed groups in the backing table as ONE commit:
        delete the keys' existing rows, insert their fresh values.
        `full` rewrites everything (init / restart rebuild)."""
        if not keys and not full:
            return
        from matrixone_tpu.storage.engine import ROWID, live_rows
        spec = rt.spec
        t = self.engine.get_table(rt.name)
        names = [c for c, _ in t.meta.schema]
        # map backing columns back to (kind, idx) and locate key columns
        key_col_of = {}           # group_key idx -> backing column name
        for (kind, idx), name in zip(spec.out_cols, names):
            if kind == "key":
                key_col_of[idx] = name
        key_cols = [key_col_of[i] for i in range(len(spec.group_keys))]
        sd = dict(t.meta.schema)
        # existing rows for the touched keys (small: the view output)
        gids: List[int] = []
        for arrays, validity, dicts, n in map(live_rows, t.iter_chunks(
                key_cols + [ROWID], 1 << 20)):
            for i in range(n):
                key = []
                for c in key_cols:
                    if not validity[c][i]:
                        key.append(None)
                    elif sd[c].is_varlen:
                        key.append(dicts[c][int(arrays[c][i])])
                    elif sd[c].oid == TypeOid.BOOL:
                        key.append(bool(arrays[c][i]))
                    elif sd[c].is_float:
                        key.append(float(arrays[c][i]))
                    else:
                        key.append(int(arrays[c][i]))
                if full or tuple(key) in keys:
                    gids.append(int(arrays[ROWID][i]))
        live = [k for k in (rt.groups if full else keys)
                if k in rt.groups]
        inserts = {}
        if live:
            vals = {name: [] for name in names}
            valid = {name: [] for name in names}
            for key in live:
                g = rt.groups[key]
                for (kind, idx), name in zip(spec.out_cols, names):
                    if kind == "key":
                        v = key[idx]
                        vals[name].append(v)
                        valid[name].append(v is not None)
                    else:
                        v, ok = _final_value(spec.aggs[idx],
                                             g["parts"][idx])
                        vals[name].append(v)
                        valid[name].append(ok)
            arrays2, validity2 = {}, {}
            for name in names:
                d = sd[name]
                vv = np.asarray(valid[name], np.bool_)
                if d.is_varlen:
                    arrays2[name] = t.encode_strings_list(
                        name, [v if ok else None
                               for v, ok in zip(vals[name],
                                                valid[name])])
                else:
                    filled = [v if ok else 0
                              for v, ok in zip(vals[name],
                                               valid[name])]
                    arrays2[name] = np.asarray(filled, d.np_dtype)
                validity2[name] = vv
            inserts = {rt.name: [(arrays2, validity2)]}
        if not inserts and not gids:
            return
        self.engine.commit_txn(
            None, inserts,
            {rt.name: np.asarray(gids, np.int64)} if gids else {})

    # ------------------------------------------- dynamic-table upgrade
    def refresh_dynamic(self, name: str, sql: str) -> Optional[int]:
        """Delta refresh for a maintainable dynamic table (the silent
        upgrade from DELETE+INSERT): replay the shared commit-delta
        stream (cdc.delta_events) past the watermark.  Returns the view
        row count, or None when the shape is not maintainable (caller
        falls back to the full rematerialize)."""
        import hashlib
        from matrixone_tpu.cdc import delta_events
        from matrixone_tpu.utils import metrics as M
        dh = hashlib.sha1(sql.encode()).hexdigest()
        rt = self._dynamic.get(name)
        if rt is None or rt.def_hash != dh:
            from matrixone_tpu.sql.parser import parse
            try:
                stmts = parse(sql)
                spec, _why = analyze(stmts[0], self.engine)
            except Exception:   # noqa: BLE001 — unparseable/unbindable:
                return None     # the full-refresh path reports it
            if spec is None:
                return None
            rt = ViewRuntime(name, spec, dh)
            san.guard(rt, self._lock, name=f"MViewRuntime[{name}]")
            self._dynamic[name] = rt
            # pin this runtime's replay history: fence GC defers any
            # compaction fence of the source until the runtime's
            # watermark passes it (delta-aware GC), so refreshes across
            # a background merge stay incremental
            reg = getattr(self.engine, "register_watermark", None)
            if reg is not None:
                reg(f"dyn:{name}", rt.spec.source,
                    lambda rt=rt: rt.watermark if rt.groups is not None
                    else None)
        was = getattr(self._maint, "active", False)
        self._maint.active = True
        try:
            with self._lock:
                src = self.engine.get_table(rt.spec.source)
                floor = getattr(src, "delta_floor", 0)
                if rt.groups is None or rt.watermark < floor:
                    # DEGRADE RUNG: first delta refresh, or the merge
                    # fence below our watermark was GC'd (history gone)
                    # — rebuild from scratch.  A merge whose fence is
                    # still held replays incrementally below via
                    # delta_events' exactly-once fence windows.
                    ts0 = self.engine.committed_ts
                    rt.replace_state(
                        self._compute_groups(rt.spec, ts0), ts0)
                    self._rewrite_backing(rt, set(rt.groups), full=True)
                    M.mview_apply.inc(tier="init")
                    return len(rt.groups)
                events = delta_events(self.engine, rt.spec.source,
                                      rt.watermark + 1)
                i = 0
                while i < len(events):
                    ts = events[i][0]
                    run = []
                    while i < len(events) and events[i][0] == ts:
                        ets, kind, payload = events[i]
                        if kind == "delete":
                            gids = np.asarray(payload, np.int64)
                            arrays, validity = src.fetch_rows(
                                gids, rt.spec.scan_columns)
                            payload = (arrays, validity, len(gids))
                        run.append((ets, rt.spec.source, kind, payload))
                        i += 1
                    self._apply_run(rt, ts, run)
                return len(rt.groups)
        finally:
            self._maint.active = was


_SERVICE_LOCK = san.lock("matrixone_tpu.mview._SERVICE_LOCK")


def service_for(engine) -> MViewService:
    """One maintenance service per engine (the TN / embedded engine —
    CN replicas never maintain; their backing rows arrive from the TN
    through the logtail)."""
    host = getattr(engine, "_inner", engine)
    svc = getattr(host, "_mview_service", None)
    if svc is None:
        with _SERVICE_LOCK:
            svc = getattr(host, "_mview_service", None)
            if svc is None:
                svc = MViewService(host)
                host._mview_service = svc
    return svc


def _final_value(a: AggCall, part: dict) -> Tuple[object, bool]:
    """Finalize one group's partial into the backing-stored value —
    mirrors vm/operators._grouped_final exactly (decimal sums stay
    scaled ints; avg divides in float64)."""
    c = part.get("count") or 0
    if a.func == "count":
        return int(c), True
    if c <= 0:
        return None, False
    if a.func == "sum":
        return part["sum"], True
    if a.func == "avg":
        s = float(part["sum"])
        if a.arg.dtype.oid == TypeOid.DECIMAL64:
            s = s / (10.0 ** a.arg.dtype.scale)
        return s / max(c, 1), True
    return part[a.func], True


def _norm_key(dref, i: int, data, valid, dtype):
    """State key of one evaluated group-key cell, at STORED
    representation (varchar decoded so dictionary growth can't alias)."""
    if not valid[i]:
        return None
    if dref is not None:
        return dref[int(data[i])]
    if dtype.oid == TypeOid.BOOL:
        return bool(data[i])
    if dtype.is_float:
        return float(data[i])
    return int(data[i])
