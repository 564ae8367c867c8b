"""Session: SQL text in, result batches out.

Reference analogue: the frontend's doComQuery -> buildPlan -> Compile -> Run
chain (`frontend/mysql_cmd_executor.go:4160`) minus the wire protocol (the
server lives in matrixone_tpu.frontend.server). DDL/DML execute directly
against the catalog; SELECT goes parse -> bind -> compile -> pull loop ->
host Batch.
"""

from __future__ import annotations

import contextvars
import dataclasses
import datetime
from typing import List, Optional

import jax
import numpy as np

from matrixone_tpu.container import Batch, Vector, dtypes as dt, from_device
from matrixone_tpu.container.dtypes import DType, TypeOid
from matrixone_tpu.sql import ast, plan as P
from matrixone_tpu.sql.binder import Binder, BindError, type_from_name
from matrixone_tpu.sql.parser import parse
from matrixone_tpu.storage.engine import (Catalog, Engine, IndexMeta,
                                          TableMeta)
from matrixone_tpu.storage.engine import ROWID
from matrixone_tpu.txn.client import TxnClient, TxnState
from matrixone_tpu.vm.compile import compile_plan
from matrixone_tpu.vm.process import ExecContext

#: the session currently executing a statement on this thread — info
#: functions (connection_id()/user()/last_insert_id()/...) resolve
#: against it at bind time (reference: frontend session variables)
_CURRENT_SESSION: contextvars.ContextVar = contextvars.ContextVar(
    "mo_current_session", default=None)


def current_session():
    return _CURRENT_SESSION.get()


@dataclasses.dataclass
class Result:
    batch: Optional[Batch] = None        # SELECT results
    affected: int = 0                    # DML row count
    text: Optional[str] = None           # EXPLAIN / SHOW output

    def rows(self) -> List[tuple]:
        if self.batch is None:
            return []
        names = list(self.batch.columns)
        cols = [self.batch.columns[n].to_pylist() for n in names]
        return [tuple(vals) for vals in zip(*cols)] if cols else []

    @property
    def column_names(self) -> List[str]:
        return list(self.batch.columns) if self.batch is not None else []


class Session:
    """One client session (reference: frontend.Session); system variables
    and (later) transaction state hang off this object."""

    def __init__(self, catalog: Optional[Engine] = None, fs=None,
                 user: str = "root", auth=None, auth_manager=None):
        from matrixone_tpu.queryservice import registry_for
        self.catalog = catalog if catalog is not None else Engine(fs)
        #: AuthContext of the logged-in user (None = trusted embedded
        #: session, unrestricted); non-sys accounts see a tenant-scoped
        #: catalog (frontend/auth.py, reference: authenticate.go)
        self.auth = auth
        self.auth_mgr = auth_manager
        if auth is not None and auth.account != "sys":
            from matrixone_tpu.frontend.auth import ScopedCatalog
            self.catalog = ScopedCatalog(self.catalog, auth.account)
        # a NEW session on a CN starts at the cluster frontier (the
        # reference's reads gate on the logtail reaching the snapshot;
        # here one catch-up per connection keeps cross-connection
        # read-your-writes without a per-statement RPC)
        sync = getattr(self.catalog, "sync_frontier", None)
        if sync is not None:
            sync()
        self.txn_client = TxnClient(self.catalog)
        self.txn = None                 # active explicit transaction
        self.last_insert_id = 0         # MySQL LAST_INSERT_ID()
        import os as _os
        self.variables = {"gpu_mode": 1, "batch_rows": 1 << 20,
                          # SET ivf_shards = N routes vector queries onto
                          # an N-device mesh (vm/vector_scan.py); the env
                          # default serves deployments that shard always
                          "ivf_shards": int(_os.environ.get(
                              "MO_IVF_SHARDS", "0") or 0),
                          # SET query_shards = N routes eligible SQL
                          # fragments onto an N-device mesh
                          # (parallel/dist_query.py shard executor)
                          "query_shards": int(_os.environ.get(
                              "MO_QUERY_SHARDS", "0") or 0)}
        self._procs = registry_for(self.catalog)
        self._admission_depth = 0      # re-entrant execute() guard
        self.conn_id = self._procs.register(user if auth is None
                                            else f"{auth.account}:"
                                                 f"{auth.user}")

    def close(self) -> None:
        """Release the session's process-registry slot (the wire server
        and embed cluster call this on disconnect/shutdown)."""
        self._procs.unregister(self.conn_id)

    def _ctx(self, frozen_ts: Optional[int] = None) -> ExecContext:
        if frozen_ts is None and self.txn is None:
            frozen_ts = self.catalog.committed_ts
        return ExecContext(catalog=self.catalog, txn=self.txn,
                           variables=self.variables,
                           frozen_ts=(None if self.txn is not None
                                      else frozen_ts))

    def _index_skip_tables(self) -> frozenset:
        """Index rewrites serve only frontier (autocommit) reads: an open
        txn reads an older snapshot + workspace that a frontier-built
        index cannot realize."""
        if self.txn is not None:
            return frozenset(self.catalog.tables)
        return frozenset()

    # ------------------------------------------------------------ execute
    def execute(self, sql: str, params: Optional[list] = None) -> Result:
        from matrixone_tpu.utils import motrace
        # the statement is the trace boundary: parse, cache lookups,
        # admission wait, fragment compile/dispatch, RPC hops, worker
        # offload and TN commit all become children of this root span
        # (re-entrant executes nest as child spans, not new traces)
        with motrace.statement_span(sql):
            return self._execute_traced(sql, params)

    def _execute_traced(self, sql: str,
                        params: Optional[list] = None) -> Result:
        import time as _time
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils import motrace
        from matrixone_tpu.utils.trace import STMT_TABLE, StatementRecorder
        # statement tracing is engine-global (one system table), never
        # tenant-scoped — always hang it off the TRUE engine: unwrap the
        # tenant scope AND the CN's RemoteCatalog facade. Writing through
        # the facade is how round 5's nastiest bug happened: the trace
        # flush's `engine.committed_ts = ...` created an INSTANCE
        # attribute on the RemoteCatalog that permanently shadowed the
        # replica's live committed_ts behind __getattr__, freezing every
        # later transaction's begin snapshot (stale snapshots ->
        # spurious write-write conflicts on busy CN sessions)
        rec_host = getattr(self.catalog, "_inner", self.catalog)
        rec_host = getattr(rec_host, "_replica", rec_host)
        if not hasattr(rec_host, "stmt_recorder"):
            rec_host.stmt_recorder = StatementRecorder(rec_host)
        if STMT_TABLE in sql:
            self.catalog.stmt_recorder.flush()
        # serving layer (matrixone_tpu/serving): normalize the statement
        # and route repeated shapes through the plan/result caches; falls
        # back to the raw parse path whenever anything is off-template
        sv = self._serving_prepare(sql, params)
        stmts = sv.make_stmts() if sv is not None else None
        if stmts is None:
            # raw path: first occurrence of a template (or an
            # unusable one) — the result cache still participates
            # through sv, the plan cache does not (template_mode off)
            if sv is not None:
                sv.template_mode = False
                if not sv.result_enabled():
                    sv = None
            with motrace.span("parse"):
                stmts = parse(sql)
            if params is not None:
                stmts = [_substitute_params(st, params) for st in stmts]
        _tok = _CURRENT_SESSION.set(self)
        try:
            return self._execute_stmts(stmts, sql, sv)
        finally:
            _CURRENT_SESSION.reset(_tok)

    def _serving_prepare(self, sql: str, params):
        """-> _ServingCtx when this statement may use the serving caches
        (single statement, autocommit, deterministic, plain params)."""
        if self.txn is not None:
            return None
        from matrixone_tpu.serving import serving_for
        state = serving_for(self.catalog)
        if not (state.plan_cache.enabled or state.result_cache.enabled):
            return None
        # nondeterministic UDFs must bypass both caches exactly like
        # now()/rand(): feed the registry's nondet names to statement
        # normalization (version-cached: a few attr reads when idle)
        from matrixone_tpu.udf.catalog import sync_serving as _udf_sync
        _udf_sync(self.catalog, state)
        norm = state.plan_cache.normalized(sql)
        if norm is None or norm.n_stmts != 1 or norm.nondet:
            return None
        try:
            full = norm.full_params(params)
        except (IndexError, TypeError, ValueError):
            return None            # arity mismatch: raw path raises it
        for p in full:
            if not isinstance(p, (int, float, str, bool, type(None),
                                  datetime.date)):
                return None
        return _ServingCtx(state, norm, full, self._acct())

    def _execute_stmts(self, stmts, sql: str, serving=None) -> Result:
        import time as _time
        from matrixone_tpu.serving import serving_for
        from matrixone_tpu.utils import metrics as M
        from matrixone_tpu.utils import motrace
        adm = serving_for(self.catalog).admission
        results = []
        # per-statement span attribution in a multi-statement batch:
        # the shared statement-root trace is one ring sequence; each
        # statement records only the spans past the previous mark (the
        # first statement's window starts at 0 so it owns `parse`)
        tr_mark = 0
        for st in stmts:
            if self._procs.is_terminated(self.conn_id):
                from matrixone_tpu.queryservice import QueryKilled
                raise QueryKilled(
                    f"connection {self.conn_id} was killed")
            t0 = _time.perf_counter()
            self._procs.start_query(self.conn_id, sql)
            self._liid_set = False     # last_insert_id(): per statement
            ann = {"cache_hit": "none", "queue_wait_ms": 0}
            self._exec_ann = ann
            ticket = None
            try:
                with motrace.span("run", stmt=type(st).__name__):
                    if adm.enabled and self._admission_gated(st):
                        lane = ("background" if str(self.variables.get(
                            "query_priority", "")).lower() == "background"
                            else "interactive")
                        ticket = adm.acquire(account=self._acct(),
                                             lane=lane,
                                             conn_id=self.conn_id,
                                             registry=self._procs)
                        self._admission_depth += 1
                        ann["queue_wait_ms"] = int(
                            ticket.queue_wait_s * 1000)
                    r = self._execute_stmt(st, serving)
                    motrace.annotate(cache_hit=ann["cache_hit"])
            except Exception as e:   # noqa: BLE001 — recorded, re-raised
                dt_ = _time.perf_counter() - t0
                M.query_seconds.observe(dt_)
                tr_id, n_sp, summ, tree = motrace.statement_record(
                    dt_ * 1000.0, since=tr_mark)
                self.catalog.stmt_recorder.record(
                    sql, "error", dt_, 0, error=str(e)[:1024],
                    cache_hit=ann["cache_hit"],
                    queue_wait_ms=ann["queue_wait_ms"],
                    trace_id=tr_id, span_count=n_sp,
                    span_summary=summ, span_tree=tree)
                raise
            finally:
                if ticket is not None:
                    self._admission_depth -= 1
                    ticket.release()
                self._procs.end_query(self.conn_id)
            dt_ = _time.perf_counter() - t0
            M.query_seconds.observe(dt_)
            rows_out = len(r.batch) if r.batch is not None else r.affected
            # slow-query hook: past MO_TRACE_SLOW_MS the FULL span tree
            # persists into the statement table (motrace.statement_record)
            tr_id, n_sp, summ, tree = motrace.statement_record(
                dt_ * 1000.0, since=tr_mark)
            tr_mark += n_sp
            self.catalog.stmt_recorder.record(
                sql, "ok", dt_, rows_out, cache_hit=ann["cache_hit"],
                queue_wait_ms=ann["queue_wait_ms"],
                trace_id=tr_id, span_count=n_sp, span_summary=summ,
                span_tree=tree)
            results.append(r)
        return results[-1] if results else Result()

    def _admission_gated(self, st: ast.Node) -> bool:
        """Workload statements pass admission; control statements (SET,
        txn control, KILL, SHOW, mo_ctl) never queue — an operator must
        always be able to inspect and kill. Re-entrant executes (dynamic
        table refresh inside an admitted statement) bypass too, or a
        1-slot server would deadlock against itself."""
        if self._admission_depth > 0:
            return False
        if isinstance(st, (ast.Select, ast.Union)):
            return not self._is_ctl_select(st)
        return isinstance(st, (ast.Insert, ast.Update, ast.Delete,
                               ast.LoadData))

    @staticmethod
    def _is_ctl_select(st: ast.Node) -> bool:
        return (isinstance(st, ast.Select) and st.from_ is None
                and len(st.items) == 1
                and isinstance(st.items[0].expr, ast.FuncCall)
                and st.items[0].expr.name == "mo_ctl")

    # ------------------------------------------------------ privileges
    def _mgr(self):
        """The engine's AccountManager (shared; lazily bootstrapped so
        embedded sessions can manage accounts too)."""
        if self.auth_mgr is not None:
            return self.auth_mgr
        inner = getattr(self.catalog, "_inner", self.catalog)
        mgr = getattr(inner, "_auth_mgr", None)
        if mgr is None:
            from matrixone_tpu.frontend.auth import AccountManager
            mgr = AccountManager(inner)
            inner._auth_mgr = mgr
        self.auth_mgr = mgr
        return mgr

    def _acct(self) -> str:
        return self.auth.account if self.auth is not None else "sys"

    def _visible_account(self) -> Optional[str]:
        """Process-registry visibility scope: None = cluster-wide (sys
        tenant / embedded sessions), else restricted to this account."""
        if self.auth is None or self.auth.account == "sys":
            return None
        return self.auth.account

    def _check(self, priv: str, obj: str = "*") -> None:
        if self.auth is None or self.auth.is_admin:
            return
        self._mgr().check(self.auth, priv, obj)

    def _check_admin(self) -> None:
        if self.auth is not None and not self.auth.is_admin:
            from matrixone_tpu.frontend.auth import AuthError
            raise AuthError(
                f"access denied: {self.auth.user!r} is not an account "
                f"administrator")

    def _enforce(self, stmt: ast.Node) -> None:
        """Per-statement privilege gate (reference: authenticate.go
        determinePrivilege + privilege check before execution)."""
        if self.auth is None or self.auth.is_admin:
            return
        if isinstance(stmt, ast.Insert):
            self._check("insert", stmt.table)
        elif isinstance(stmt, ast.Update):
            self._check("update", stmt.table)
        elif isinstance(stmt, ast.Delete):
            self._check("delete", stmt.table)
        elif isinstance(stmt, ast.LoadData):
            self._check("insert", stmt.table)
        elif isinstance(stmt, ast.DropTable):
            self._check("drop", stmt.name)
        elif isinstance(stmt, ast.CreateFunction):
            self._check("create")
        elif isinstance(stmt, ast.DropFunction):
            self._check("drop")
        elif isinstance(stmt, ast.DropMaterializedView):
            self._check("drop", stmt.name)
        elif isinstance(stmt, (ast.CreateTable, ast.CreateIndex,
                               ast.CreateExternalTable, ast.CreateSource,
                               ast.CreateDynamicTable, ast.CreateStage,
                               ast.CreateSnapshot, ast.CreatePublication,
                               ast.CreateMaterializedView,
                               ast.AlterPartition, ast.RestoreTable)):
            self._check("create")

    def _execute_stmt(self, stmt: ast.Node, serving=None) -> Result:
        self._enforce(stmt)
        acc = self._account_stmt(stmt)
        if acc is not None:
            return acc
        if isinstance(stmt, (ast.Select, ast.Union)):
            return self._select(stmt, serving=serving)
        if isinstance(stmt, ast.CreateTable):
            return self._create_table(stmt)
        if isinstance(stmt, ast.DropTable):
            from matrixone_tpu.mview import catalog as vcat
            if vcat.lookup(self.catalog, stmt.name) is not None:
                raise BindError(
                    f"{stmt.name!r} is a materialized view; use DROP "
                    f"MATERIALIZED VIEW")
            self.catalog.drop_table(stmt.name, stmt.if_exists)
            return Result()
        if isinstance(stmt, ast.CreateIndex):
            return self._create_index(stmt)
        if isinstance(stmt, ast.CreateFunction):
            return self._create_function(stmt)
        if isinstance(stmt, ast.DropFunction):
            return self._drop_function(stmt)
        if isinstance(stmt, ast.ShowFunctions):
            return self._show_functions()
        if isinstance(stmt, ast.Insert):
            return self._insert(stmt)
        if isinstance(stmt, ast.Explain):
            if isinstance(stmt.stmt, ast.Delete):
                # the plan that finds a DELETE's victims
                return Result(text=P.explain(self._dml_plan(
                    stmt.stmt.table, stmt.stmt.where)[0]))
            if not isinstance(stmt.stmt, (ast.Select, ast.Union)):
                raise BindError("EXPLAIN supports SELECT and DELETE only "
                                "for now")
            node = self._plan_select(stmt.stmt)
            if stmt.analyze:
                return Result(text=self._explain_analyze(node))
            anns = [a for a in (self._fragment_annotator(node),
                                self._mview_annotator(),
                                self._exchange_annotator(node))
                    if a is not None]
            annotate = (None if not anns else
                        (lambda pn: "".join(a(pn) for a in anns)))
            return Result(text=P.explain(node, annotate=annotate))
        if isinstance(stmt, ast.CreatePublication):
            self.catalog.create_publication(stmt.name, stmt.tables)
            return Result()
        if isinstance(stmt, ast.DropPublication):
            self.catalog.drop_publication(stmt.name)
            return Result()
        if isinstance(stmt, ast.ShowPublications):
            names = sorted(self.catalog.publications)
            b = Batch.from_pydict(
                {"Publication": names,
                 "Tables": [", ".join(self.catalog.publications[n])
                            for n in names]},
                {"Publication": dt.VARCHAR, "Tables": dt.VARCHAR})
            return Result(batch=b)
        if isinstance(stmt, ast.CreateSource):
            schema = [(c.name, type_from_name(c.type_name, c.type_args))
                      for c in stmt.columns]
            self.catalog.create_table(TableMeta(stmt.name, schema, []))
            self.catalog.mark_source(stmt.name)
            return Result()
        if isinstance(stmt, ast.CreateDynamicTable):
            return self._create_dynamic_table(stmt)
        if isinstance(stmt, ast.RefreshDynamicTable):
            from matrixone_tpu.stream import refresh_dynamic_table
            if stmt.name not in self.catalog.dynamic_tables:
                raise BindError(f"no such dynamic table {stmt.name!r}")
            n = refresh_dynamic_table(self, stmt.name)
            return Result(affected=n)
        if isinstance(stmt, ast.CreateMaterializedView):
            return self._create_materialized_view(stmt)
        if isinstance(stmt, ast.DropMaterializedView):
            return self._drop_materialized_view(stmt)
        if isinstance(stmt, ast.ShowMaterializedViews):
            return self._show_materialized_views()
        if isinstance(stmt, ast.RefreshMaterializedView):
            return Result(affected=self._refresh_mview(stmt.name))
        if isinstance(stmt, ast.LoadData):
            return self._load_data(stmt)
        if isinstance(stmt, ast.CreateStage):
            self.catalog.create_stage(stmt.name, stmt.url)
            return Result()
        if isinstance(stmt, ast.DropStage):
            self.catalog.drop_stage(stmt.name)
            return Result()
        if isinstance(stmt, ast.ShowStages):
            names = sorted(self.catalog.stages)
            b = Batch.from_pydict(
                {"Stage": names,
                 "URL": [self.catalog.stages[n] for n in names]},
                {"Stage": dt.VARCHAR, "URL": dt.VARCHAR})
            return Result(batch=b)
        if isinstance(stmt, ast.CreateExternalTable):
            schema = [(c.name, type_from_name(c.type_name, c.type_args))
                      for c in stmt.columns]
            fmt = _resolve_format(stmt.fmt, stmt.location)
            if stmt.snapshot is not None and fmt != "iceberg":
                raise BindError("SNAPSHOT applies to FORMAT iceberg only")
            self.catalog.create_external(
                TableMeta(stmt.name, schema, []), stmt.location, fmt,
                snapshot=stmt.snapshot)
            return Result()
        if isinstance(stmt, ast.ShowProcesslist):
            # tenant isolation (reference: authenticate.go account
            # scoping): the registry is engine-global, but a non-sys
            # session must not see other tenants' connections — their
            # SQL text can carry data
            from matrixone_tpu.queryservice import account_of
            pl = self._procs.processlist()
            scope = self._visible_account()
            if scope is not None:
                pl = [p for p in pl if account_of(p["User"]) == scope]
            b = Batch.from_pydict(
                {"Id": [p["Id"] for p in pl],
                 "User": [p["User"] for p in pl],
                 "State": [p["State"] for p in pl],
                 "Time": [p["Time"] for p in pl],
                 "Query": [p["Query"] for p in pl]},
                {"Id": dt.INT64, "User": dt.VARCHAR, "State": dt.VARCHAR,
                 "Time": dt.FLOAT64, "Query": dt.TEXT})
            return Result(batch=b)
        if isinstance(stmt, ast.Kill):
            scope = self._visible_account()
            owner = self._procs.owner_account(stmt.conn_id)
            if scope is not None and owner != scope:
                # cross-tenant KILL is a DoS vector; deny with ONE
                # indistinguishable error whether the conn is another
                # tenant's or nonexistent (no conn-id existence oracle)
                from matrixone_tpu.frontend.auth import AuthError
                raise AuthError(
                    f"access denied: connection {stmt.conn_id} does not "
                    f"belong to account {scope!r}")
            if owner is None:
                raise BindError(f"no connection {stmt.conn_id}")
            if not self._procs.kill(stmt.conn_id,
                                    query_only=stmt.query_only):
                raise BindError(f"no connection {stmt.conn_id}")
            return Result()
        if isinstance(stmt, ast.AlterPartition):
            return self._alter_partition(stmt)
        if isinstance(stmt, ast.ShowPartitions):
            return self._show_partitions(stmt)
        if isinstance(stmt, ast.AnalyzeTable):
            from matrixone_tpu.sql.stats import provider_for
            if getattr(self.catalog.get_table(stmt.name), "is_external",
                       False):
                raise BindError(
                    f"{stmt.name!r} is an external table; it has no "
                    f"segment statistics to analyze")
            st = provider_for(self.catalog).refresh(stmt.name)
            b = Batch.from_pydict(
                {"table": [stmt.name], "rows": [st.row_count],
                 "columns": [len(st.cols)]},
                {"table": dt.VARCHAR, "rows": dt.INT64,
                 "columns": dt.INT64})
            return Result(batch=b)
        if isinstance(stmt, ast.ShowTables):
            names = sorted(self.catalog.tables)
            b = Batch.from_pydict({"Tables": names},
                                  {"Tables": dt.VARCHAR})
            return Result(batch=b)
        if isinstance(stmt, ast.ShowCreateTable):
            t = self.catalog.get_table(stmt.name)
            cols = []
            for c, d in t.meta.schema:
                extra = " auto_increment" if c == t.meta.auto_increment else ""
                cols.append(f"  `{c}` {d}{extra}")
            if t.meta.primary_key:
                cols.append("  primary key ("
                            + ", ".join(t.meta.primary_key) + ")")
            ddl = f"create table `{stmt.name}` (\n" + ",\n".join(cols) + "\n)"
            b = Batch.from_pydict({"Table": [stmt.name],
                                   "Create Table": [ddl]},
                                  {"Table": dt.VARCHAR,
                                   "Create Table": dt.TEXT})
            return Result(batch=b)
        if isinstance(stmt, ast.ShowColumns):
            t = self.catalog.get_table(stmt.name)
            b = Batch.from_pydict(
                {"Field": [c for c, _ in t.meta.schema],
                 "Type": [str(d) for _, d in t.meta.schema],
                 "Key": ["PRI" if c in t.meta.primary_key else ""
                         for c, _ in t.meta.schema]},
                {"Field": dt.VARCHAR, "Type": dt.VARCHAR,
                 "Key": dt.VARCHAR})
            return Result(batch=b)
        if isinstance(stmt, ast.ShowIndexes):
            ixs = self.catalog.indexes_on(stmt.name)
            b = Batch.from_pydict(
                {"Key_name": [ix.name for ix in ixs],
                 "Algo": [ix.algo for ix in ixs],
                 "Columns": [",".join(ix.columns) for ix in ixs],
                 "Dirty": [int(ix.dirty) for ix in ixs]},
                {"Key_name": dt.VARCHAR, "Algo": dt.VARCHAR,
                 "Columns": dt.VARCHAR, "Dirty": dt.INT64})
            return Result(batch=b)
        if isinstance(stmt, ast.ShowVariables):
            import re as _re
            names = sorted(self.variables)
            if stmt.like:
                # SQL LIKE: only % and _ are wildcards; everything
                # else (incl. regex/fnmatch metachars) is literal
                pat = "".join(".*" if ch == "%" else "." if ch == "_"
                              else _re.escape(ch) for ch in stmt.like)
                rx = _re.compile(f"^{pat}$")
                names = [n for n in names if rx.match(n)]
            b = Batch.from_pydict(
                {"Variable_name": names,
                 "Value": [str(self.variables[n]) for n in names]},
                {"Variable_name": dt.VARCHAR, "Value": dt.VARCHAR})
            return Result(batch=b)
        if isinstance(stmt, ast.SetVariable):
            if isinstance(stmt.value, ast.Literal):
                value = stmt.value.value
                # fault injection control (reference: mo_ctl addfaultpoint)
                from matrixone_tpu.utils.fault import INJECTOR, parse_spec
                if stmt.name == "fault_point" and isinstance(value, str):
                    try:
                        INJECTOR.add(**parse_spec(value))
                    except ValueError as e:
                        raise BindError(str(e))
                elif stmt.name == "fault_point_clear":
                    INJECTOR.remove(str(value))
                else:
                    self.variables[stmt.name] = value
            return Result()
        if isinstance(stmt, ast.CreateSnapshot):
            self.catalog.create_snapshot(stmt.name)
            return Result()
        if isinstance(stmt, ast.DropSnapshot):
            self.catalog.drop_snapshot(stmt.name)
            return Result()
        if isinstance(stmt, ast.ShowTrace):
            # recent traces from the motrace ring, oldest first
            from matrixone_tpu.utils import motrace
            ts = motrace.TRACER.traces()
            b = Batch.from_pydict(
                {"TraceId": [t["trace_id"] for t in ts],
                 "Root": [t["root"] for t in ts],
                 "Procs": [t["procs"] for t in ts],
                 "Spans": [t["spans"] for t in ts],
                 "StartUs": [t["ts_us"] for t in ts],
                 "DurationMs": [t["dur_ms"] for t in ts]},
                {"TraceId": dt.VARCHAR, "Root": dt.VARCHAR,
                 "Procs": dt.VARCHAR, "Spans": dt.INT64,
                 "StartUs": dt.INT64, "DurationMs": dt.FLOAT64})
            return Result(batch=b)
        if isinstance(stmt, ast.ShowSnapshots):
            names = sorted(self.catalog.snapshots)
            b = Batch.from_pydict(
                {"Snapshot": names,
                 "Timestamp": [self.catalog.snapshots[n] for n in names]},
                {"Snapshot": dt.VARCHAR, "Timestamp": dt.INT64})
            return Result(batch=b)
        if isinstance(stmt, ast.RestoreTable):
            snaps = self.catalog.snapshots
            if stmt.snapshot not in snaps:
                raise BindError(f"no such snapshot {stmt.snapshot!r}")
            n = self.catalog.restore_table(stmt.table, snaps[stmt.snapshot])
            return Result(affected=n)
        if isinstance(stmt, ast.Delete):
            return self._delete(stmt)
        if isinstance(stmt, ast.Update):
            return self._update(stmt)
        if isinstance(stmt, ast.BeginTxn):
            if self.txn is not None:
                old, self.txn = self.txn, None
                old.commit()            # MySQL: BEGIN commits the open txn
            self.txn = self.txn_client.begin()
            return Result()
        if isinstance(stmt, ast.CommitTxn):
            if self.txn is not None:
                old, self.txn = self.txn, None   # clear even on conflict
                affected = old.commit()
                return Result(affected=affected)
            return Result()
        if isinstance(stmt, ast.RollbackTxn):
            if self.txn is not None:
                self.txn.rollback()
                self.txn = None
            return Result()
        raise BindError(f"unsupported statement {type(stmt).__name__}")

    def _fragment_annotator(self, node):
        """EXPLAIN decoration: compile the operator tree (cheap, no
        execution) and mark which plan nodes fused into which fragment."""
        from matrixone_tpu.vm import fusion
        if not fusion.enabled(self._ctx()):
            return None
        op = compile_plan(node, self._ctx())
        fmap = fusion.fragment_map(op)
        if not fmap:
            return None
        roles = fusion.fragment_roles(op)
        return lambda n: ((f" fragment=f{fmap[id(n)]}"
                           + (f" {roles[id(n)]}" if id(n) in roles
                              else ""))
                          if id(n) in fmap else "")

    def _exchange_annotator(self, node):
        """EXPLAIN decoration for the device-shard executor: mark each
        exchange the CBO planned — exchange=broadcast|shuffle|local on
        the spine joins and the probe scan (parallel/dist_query.py)."""
        shards = int(self.variables.get("query_shards", 0) or 0)
        if shards < 2:
            return None
        from matrixone_tpu.parallel import dist_query as DQ
        modes = DQ.explain_exchanges(
            node, self.catalog, shards,
            min_rows=int(self.variables.get("dist_min_rows", 100_000)))
        if not modes:
            return None
        return lambda n: (f" exchange={modes[id(n)]}"
                          if id(n) in modes else "")

    def _explain_analyze(self, node) -> str:
        """Run the plan, recording per-operator batches/rows/time
        (reference: EXPLAIN ANALYZE via process.Analyzer/OpAnalyzer,
        vm/types.go:256 + compile/analyze_module.go)."""
        import time as _time
        import jax as _jax
        import jax.numpy as _jnp
        op = compile_plan(node, self._ctx())
        stats = {}

        def wrap(o):
            orig = o.execute
            st = stats.setdefault(id(o), {"op": type(o).__name__,
                                          "batches": 0, "rows": 0,
                                          "seconds": 0.0})

            def timed():
                it = orig()
                while True:
                    t0 = _time.perf_counter()
                    try:
                        ex = next(it)
                    except StopIteration:
                        st["seconds"] += _time.perf_counter() - t0
                        return
                    # settle the batch's device work before stamping the
                    # operator: JAX dispatch is async, so without the
                    # sync a heavy projection's time would be billed to
                    # whichever DOWNSTREAM operator first touches the
                    # arrays (lazy-dispatch skew)
                    from matrixone_tpu.utils import san as _san
                    _san.check_blocking("device.sync")
                    for c in ex.batch.columns.values():
                        _jax.block_until_ready(c.data)
                    st["seconds"] += _time.perf_counter() - t0
                    st["batches"] += 1
                    st["rows"] += int(_jax.device_get(
                        _jnp.sum(ex.mask.astype(_jnp.int32))))
                    yield ex
            o.execute = timed
            for attr in ("child", "left", "right"):
                c = getattr(o, attr, None)
                if c is not None:
                    wrap(c)
            for c in getattr(o, "children", []) or []:
                wrap(c)
        wrap(op)
        for _ in op.execute():
            pass

        def render(o, indent=0):
            from matrixone_tpu.sql.plan import _udf_call_notes
            from matrixone_tpu.vm.fusion import FusedFragmentOp
            st = stats[id(o)]
            notes = _udf_call_notes(getattr(o, "node", None)) \
                if getattr(o, "node", None) is not None else ""
            line = ("  " * indent + f"{st['op']}{notes}  rows={st['rows']} "
                    f"batches={st['batches']} time={st['seconds']*1000:.1f}ms")
            out = [line]
            if isinstance(o, FusedFragmentOp):
                fs = o.last_stats
                build = ("" if "build_dispatches" not in fs else
                         f" build_dispatches={fs['build_dispatches']}")
                out.append(
                    "  " * (indent + 1)
                    + f"fragment f{o.fragment_id} [{o.describe()}] "
                      f"mode={fs['mode']} dispatches={fs['dispatches']} "
                      f"trace_ms={fs['trace_ms']:.1f} "
                      f"compile_cache={fs['cache']}" + build)
            if notes:
                # the UdfCall rides the operator's pull loop: its
                # rows/batches ARE the operator's (EXPLAIN ANALYZE
                # surface for the udf subsystem)
                out.append("  " * (indent + 1)
                           + f"{notes.strip()}  rows={st['rows']} "
                             f"batches={st['batches']}")
            for attr in ("child", "left", "right"):
                c = getattr(o, attr, None)
                if c is not None:
                    out.extend(render(c, indent + 1))
            for c in getattr(o, "children", []) or []:
                out.extend(render(c, indent + 1))
            return out
        return "\n".join(render(op))

    # ---------------------------------------------------- subquery inlining
    def _run_subquery(self, sel):
        """Execute a nested subquery with a nesting bound (clean
        BindError instead of a RecursionError deep in the engine)."""
        d = getattr(self, "_subq_depth", 0)
        if d > 64:
            raise BindError("subquery nesting too deep")
        self._subq_depth = d + 1
        try:
            return self._select(sel)
        finally:
            self._subq_depth = d

    def _inline_subqueries(self, node, ctes=None):
        """Execute uncorrelated subqueries once and inline the results
        (reference: the planner turns these into joins; execute-once has
        identical semantics for the uncorrelated case). Correlated
        subqueries surface as 'unknown column' from the inner bind."""
        import dataclasses as dc
        if isinstance(node, ast.Subquery):
            if ctes:
                node.select.ctes = list(ctes) + list(node.select.ctes)
            r = self._run_subquery(node.select)
            rows = r.rows()
            if len(r.column_names) != 1:
                raise BindError("scalar subquery must return one column")
            if len(rows) > 1:
                raise BindError("scalar subquery returned more than one row")
            v = rows[0][0] if rows else None
            return _param_literal(v)
        if isinstance(node, ast.Exists):
            inner_limit = (1 if node.select.limit is None
                           else min(1, node.select.limit))
            sub = dc.replace(node.select, limit=inner_limit)
            if ctes:
                sub.ctes = list(ctes) + list(sub.ctes)
            r = self._run_subquery(sub)
            has = len(r.rows()) > 0
            return ast.Literal(has != node.negated, "bool")
        if isinstance(node, ast.InList) and len(node.items) == 1 \
                and isinstance(node.items[0], ast.Subquery):
            if ctes:
                node.items[0].select.ctes = \
                    list(ctes) + list(node.items[0].select.ctes)
            r = self._run_subquery(node.items[0].select)
            if len(r.column_names) != 1:
                raise BindError("IN subquery must return one column")
            vals = [row[0] for row in r.rows()]
            if node.negated and any(v is None for v in vals):
                # NOT IN with NULLs is never TRUE (SQL ternary logic)
                return ast.Literal(False, "bool")
            vals = [v for v in vals if v is not None]
            if not vals:
                return ast.Literal(bool(node.negated), "bool")
            return ast.InList(node.expr,
                              [_param_literal(v) for v in vals],
                              node.negated)
        if dc.is_dataclass(node) and isinstance(node, ast.Node) \
                and not isinstance(node, (ast.SubqueryRef,)):
            for f in dc.fields(node):
                v = getattr(node, f.name)
                if isinstance(v, ast.Node):
                    setattr(node, f.name,
                            self._inline_subqueries(v, ctes))
                elif isinstance(v, list):
                    setattr(node, f.name, [
                        self._inline_subqueries(x, ctes)
                        if isinstance(x, ast.Node) else
                        tuple(self._inline_subqueries(y, ctes)
                              if isinstance(y, ast.Node) else y
                              for y in x) if isinstance(x, tuple) else x
                        for x in v])
        return node

    def _prepare_select(self, sel) -> None:
        """Inline uncorrelated subqueries in WHERE/HAVING/select items
        (not derived tables — those bind as plans)."""
        if isinstance(sel, ast.Union):
            for arm in sel.selects:
                self._prepare_select(arm)
            return
        if not isinstance(sel, ast.Select):
            return
        ctes = sel.ctes   # WITH scope is visible inside subqueries
        for i, (_name, sub) in enumerate(ctes):
            # a CTE body sees only EARLIER ctes
            if isinstance(sub, ast.Select) and not sub.ctes:
                sub.ctes = list(ctes[:i])
            self._prepare_select(sub)
        # derived tables in FROM get the same treatment (their subqueries
        # may be correlated against their own FROM); guarded by a marker so
        # the decorrelation-added derived table below is prepared exactly
        # once
        def prep_from(f):
            if isinstance(f, ast.SubqueryRef):
                if getattr(f.select, "_mo_prepared", False):
                    return
                if isinstance(f.select, ast.Select) and not f.select.ctes:
                    f.select.ctes = list(ctes)
                self._prepare_select(f.select)
            elif isinstance(f, ast.Join):
                prep_from(f.left)
                prep_from(f.right)
        prep_from(sel.from_)
        # decorrelate correlated EXISTS / scalar-agg subqueries into joins
        # (reference: plan builder subquery flattening); uncorrelated ones
        # are inlined below by executing once
        from matrixone_tpu.sql.decorrelate import decorrelate_select
        decorrelate_select(sel, self.catalog, dict(ctes))
        for sj in sel.semijoins:
            self._prepare_select(sj.select)
        prep_from(sel.from_)   # derived tables ADDED by decorrelation
        for it in sel.items:
            it.expr = self._inline_subqueries(it.expr, ctes=ctes)
        if sel.where is not None:
            sel.where = self._inline_subqueries(sel.where, ctes=ctes)
        if sel.having is not None:
            sel.having = self._inline_subqueries(sel.having, ctes=ctes)
        sel._mo_prepared = True

    def _try_mo_ctl(self, sel) -> Optional[Result]:
        """`select mo_ctl('cmd'[, 'arg'])` — ops control functions
        (reference: plan/function/ctl mo_ctl): checkpoint | merge | flush."""
        if not (isinstance(sel, ast.Select) and sel.from_ is None
                and len(sel.items) == 1):
            return None
        e = sel.items[0].expr
        if not (isinstance(e, ast.FuncCall) and e.name == "mo_ctl"):
            return None
        args = [a.value for a in e.args if isinstance(a, ast.Literal)]
        cmd = str(args[0]).lower() if args else ""
        arg = str(args[1]) if len(args) > 1 else ""
        if cmd == "checkpoint":
            self.catalog.checkpoint()
            out = "checkpoint done"
        elif cmd == "merge":
            def describe(code):
                if code == -1:
                    return "skipped (too few segments)"
                if code == -2:
                    return "deferred (open transactions)"
                if code == -3:
                    return "deferred (lost race with a concurrent " \
                           "write — retry)"
                return f"kept {code} rows"
            if arg in ("status", "run", "pause", "resume", "gc"):
                # background compaction scheduler ops surface
                # (storage/merge_sched) — the lint/san/crash pattern
                import json as _json
                from matrixone_tpu.storage import merge_sched
                sched = merge_sched.scheduler_for(self.catalog)
                if arg == "status":
                    out = _json.dumps(sched.status(), sort_keys=True,
                                      default=str)
                elif arg == "run":
                    out = _json.dumps(sched.run_cycle(), sort_keys=True,
                                      default=str)
                elif arg == "gc":
                    out = _json.dumps(self.catalog.gc_fences(),
                                      sort_keys=True)
                elif arg == "pause":
                    sched.pause()
                    out = "merge scheduler paused"
                else:
                    sched.resume()
                    out = "merge scheduler resumed"
            elif not arg:
                results = []
                for name in list(self.catalog.tables):
                    if not name.startswith("system_"):
                        r = self.catalog.merge_table(name,
                                                     checkpoint=False)
                        if r >= 0:
                            results.append(f"{name}: {describe(r)}")
                if results:
                    self.catalog.checkpoint()
                out = "; ".join(results) or "nothing to merge"
            else:
                out = f"merge {arg}: " + describe(
                    self.catalog.merge_table(arg))
        elif cmd == "flush":
            if hasattr(self.catalog, "stmt_recorder"):
                self.catalog.stmt_recorder.flush()
            out = "flushed"
        elif cmd == "fault":
            # operational fault-point surface (reference: mo_ctl
            # addfaultpoint): status | clear | arm:<spec>
            import json as _json
            from matrixone_tpu.utils.fault import INJECTOR, parse_spec
            if arg in ("", "status"):
                out = _json.dumps(INJECTOR.describe(), sort_keys=True)
            elif arg == "clear":
                INJECTOR.clear()
                out = "faults cleared"
            elif arg.startswith("arm:"):
                try:
                    INJECTOR.add(**parse_spec(arg[4:]))
                except ValueError as e:
                    raise BindError(str(e))
                out = f"armed {arg[4:].split(':', 1)[0]}"
            else:
                raise BindError(f"unknown fault subcommand {arg!r}; "
                                "use status | clear | arm:<spec>")
        elif cmd == "serving":
            # serving-layer ops surface: plan/result cache + admission
            # (matrixone_tpu/serving; reference: proxy/queryservice tier)
            import json as _json
            from matrixone_tpu.serving import serving_for
            sv = serving_for(self.catalog)
            if arg in ("", "status"):
                out = _json.dumps(sv.status(), sort_keys=True,
                                  default=str)
            elif arg == "clear":
                sv.clear()
                out = "serving caches cleared"
            elif arg.startswith("slots:"):
                try:
                    sv.admission.slots = int(arg.split(":", 1)[1])
                except ValueError:
                    raise BindError(f"bad slot count in {arg!r}")
                out = f"admission slots = {sv.admission.slots}"
            elif arg.startswith("account_slots:"):
                try:
                    sv.admission.account_slots = int(
                        arg.split(":", 1)[1])
                except ValueError:
                    raise BindError(f"bad account slot count in {arg!r}")
                out = (f"admission account_slots = "
                       f"{sv.admission.account_slots}")
            elif arg in ("plan:on", "plan:off"):
                sv.plan_cache.enabled = arg.endswith(":on")
                if not sv.plan_cache.enabled:
                    sv.plan_cache.clear()
                out = f"plan cache {'on' if sv.plan_cache.enabled else 'off'}"
            elif arg.startswith("result:"):
                sub = arg.split(":", 1)[1]
                if sub == "off":
                    sv.result_cache.max_bytes = 0
                    sv.result_cache.clear()
                elif sub == "on":
                    if sv.result_cache.max_bytes <= 0:
                        sv.result_cache.max_bytes = 64 << 20
                else:
                    try:
                        mb = int(sub)
                    except ValueError:
                        raise BindError(
                            f"unknown result subcommand {sub!r}; use "
                            f"on | off | <mb>")
                    # shrinking must evict NOW: a read-hot workload never
                    # calls put(), so its eviction loop would not run
                    sv.result_cache.set_max_bytes(mb << 20)
                    if sv.result_cache.max_bytes <= 0:
                        sv.result_cache.clear()
                out = f"result cache {sv.result_cache.max_bytes >> 20} MB"
            else:
                raise BindError(
                    f"unknown serving subcommand {arg!r}; use status | "
                    f"clear | slots:<n> | account_slots:<n> | "
                    f"plan:<on|off> | result:<on|off|mb>")
        elif cmd == "udf":
            # UDF subsystem ops surface: compile-cache + tier counters
            import json as _json
            from matrixone_tpu import udf as U
            from matrixone_tpu.udf import catalog as ucat
            if arg in ("", "status"):
                st = U.stats()
                st["functions"] = len(ucat.registry_for(self.catalog))
                out = _json.dumps(st, sort_keys=True)
            elif arg == "clear":
                U.COMPILE_CACHE.clear()
                out = "udf compile cache cleared"
            else:
                raise BindError(f"unknown udf subcommand {arg!r}; "
                                "use status | clear")
        elif cmd == "fusion":
            # whole-plan fusion ops surface (vm/fusion.py): fragment
            # compile-cache + execution-mode counters, matching the
            # mo_ctl('udf'|'serving') pattern
            import json as _json
            from matrixone_tpu.vm import fusion
            if arg in ("", "status"):
                out = _json.dumps(fusion.stats(), sort_keys=True)
            elif arg == "clear":
                fusion.CACHE.clear()
                out = "fusion compile cache cleared"
            else:
                raise BindError(f"unknown fusion subcommand {arg!r}; "
                                "use status | clear")
        elif cmd == "lint":
            # static-analysis ops surface (tools/molint): checker
            # inventory, last-run findings, suppression count —
            # mirrors the mo_ctl('udf'|'serving'|'rpc') pattern
            import json as _json
            try:
                from tools import molint
            except ImportError:
                raise BindError(
                    "molint unavailable: the tools/ package is not on "
                    "sys.path (run from a repo checkout)")
            if arg in ("", "status"):
                out = _json.dumps(molint.last_run_status(),
                                  sort_keys=True)
            elif arg == "run":
                _f, st = molint.run_checks(molint.repo_root())
                out = _json.dumps(st, sort_keys=True)
            else:
                raise BindError(f"unknown lint subcommand {arg!r}; "
                                "use status | run")
        elif cmd == "san":
            # runtime concurrency sanitizer ops surface (utils/san.py):
            # findings/edges/daemon report + clear — mirrors the
            # mo_ctl('fault'|'lint') pattern
            import json as _json
            from matrixone_tpu.utils import san as _san
            if arg in ("", "status"):
                out = _json.dumps(_san.report(), sort_keys=True)
            elif arg == "clear":
                _san.clear()
                out = "san findings cleared"
            else:
                raise BindError(f"unknown san subcommand {arg!r}; "
                                "use status | clear")
        elif cmd == "qa":
            # differential query-equivalence analyzer ops surface
            # (tools/moqa + utils/qa.py): pair inventory, canary
            # report, last corpus run; run:<seed> executes a small
            # in-process corpus — mirrors the mo_ctl('lint'|'san')
            # pattern
            import json as _json
            try:
                from tools import moqa
            except ImportError:
                raise BindError(
                    "moqa unavailable: the tools/ package is not on "
                    "sys.path (run from a repo checkout)")
            if arg in ("", "status"):
                out = _json.dumps(moqa.last_run_status(),
                                  sort_keys=True, default=str)
            elif arg == "clear":
                from matrixone_tpu.utils import qa as _qa
                _qa.clear()
                out = "qa findings cleared"
            elif arg.startswith("run:"):
                try:
                    seed = int(arg.split(":", 1)[1])
                except ValueError:
                    raise BindError(f"bad seed in {arg!r}")
                # a QUICK in-process probe: env-toggled pairs only
                # (the heavyweight replay pairs belong to the corpus
                # gate / CLI, not an ops command)
                rep = moqa.run_corpus(seed=seed,
                                      queries_per_scenario=6,
                                      pairs=["fusion", "dense-groups",
                                             "plan-cache"],
                                      reduce_findings=0,
                                      oracle_fraction=0.34)
                out = _json.dumps(
                    {k: rep[k] for k in ("seed", "queries", "pairs",
                                         "total_checks", "seconds")}
                    | {"findings": len(rep["findings"])},
                    sort_keys=True)
            else:
                raise BindError(f"unknown qa subcommand {arg!r}; "
                                "use status | clear | run:<seed>")
        elif cmd == "keys":
            # trace-capture / cache-key auditor ops surface
            # (utils/keys.py + tools/mokey): armed state, audited
            # sites, mismatch findings with both stacks, last static
            # run — mirrors the mo_ctl('lint'|'san'|'qa') pattern
            import json as _json
            from matrixone_tpu.utils import keys as _keys
            if arg in ("", "status"):
                st = _keys.report()
                try:
                    from tools import mokey as _mokey
                    st["static"] = _mokey.last_run_status()
                except ImportError:
                    st["static"] = None
                out = _json.dumps(st, sort_keys=True, default=str)
            elif arg == "clear":
                _keys.clear()
                out = "key-audit records and findings cleared"
            elif arg == "audit:on":
                _keys.arm()
                out = "key audit armed"
            elif arg == "audit:off":
                _keys.disarm()
                out = "key audit disarmed"
            else:
                raise BindError(f"unknown keys subcommand {arg!r}; "
                                "use status | clear | audit:on | "
                                "audit:off")
        elif cmd == "crash":
            # crash-recovery sweep ops surface (utils/crash.py +
            # tools/mocrash): journal/recording state, last sweep
            # summary; run:<seed> executes a small in-process sweep —
            # mirrors the mo_ctl('lint'|'san'|'qa'|'keys') pattern
            import json as _json
            from matrixone_tpu.utils import crash as _crash
            if arg in ("", "status"):
                try:
                    from tools import mocrash as _mocrash
                    out = _json.dumps(_mocrash.last_run_status(),
                                      sort_keys=True, default=str)
                except ImportError:
                    out = _json.dumps(_crash.report(), sort_keys=True,
                                      default=str)
            elif arg == "clear":
                _crash.clear()
                out = "crash sweep records cleared"
            elif arg.startswith("run:"):
                try:
                    seed = int(arg.split(":", 1)[1])
                except ValueError:
                    raise BindError(f"bad seed in {arg!r}")
                try:
                    from tools import mocrash as _mocrash
                except ImportError:
                    raise BindError(
                        "mocrash unavailable: the tools/ package is "
                        "not on sys.path (run from a repo checkout)")
                # a QUICK in-process probe: capped points, engine
                # scenario only (the full sweep belongs to the gate /
                # CLI, not an ops command)
                rep = _mocrash.run_sweep(seed=seed, points=40,
                                         scenario="engine")
                out = _json.dumps(
                    {k: rep[k] for k in ("seed", "events", "points",
                                         "recoveries", "seconds")}
                    | {"findings": len(rep["findings"])},
                    sort_keys=True)
            else:
                raise BindError(f"unknown crash subcommand {arg!r}; "
                                "use status | clear | run:<seed>")
        elif cmd == "mview":
            # materialized-view ops surface: registry + per-view
            # watermark/mode, on-demand refresh — matching the
            # mo_ctl('udf'|'fusion'|'serving') pattern
            import json as _json
            from matrixone_tpu import mview as MV
            if arg in ("", "status"):
                out = _json.dumps(MV.stats(self.catalog),
                                  sort_keys=True, default=str)
            elif arg.startswith("refresh:"):
                name = arg.split(":", 1)[1]
                n = self._refresh_mview(name)
                out = f"refreshed {name}: {n} rows"
            else:
                raise BindError(f"unknown mview subcommand {arg!r}; "
                                "use status | refresh:<view>")
        elif cmd == "trace":
            # distributed-tracing ops surface (utils/motrace.py):
            # status | on | off | clear | sample:<f> | slow:<ms> |
            # dump:<path> — mirrors the mo_ctl('fault'|'san') pattern
            import json as _json
            from matrixone_tpu.utils import motrace as _mt
            if arg in ("", "status"):
                out = _json.dumps(_mt.TRACER.status(), sort_keys=True)
            elif arg == "on":
                _mt.TRACER.arm()
                out = "trace armed"
            elif arg == "off":
                _mt.TRACER.disarm()
                out = "trace disarmed"
            elif arg == "clear":
                _mt.TRACER.clear()
                out = "trace ring cleared"
            elif arg.startswith("sample:"):
                try:
                    _mt.TRACER.sample = float(arg.split(":", 1)[1])
                except ValueError:
                    raise BindError(f"bad sample fraction in {arg!r}")
                out = f"trace sample = {_mt.TRACER.sample}"
            elif arg.startswith("slow:"):
                try:
                    _mt.TRACER.slow_ms = float(arg.split(":", 1)[1])
                except ValueError:
                    raise BindError(f"bad slow threshold in {arg!r}")
                out = f"trace slow_ms = {_mt.TRACER.slow_ms}"
            elif arg.startswith("dump:"):
                paths = _mt.dump(arg.split(":", 1)[1])
                out = (f"dumped {len(paths)} trace(s) -> "
                       + (paths[0].rsplit('/', 1)[0] if paths
                          else "nothing to dump"))
            else:
                raise BindError(
                    f"unknown trace subcommand {arg!r}; use status | "
                    f"on | off | clear | sample:<f> | slow:<ms> | "
                    f"dump:<path>")
        elif cmd == "metrics":
            # scrape surface: the full registry in Prometheus text
            # exposition format (also served by `python -m
            # tools.moscrape`); 'snapshot' returns the structured dict
            import json as _json
            from matrixone_tpu.utils import metrics as _m
            if arg in ("", "dump"):
                out = _m.REGISTRY.render()
            elif arg == "snapshot":
                out = _json.dumps(_m.REGISTRY.snapshot(),
                                  sort_keys=True)
            else:
                raise BindError(f"unknown metrics subcommand {arg!r}; "
                                "use dump | snapshot")
        elif cmd == "rpc":
            # per-peer circuit breaker state + the CN's logtail breaker
            import json as _json
            from matrixone_tpu.cluster.rpc import breaker_states
            st = {"breakers": breaker_states()}
            consumer = getattr(self.catalog, "consumer", None)
            if consumer is not None:
                st["logtail"] = {
                    "state": "open" if consumer.broken else "closed",
                    "strikes": consumer.strikes,
                    "applied_ts": consumer.applied_ts,
                    "last_error": consumer.last_error}
            out = _json.dumps(st, sort_keys=True)
        else:
            raise BindError(f"unknown mo_ctl command {cmd!r}")
        b = Batch.from_pydict({"mo_ctl": [out]}, {"mo_ctl": dt.VARCHAR})
        return Result(batch=b)

    def _cbo(self, node):
        """Stats-driven join reordering (reference: plan/query_builder.go
        determineJoinOrder). `SET cbo = 0` disables it for plan debugging."""
        if str(self.variables.get("cbo", 1)) in ("0", "off", "false"):
            return node
        from matrixone_tpu.sql.cbo import optimize_plan
        return optimize_plan(node, self.catalog)

    # ------------------------------------------------------------- select
    def _plan_select(self, sel) -> P.PlanNode:
        """SELECT/UNION AST -> the plan that runs (and that EXPLAIN shows):
        bind, join order, index rewrites, then projection pruning last, so
        every Scan carries only the columns the finished plan reads."""
        from matrixone_tpu.sql.optimize import apply_indices, prune_columns
        self._prepare_select(sel)
        node = Binder(self.catalog).bind_statement(sel)
        node = self._cbo(node)
        node = apply_indices(
            node, self.catalog,
            nprobe=int(self.variables.get("ivf_nprobe", 8)),
            skip_tables=self._index_skip_tables())
        node = prune_columns(node)
        if self.txn is None:
            # an open txn reads its own workspace, whose rows are held to
            # the primary key only when it commits
            from matrixone_tpu.sql.cbo import mark_unique_builds
            node = mark_unique_builds(node, self.catalog)
        return node

    def _select(self, sel: ast.Select, serving=None) -> Result:
        ctl = self._try_mo_ctl(sel)
        if ctl is not None:
            return ctl
        from matrixone_tpu.utils import motrace
        # spans over the statement's path outside planning and execution:
        # under many callers a thread waits here for the caches' locks
        with motrace.span("serving.lookup"):
            sv = (serving if (serving is not None and self.txn is None)
                  else None)
            lazy = sv is not None and sv.owns_pristine(sel)
            if sv is not None and not sv.usable_for(sel):
                sv = None
            if sv is None and lazy:
                # caches declined but the caller handed us the pristine
                # template: bind a private substituted copy, never the
                # shared template itself
                sel = serving.instantiate(raise_errors=True)
                lazy = False
            ann = getattr(self, "_exec_ann", None)
            # ---- result cache: serve the whole statement if every scanned
            # table is still at the version the entry was stored under
            if sv is not None and sv.result_enabled():
                hit = sv.state.result_cache.get(
                    sv.result_key(), self._recompute_versions)
                if hit is not None:
                    batch, stored = hit
                    # privileges gate CACHED results too: the entry's
                    # version tuple carries the scanned table names, so an
                    # unprivileged user in the same account can never read
                    # a colleague's warm rows
                    if self.auth is not None and not self.auth.is_admin:
                        for ent in stored[1]:
                            self._check("select", ent[0])
                    if ann is not None:
                        ann["cache_hit"] = "result"
                    return Result(batch=batch)
            # ---- plan cache: skip prepare/bind/optimize on a hit (only in
            # template mode — raw-path literals carry no parameter tags)
            node = None
            plan_missed = False
            if sv is not None and sv.template_mode and sv.plan_enabled():
                gens = self._serving_gens()
                outcome, node = sv.state.plan_cache.lookup(
                    sv.plan_key(), gens[0], gens[1], sv.full)
                plan_missed = outcome == "miss"
                if node is not None and ann is not None \
                        and ann["cache_hit"] == "none":
                    ann["cache_hit"] = "plan"
        if node is None:
            if lazy:
                # instantiate the template only now: a plan-cache hit
                # above never pays the AST deepcopy at all
                sel = sv.instantiate(raise_errors=True)
            with motrace.span("plan"):
                node = self._plan_select(sel)
            if sv is not None and sv.template_mode \
                    and sv.plan_enabled() and plan_missed:
                # store under the gens captured at LOOKUP time: a DDL
                # racing the bind must orphan this entry, so the plan
                # bound against the old schema never passes the gen
                # check under the post-DDL generation
                sv.state.plan_cache.store(
                    sv.plan_key(), node, len(sv.full), gens[0], gens[1])
        if self.auth is not None and not self.auth.is_admin:
            for tname in _plan_tables(node):
                self._check("select", tname)
        # versions and the execution snapshot must be captured
        # ATOMICALLY under the engine commit lock: a commit bumps table
        # versions BEFORE advancing committed_ts, so a lock-free capture
        # can pair mid-commit versions with an old snapshot — the entry
        # then publishes old rows under a key that matches the
        # post-commit state (the staleness chaos drill caught exactly
        # this).  Execution is then FROZEN at the captured ts.
        with motrace.span("vm.compile"):
            versions = frozen = None
            if sv is not None and sv.result_enabled():
                versions, frozen = self._capture_versions(node)
            ctx = self._ctx(frozen_ts=frozen)
            node2 = self._maybe_distribute(node, ctx)
            # ---- compiled-tree reuse: a plan-cache hit used to rebuild the
            # full operator tree anyway; the tree of the last completed
            # execution rides the plan-cache entry (identity-guard POP: a
            # concurrent execution finds None and compiles its own)
            op = None
            tree_cacheable = (sv is not None and sv.template_mode
                              and sv.plan_enabled() and node2 is node)
            tree_vars = self._tree_vars_sig() if tree_cacheable else None
            if tree_cacheable:
                from matrixone_tpu.utils import keys as keyaudit
                if keyaudit.armed():
                    # each build-time knob re-read INDEPENDENTLY of
                    # _tree_vars_sig: a knob that starts steering tree
                    # construction without riding the signature (the
                    # kill-switches-not-in-_tree_vars_sig bug class)
                    # mismatches here instead of reusing a wrong tree
                    keyaudit.audit(
                        "serving/plan_cache.py:tree",
                        (sv.plan_key(), gens[0], gens[1], tree_vars),
                        self._tree_vars_deps())
                cached = sv.state.plan_cache.take_tree(
                    sv.plan_key(), gens[0], gens[1], tree_vars)
                if cached is not None:
                    op = sv.state.plan_cache.rebind_tree(cached, sv.full)
                    if op is not None:
                        from matrixone_tpu.vm.compile import retarget_tree
                        retarget_tree(op, ctx)
                        # the tree's plan nodes are the authoritative ones
                        # for this execution (params patched in place)
                        node = cached["plan"]
            built = None
            if op is None:
                op = compile_plan(node2, ctx)
                node = node2
                if tree_cacheable:
                    built = {"op": op, "plan": node2}
            else:
                built = cached
        out_batches = []
        for ex in op.execute():
            # KILL lands between device batches (queryservice): the pull
            # loop is the engine's natural preemption point
            self._procs.check_killed(self.conn_id)
            out_batches.append(self._to_host(ex, node.schema))
        with motrace.span("serving.store"):
            if tree_cacheable and built is not None:
                sv.state.plan_cache.put_tree(sv.plan_key(), built, gens[0],
                                             gens[1], tree_vars)
            if not out_batches:
                empty = {n: Vector.from_values([], d) for n, d in node.schema}
                result = Result(batch=Batch(empty))
            elif len(out_batches) == 1:
                result = Result(batch=out_batches[0])
            else:
                # concatenate host batches
                cols = {}
                for n, d in node.schema:
                    vals = []
                    for b in out_batches:
                        vals.extend(b.columns[n].to_pylist())
                    cols[n] = Vector.from_values(vals, d)
                result = Result(batch=Batch(cols))
            if versions is not None and result.batch is not None:
                sv.state.result_cache.put(sv.result_key(), result.batch,
                                          versions)
        return result

    def _tree_vars_sig(self) -> tuple:
        """Session state BAKED into a compiled operator tree at build
        time (everything else is re-read through the ExecContext at
        execute time).  DERIVED from _tree_vars_deps so the signature
        and the audited dep set cannot drift: a knob added to the deps
        rides the signature automatically, and there is no second list
        to forget."""
        return tuple(self._tree_vars_deps().values())

    def _tree_vars_deps(self) -> dict:
        """Every build-time knob a compiled operator tree bakes, NAMED:
        the fusion gates — incl. the join/window/topk kill-switches
        the planner consults while building fragments — and the join
        build budget (JoinOp snapshots it at construction).  The armed
        key auditor (utils/keys.py) hashes these per tree take/put;
        adding a build-time knob means adding a row HERE (dict order is
        part of the signature — append, don't reorder)."""
        from matrixone_tpu.vm import fusion
        return {
            "plan_fusion": fusion.enabled(self._ctx()),
            "fusion_join": fusion.join_fusion_enabled(),
            "fusion_window": fusion.window_fusion_enabled(),
            "fusion_topk": fusion.topk_fusion_enabled(),
            "join_build_budget":
                self.variables.get("join_build_budget"),
        }

    # ------------------------------------------------- serving versions
    def _serving_gens(self):
        return (getattr(self.catalog, "ddl_gen", 0),
                getattr(self.catalog, "stats_gen", 0))

    def _capture_versions(self, node):
        """-> ((ddl_gen, per-scan table versions), frozen_ts) for the
        result cache, or (None, None) when any scanned table is
        unversionable (external / scan-in-place tables change outside
        the commit funnel).  Runs under the engine commit lock so the
        version tuple and the snapshot ts are one consistent point —
        never a mid-commit mixture."""
        from matrixone_tpu.serving.plan_cache import iter_plan_values
        lock = getattr(self.catalog, "_commit_lock", None)
        if lock is None:
            return None, None
        scans = set()
        for v in iter_plan_values(node):
            if isinstance(v, (P.Scan, P.VectorTopK, P.FulltextTopK)):
                scans.add((v.table, getattr(v, "as_of_ts", None)))
        with lock:
            ts0 = getattr(self.catalog, "committed_ts", None)
            entries = []
            for table, as_of in sorted(scans, key=lambda x: (x[0],
                                                             x[1] or -1)):
                try:
                    t = self.catalog.get_table(table)
                except Exception:   # noqa: BLE001 — raced drop: bypass
                    return None, None
                if as_of is not None and ts0 is not None \
                        and as_of <= ts0:
                    # strictly in the committed past: immutable (every
                    # future commit gets ts > committed_ts >= as_of).
                    # A future-dated as-of still SEES later commits, so
                    # it falls through to live versioning below.
                    entries.append((table, "asof", as_of))
                    continue
                ver = getattr(t, "last_commit_ts", None)
                if ver is None or getattr(t, "is_external", False):
                    return None, None
                entries.append((table, ver, len(t.segments),
                                len(t.tombstones)))
            if ts0 is None:
                return None, None
            return (getattr(self.catalog, "ddl_gen", 0),
                    tuple(entries)), ts0

    def _recompute_versions(self, stored):
        """Re-evaluate a stored entry's version tuple against the live
        catalog (under the commit lock: a mid-commit read could match a
        consistent future tuple and serve rows ahead of the frontier);
        any mismatch (incl. a dropped table) orphans the entry."""
        lock = getattr(self.catalog, "_commit_lock", None)
        if lock is None:
            return None
        try:
            with lock:
                entries = []
                for ent in stored[1]:
                    if ent[1] == "asof":
                        entries.append(ent)     # immutable past
                        continue
                    t = self.catalog.get_table(ent[0])
                    entries.append(
                        (ent[0], getattr(t, "last_commit_ts", -1),
                         len(t.segments), len(t.tombstones)))
                return (getattr(self.catalog, "ddl_gen", 0),
                        tuple(entries))
        except Exception:       # noqa: BLE001 — table gone: never match
            return None

    def _account_stmt(self, stmt: ast.Node) -> Optional[Result]:
        """CREATE ACCOUNT/USER/ROLE, GRANT/REVOKE, SHOW GRANTS
        (reference: frontend/authenticate.go handlers)."""
        from matrixone_tpu.frontend.auth import SYS_ACCOUNT, AuthError
        if isinstance(stmt, ast.CreateAccount):
            # only the sys account provisions tenants (reference rule)
            if self.auth is not None and self._acct() != SYS_ACCOUNT:
                raise AuthError("only the sys account can create accounts")
            self._check_admin()
            self._mgr().create_account(stmt.name, stmt.admin_user,
                                       stmt.admin_password,
                                       stmt.if_not_exists)
            return Result()
        if isinstance(stmt, ast.DropAccount):
            if self.auth is not None and self._acct() != SYS_ACCOUNT:
                raise AuthError("only the sys account can drop accounts")
            self._check_admin()
            self._mgr().drop_account(stmt.name)
            return Result()
        if isinstance(stmt, ast.CreateUser):
            self._check_admin()
            self._mgr().create_user(self._acct(), stmt.name,
                                    stmt.password, stmt.if_not_exists)
            return Result()
        if isinstance(stmt, ast.DropUser):
            self._check_admin()
            self._mgr().drop_user(self._acct(), stmt.name)
            return Result()
        if isinstance(stmt, ast.CreateRole):
            self._check_admin()
            self._mgr().create_role(self._acct(), stmt.name)
            return Result()
        if isinstance(stmt, ast.DropRole):
            self._check_admin()
            self._mgr().drop_role(self._acct(), stmt.name)
            return Result()
        if isinstance(stmt, ast.GrantPriv):
            self._check_admin()
            self._mgr().grant_priv(self._acct(), stmt.privs, stmt.obj,
                                   stmt.role)
            return Result()
        if isinstance(stmt, ast.RevokePriv):
            self._check_admin()
            self._mgr().revoke_priv(self._acct(), stmt.privs, stmt.obj,
                                    stmt.role)
            return Result()
        if isinstance(stmt, ast.GrantRole):
            self._check_admin()
            self._mgr().grant_role(self._acct(), stmt.role, stmt.user)
            return Result()
        if isinstance(stmt, ast.RevokeRole):
            self._check_admin()
            self._mgr().revoke_role(self._acct(), stmt.role, stmt.user)
            return Result()
        if isinstance(stmt, ast.ShowAccounts):
            from matrixone_tpu.frontend.auth import SYS_ACCOUNT, AuthError
            if self.auth is not None and self._acct() != SYS_ACCOUNT:
                raise AuthError(
                    "only the sys account can list accounts")
            m = self._mgr()._m()
            names = sorted(m["accounts"])
            b = Batch.from_pydict(
                {"Account": names,
                 "AdminName": [m["accounts"][n].get("admin_user", "")
                               for n in names]},
                {"Account": dt.VARCHAR, "AdminName": dt.VARCHAR})
            return Result(batch=b)
        if isinstance(stmt, ast.ShowGrants):
            user = stmt.user or (self.auth.user if self.auth else "root")
            if stmt.user and stmt.user != (
                    self.auth.user if self.auth else "root"):
                self._check_admin()
            rows = self._mgr().grants_for(self._acct(), user)
            b = Batch.from_pydict(
                {"Role": [r for r, _o, _p in rows],
                 "Object": [o for _r, o, _p in rows],
                 "Privilege": [p for _r, _o, p in rows]},
                {"Role": dt.VARCHAR, "Object": dt.VARCHAR,
                 "Privilege": dt.VARCHAR})
            return Result(batch=b)
        return None

    def _maybe_distribute(self, node, ctx):
        """Distributed scopes (reference: compile decides Magic: Remote,
        compile/types.go:162): when this CN knows peer fragment
        endpoints, qualifying plans execute their lower subtree across
        the peers and re-enter locally as a Materialized node. `SET
        dist = 0` disables; `dist_min_rows` tunes the size threshold.

        Device shards take PRIORITY over host peers: `SET query_shards
        = N` (env MO_QUERY_SHARDS) runs the same fragment split across
        N device shards of the local mesh — no serialization, no
        network — and falls through to peers/local when the plan or
        mesh does not qualify (parallel/dist_query.py)."""
        if self.txn is not None:
            return node
        if str(self.variables.get("dist", 1)) in ("0", "off", "false"):
            return node
        shards = int(self.variables.get("query_shards", 0) or 0)
        if shards >= 2:
            from matrixone_tpu.parallel import dist_query as DQ
            rebuilt = DQ.try_shard(
                node, self.catalog, ctx, shards,
                min_rows=int(self.variables.get("dist_min_rows",
                                                100_000)))
            if rebuilt is not None:
                return rebuilt
        peers = getattr(self.catalog, "dist_peers", None)
        if not peers:
            return node
        from matrixone_tpu.parallel import fragments as FR
        pool = FR.pool_for(self.catalog)
        rebuilt = FR.try_distribute(
            node, self.catalog, ctx, pool,
            min_rows=int(self.variables.get("dist_min_rows", 100_000)),
            batch_rows=int(self.variables.get("dist_batch_rows", 1 << 16)))
        return rebuilt if rebuilt is not None else node

    def _to_host(self, ex, schema) -> Batch:
        from matrixone_tpu.ops import filter as F
        from matrixone_tpu.utils import motrace
        with motrace.span("result.fetch"):
            # compact masked rows before leaving device
            db = F.compact(ex.batch, ex.mask, ex.padded_len)
            return from_device(db, ex.dicts, schema=dict(schema))

    # --------------------------------------------------------------- ddl
    def _create_table(self, stmt: ast.CreateTable) -> Result:
        schema = [(c.name, type_from_name(c.type_name, c.type_args))
                  for c in stmt.columns]
        auto = [c.name for c in stmt.columns if c.auto_increment]
        if len(auto) > 1:
            raise BindError("only one AUTO_INCREMENT column allowed")
        not_null = [c.name for c in stmt.columns if c.not_null]
        part = None
        if stmt.partition_by is not None:
            from matrixone_tpu.storage.partition import build_spec
            part = build_spec(stmt.partition_by, schema)
        self.catalog.create_table(
            TableMeta(stmt.name, schema, stmt.primary_key,
                      auto_increment=auto[0] if auto else None,
                      not_null=not_null, partition=part),
            if_not_exists=stmt.if_not_exists)
        return Result()

    def _derived_table_schema(self, sel, what: str) -> list:
        """Bind a stored SELECT and derive its backing-table schema
        (alias qualifiers stripped, names validated) — ONE validator
        shared by dynamic tables and materialized views so the two
        surfaces cannot drift."""
        import re
        self._prepare_select(sel)
        node = Binder(self.catalog).bind_statement(sel)
        schema = [(n.split(".")[-1], d) for n, d in node.schema]
        if len({c for c, _ in schema}) != len(schema):
            raise BindError(f"{what} SELECT has duplicate output names")
        for c, _ in schema:
            if not re.match(r"^[A-Za-z_][A-Za-z0-9_]*$", c):
                raise BindError(
                    f"{what} output {c!r} is not a valid column "
                    f"name; alias the expression (AS name)")
        return schema

    def _create_dynamic_table(self, stmt: ast.CreateDynamicTable) -> Result:
        """CREATE DYNAMIC TABLE name AS SELECT ... — materialize once now,
        store the defining SELECT for REFRESH (reference: stream dynamic
        tables driven by the task framework)."""
        from matrixone_tpu.stream import refresh_dynamic_table
        schema = self._derived_table_schema(stmt.select, "dynamic table")
        self.catalog.create_table(TableMeta(stmt.name, schema, []))
        self.catalog.register_dynamic(stmt.name, stmt.sql_text)
        try:
            n = refresh_dynamic_table(self, stmt.name)
        except Exception:  # noqa: BLE001 — compensating drop, re-raised
            # no orphan catalog/WAL state from a failed CREATE: the
            # drop is WAL-logged too, so replay converges to "absent"
            self.catalog.drop_table(stmt.name, if_exists=True)
            raise
        return Result(affected=n)

    # ------------------------------------------------- materialized views
    def _create_materialized_view(self,
                                  stmt: ast.CreateMaterializedView
                                  ) -> Result:
        """CREATE MATERIALIZED VIEW: backing table + one system_mview
        catalog row (riding the ordinary commit+logtail funnels for
        durability/restart/replication).  Maintainable shapes run
        incremental — the catalog row's own post-commit hook initializes
        the state and first materialization; everything else
        materializes once here and refreshes fully on demand."""
        import copy
        import time as _time
        from matrixone_tpu import mview as MV
        from matrixone_tpu.mview import catalog as vcat
        if self.txn is not None:
            raise BindError(
                "CREATE MATERIALIZED VIEW inside an explicit "
                "transaction is not supported (view DDL is autocommit)")
        # maintainability first, on a pristine copy (bind errors for
        # genuinely broken SQL surface from the schema bind below)
        spec, why = None, "tenant sessions run full refresh"
        host = getattr(self.catalog, "_inner", self.catalog)
        if (self.auth is None or self.auth.account == "sys") \
                and hasattr(host, "commit_txn"):
            try:
                spec, why = MV.analyze(copy.deepcopy(stmt.select),
                                       self.catalog)
            except BindError:
                spec = None        # real bind errors re-raise below
        schema = self._derived_table_schema(stmt.select,
                                            "materialized view")
        if vcat.lookup(self.catalog, stmt.name) is not None:
            raise BindError(
                f"materialized view {stmt.name!r} already exists")
        self.catalog.create_table(TableMeta(stmt.name, schema, []))
        vcat.ensure_table(self.catalog)
        d = vcat.MViewDef(
            name=stmt.name.lower(), sql=stmt.sql_text,
            mode="incremental" if spec is not None else "full",
            source=spec.source if spec is not None else "")
        t = self.catalog.get_table(vcat.MVIEW_TABLE)
        batch = vcat.row_batch(d, _time.time_ns() // 1000)
        arrays, validity = t.batch_to_arrays(batch)
        txn = self.txn_client.begin()
        try:
            txn.write_batch(vcat.MVIEW_TABLE, arrays, validity)
            # the commit's post-commit hook syncs the maintenance
            # service, which initializes incremental state + the first
            # materialization before this returns
            txn.commit()
        except BaseException:  # noqa: BLE001 — compensate, re-raise
            txn.rollback()
            self.catalog.drop_table(stmt.name, if_exists=True)
            raise
        if spec is None:
            from matrixone_tpu.stream import rematerialize
            try:
                n = rematerialize(self, stmt.name, stmt.sql_text)
            except Exception:  # noqa: BLE001 — compensating drop, then
                # re-raised: a failed CREATE leaves no orphan state
                self._drop_mview_row(stmt.name)
                self.catalog.drop_table(stmt.name, if_exists=True)
                raise
        else:
            # the post-commit hook swallows maintenance errors (it must
            # never fail an unrelated writer's commit) — but THIS
            # statement's own init failure must surface, not report a
            # registered-yet-permanently-empty view.  Only checkable
            # where the maintaining engine is local; on a CN the TN
            # initializes asynchronously.
            if isinstance(host, Engine):
                svc = getattr(host, "_mview_service", None)
                rt = svc.runtime(d.name) if svc is not None else None
                if rt is None or rt.watermark is None:
                    self._drop_mview_row(stmt.name)
                    self.catalog.drop_table(stmt.name, if_exists=True)
                    raise BindError(
                        f"materialized view {stmt.name!r} failed to "
                        f"initialize (see mo_ctl('mview','status'))")
            n = self.catalog.get_table(stmt.name).n_rows
        return Result(affected=n)

    def _drop_mview_row(self, name: str) -> None:
        from matrixone_tpu.mview import catalog as vcat
        gids = vcat.gids_for_name(self.catalog, name)
        if not len(gids):
            return
        txn = self.txn_client.begin()
        try:
            txn.delete_rows(vcat.MVIEW_TABLE, gids)
            txn.commit()
        except BaseException:  # noqa: BLE001 — rollback, re-raised
            txn.rollback()
            raise

    def _drop_materialized_view(self, stmt: ast.DropMaterializedView
                                ) -> Result:
        from matrixone_tpu.mview import catalog as vcat
        d = vcat.lookup(self.catalog, stmt.name)
        if d is None:
            if stmt.if_exists:
                return Result()
            raise BindError(f"no such materialized view {stmt.name!r}")
        # catalog row first: its commit's hook detaches the maintainer
        # BEFORE the backing table disappears under it
        self._drop_mview_row(stmt.name)
        self.catalog.drop_table(stmt.name, if_exists=True)
        return Result()

    def _show_materialized_views(self) -> Result:
        from matrixone_tpu.mview import catalog as vcat
        reg = vcat.registry_for(self.catalog)
        host = getattr(self.catalog, "_inner", self.catalog)
        svc = getattr(host, "_mview_service", None)
        names = sorted(reg)
        wms, rows = [], []
        for n in names:
            rt = svc.runtime(n) if svc is not None else None
            wms.append(rt.watermark if rt is not None else None)
            try:
                rows.append(self.catalog.get_table(n).n_rows)
            except Exception:  # noqa: BLE001 — backing table dropped
                rows.append(None)
        b = Batch.from_pydict(
            {"Name": names,
             "Mode": [reg[n].mode for n in names],
             "Source": [reg[n].source or None for n in names],
             "Watermark": wms,
             "Rows": rows,
             "Definition": [reg[n].sql for n in names]},
            {"Name": dt.VARCHAR, "Mode": dt.VARCHAR,
             "Source": dt.VARCHAR, "Watermark": dt.INT64,
             "Rows": dt.INT64, "Definition": dt.TEXT})
        return Result(batch=b)

    def _refresh_mview(self, name: str) -> int:
        """REFRESH MATERIALIZED VIEW: incremental views are maintained
        continuously (refresh just reports); full views rematerialize."""
        from matrixone_tpu.mview import catalog as vcat
        d = vcat.lookup(self.catalog, name)
        if d is None:
            raise BindError(f"no such materialized view {name!r}")
        if d.mode == "incremental":
            return self.catalog.get_table(name).n_rows
        from matrixone_tpu.stream import rematerialize
        return rematerialize(self, name, d.sql)

    def _reject_mview_write(self, table: str) -> None:
        """Direct DML against a materialized view would be clobbered by
        the next maintenance/refresh — reject it cleanly.  (Maintenance
        itself writes through engine.commit_txn, never a session.)"""
        if getattr(self, "_mview_refresh", 0):
            return            # the refresh machinery's own writes
        from matrixone_tpu.mview import catalog as vcat
        if vcat.lookup(self.catalog, table) is not None:
            raise BindError(
                f"{table!r} is a materialized view; it is maintained "
                f"from its source — write to the source table instead")

    def _mview_annotator(self):
        """EXPLAIN decoration: mark scans of materialized-view backing
        tables with their maintenance mode."""
        from matrixone_tpu.mview import catalog as vcat
        reg = vcat.registry_for(self.catalog)
        if not reg:
            return None

        def ann(n):
            t = getattr(n, "table", None)
            if isinstance(n, P.Scan) and isinstance(t, str) \
                    and t.lower() in reg:
                return f" mview={reg[t.lower()].mode}"
            return ""
        return ann

    # --------------------------------------------------------------- udf
    def _create_function(self, stmt: ast.CreateFunction) -> Result:
        """CREATE [OR REPLACE] FUNCTION: validate + trial-compile the
        body, then persist one row in the system_udf catalog table via
        the ordinary commit pipeline — durability, restart replay, and
        CN replication all ride the existing funnels (udf/catalog.py)."""
        import time as _time
        from matrixone_tpu import udf as U
        from matrixone_tpu.udf import catalog as ucat
        if self.txn is not None:
            raise BindError(
                "CREATE FUNCTION inside an explicit transaction is not "
                "supported (function DDL is autocommit)")
        props = {str(k).lower(): str(v).lower()
                 for k, v in stmt.properties.items()}
        for k in props:
            if k not in ("deterministic", "vectorized"):
                raise BindError(f"unknown function property {k!r}; "
                                f"use 'deterministic' | 'vectorized'")
        meta = U.UdfMeta(
            name=stmt.name.lower(),
            kind="aggregate" if stmt.aggregate else "scalar",
            arg_names=[a for a, _t, _ta in stmt.args],
            arg_types=[type_from_name(t, ta) for _a, t, ta in stmt.args],
            ret_type=type_from_name(stmt.ret_type, stmt.ret_args),
            language=stmt.language, body=stmt.body,
            deterministic=props.get("deterministic", "true") != "false",
            vectorized=props.get("vectorized", "true") != "false")
        try:
            U.validate_meta(meta)
        except U.UdfError as e:
            raise BindError(str(e))
        ucat.ensure_table(self.catalog)
        existing = ucat.registry_for(self.catalog)
        if meta.name in existing and not stmt.or_replace:
            raise BindError(f"function {meta.name!r} already exists "
                            f"(use CREATE OR REPLACE FUNCTION)")
        t = self.catalog.get_table(ucat.UDF_TABLE)
        batch = ucat.row_batch(meta, _time.time_ns() // 1000)
        arrays, validity = t.batch_to_arrays(batch)
        txn = self.txn_client.begin()
        try:
            if meta.name in existing:
                # OR REPLACE: delete + insert commit atomically
                txn.delete_rows(ucat.UDF_TABLE, ucat.gids_for_name(
                    self.catalog, meta.name))
            txn.write_batch(ucat.UDF_TABLE, arrays, validity)
            txn.commit()
        except BaseException:   # noqa: BLE001 — rollback, then re-raised
            txn.rollback()
            raise
        return Result()

    def _drop_function(self, stmt: ast.DropFunction) -> Result:
        from matrixone_tpu.udf import catalog as ucat
        if self.txn is not None:
            raise BindError(
                "DROP FUNCTION inside an explicit transaction is not "
                "supported (function DDL is autocommit)")
        u = ucat.registry_for(self.catalog).get(stmt.name.lower())
        if u is None:
            if stmt.if_exists:
                return Result()
            raise BindError(f"no such function {stmt.name!r}")
        gids = ucat.gids_for_name(self.catalog, stmt.name)
        txn = self.txn_client.begin()
        try:
            txn.delete_rows(ucat.UDF_TABLE, gids)
            txn.commit()
        except BaseException:   # noqa: BLE001 — rollback, then re-raised
            txn.rollback()
            raise
        return Result(affected=len(gids))

    def _show_functions(self) -> Result:
        from matrixone_tpu.udf import catalog as ucat
        reg = ucat.registry_for(self.catalog)
        names = sorted(reg)
        b = Batch.from_pydict(
            {"Function": names,
             "Kind": [reg[n].kind for n in names],
             "Signature": [reg[n].signature() for n in names],
             "Language": [reg[n].language for n in names],
             "Deterministic": [int(reg[n].deterministic) for n in names],
             "Vectorized": [int(reg[n].vectorized) for n in names]},
            {"Function": dt.VARCHAR, "Kind": dt.VARCHAR,
             "Signature": dt.TEXT, "Language": dt.VARCHAR,
             "Deterministic": dt.INT64, "Vectorized": dt.INT64})
        return Result(batch=b)

    def _alter_partition(self, stmt: ast.AlterPartition) -> Result:
        """TRUNCATE/DROP PARTITION (partitionservice management ops):
        rows leave via an ordinary tombstone commit, so MVCC snapshots
        and time travel keep seeing the pre-truncate state."""
        import numpy as np
        t = self.catalog.get_table(stmt.table)
        spec = t.meta.partition
        if spec is None:
            raise BindError(f"table {stmt.table!r} is not partitioned")
        if stmt.part not in spec.names:
            raise BindError(f"no partition {stmt.part!r} on {stmt.table!r}")
        if stmt.action == "drop":
            # validate BEFORE the tombstone commit: a refused DROP must
            # not have already destroyed the partition's rows
            if spec.kind != "range":
                raise BindError("DROP PARTITION requires RANGE partitioning")
            if len(spec.names) == 1:
                raise BindError("cannot drop the last partition")
        pid = spec.names.index(stmt.part)
        dead = t._dead_gids(None, None)
        gids = []
        for seg in t.segments:
            if seg.part_id != pid:
                continue
            g = np.arange(seg.base_gid, seg.base_gid + seg.n_rows,
                          dtype=np.int64)
            if len(dead):
                g = g[~np.isin(g, dead)]
            gids.append(g)
        all_gids = (np.concatenate(gids) if gids
                    else np.zeros(0, np.int64))
        if len(all_gids):
            self.catalog.commit_txn(None, {}, {stmt.table: all_gids})
        if stmt.action == "drop":
            self.catalog.alter_partition_drop(stmt.table, stmt.part)
        b = Batch.from_pydict(
            {"partition": [stmt.part], "rows_removed": [len(all_gids)]},
            {"partition": dt.VARCHAR, "rows_removed": dt.INT64})
        return Result(batch=b)

    def _show_partitions(self, stmt: ast.ShowPartitions) -> Result:
        import numpy as np
        t = self.catalog.get_table(stmt.name)
        spec = t.meta.partition
        if spec is None:
            raise BindError(f"table {stmt.name!r} is not partitioned")
        dead = t._dead_gids(None, None)
        rows = {i: 0 for i in range(spec.n_parts)}
        for seg in t.segments:
            if seg.part_id < 0:
                continue
            alive = seg.n_rows
            if len(dead):
                g = np.arange(seg.base_gid, seg.base_gid + seg.n_rows,
                              dtype=np.int64)
                alive = int((~np.isin(g, dead)).sum())
            rows[seg.part_id] = rows.get(seg.part_id, 0) + alive
        bounds = [("MAXVALUE" if b is None else str(b))
                  for b in spec.bounds] if spec.kind == "range" \
            else [""] * spec.n_parts
        b = Batch.from_pydict(
            {"partition": list(spec.names),
             "method": [spec.kind] * spec.n_parts,
             "expr": [spec.column] * spec.n_parts,
             "bound": bounds,
             "rows": [rows[i] for i in range(spec.n_parts)]},
            {"partition": dt.VARCHAR, "method": dt.VARCHAR,
             "expr": dt.VARCHAR, "bound": dt.VARCHAR, "rows": dt.INT64})
        return Result(batch=b)

    def _create_index(self, stmt: ast.CreateIndex) -> Result:
        table = self.catalog.get_table(stmt.table)
        algo = (stmt.using or "").lower()
        if algo in ("ivfflat", "ivf_flat", "ivfpq", "ivf_pq", "hnsw"):
            col = stmt.columns[0]
            coltype = dict(table.meta.schema)[col]
            if not coltype.is_vector:
                raise BindError(f"{algo} index requires a vecf32 column")
            from matrixone_tpu import indexing
            op_type = stmt.options.get("op_type", "vector_l2_ops")
            metric = {"vector_l2_ops": "l2", "vector_cosine_ops": "cosine",
                      "vector_ip_ops": "ip"}.get(op_type, "l2")
            algo_name = ("hnsw" if algo == "hnsw"
                         else "ivfpq" if "pq" in algo else "ivfflat")
            if algo_name == "ivfpq" and metric == "ip":
                raise BindError(
                    "ivfpq does not support vector_ip_ops; use ivfflat")
            build_fn = (indexing.build_hnsw if algo_name == "hnsw"
                        else indexing.build_ivfflat)
            meta = IndexMeta(stmt.name, stmt.table, stmt.columns, algo_name,
                             dict(stmt.options), dirty=True)
            meta.options["_metric"] = metric
            try:
                build_fn(self.catalog, meta)
            except ValueError as e:
                raise BindError(str(e))
            self.catalog.register_index(meta)
            indexing.register_in_cache(self.catalog, meta)
            return Result()
        if algo == "fulltext":
            from matrixone_tpu import indexing
            for col in stmt.columns:
                if not dict(table.meta.schema)[col].is_varlen:
                    raise BindError(
                        f"fulltext index requires text columns ({col})")
            meta = IndexMeta(stmt.name, stmt.table, stmt.columns,
                             "fulltext", dict(stmt.options), dirty=True)
            indexing.build_fulltext(self.catalog, meta)
            self.catalog.register_index(meta)
            indexing.register_in_cache(self.catalog, meta)
            return Result()
        raise BindError(f"unsupported index algo {stmt.using!r}")

    # --------------------------------------------------------------- etl
    def load_csv(self, table: str, path: str, **read_kwargs) -> int:
        """Bulk CSV load (reference: colexec/external CSV reader) via
        pyarrow.csv into the table's schema."""
        import pyarrow.csv as pacsv
        return self._ingest_arrow(table, pacsv.read_csv(path, **read_kwargs))

    def load_parquet(self, table: str, path: str) -> int:
        """Bulk parquet load (reference: colexec/external parquet path)."""
        import pyarrow.parquet as papq
        return self._ingest_arrow(table, papq.read_table(path))

    def _load_data(self, stmt: ast.LoadData) -> Result:
        """LOAD DATA INFILE: path may be local / file:// / fs:// /
        stage:// — resolved through the stage registry + fileservice."""
        import pyarrow.csv as pacsv
        import pyarrow.parquet as papq
        from matrixone_tpu.storage.external import open_location
        self._reject_mview_write(stmt.table)
        fmt = _resolve_format(stmt.fmt, stmt.path)
        if fmt == "iceberg":
            raise BindError(
                "LOAD DATA does not support FORMAT iceberg; create an "
                "external table over it and INSERT ... SELECT instead")
        src = open_location(self.catalog, stmt.path)
        tbl = (papq.read_table(src) if fmt == "parquet"
               else pacsv.read_csv(src))
        n = self._ingest_arrow(stmt.table, tbl)
        return Result(affected=n)

    def _ingest_arrow(self, table: str, tbl) -> int:
        t = self.catalog.get_table(table)
        auto_col = t.meta.auto_increment
        required = [c for c, _ in t.meta.schema if c != auto_col]
        missing = [c for c in required if c not in tbl.schema.names]
        if missing:
            raise BindError(
                f"load into {table!r}: file is missing columns {missing}; "
                f"file has {tbl.schema.names}")
        # extra CSV columns are ignored; the auto_increment column may be
        # absent (values are allocated) or present (counter advances past)
        want = [c for c, _ in t.meta.schema if c in tbl.schema.names]
        from matrixone_tpu.container.batch import Batch as _B
        total = 0
        schema_map = dict(t.meta.schema)
        # every chunk buffers in a txn workspace — explicit txn or a
        # statement-scoped one — so a KILL (or any error) mid-file
        # discards the WHOLE statement; chunk-at-a-time autocommit would
        # leave a killed LOAD half-applied (MySQL rolls the statement back)
        txn = self.txn or self.txn_client.begin()
        try:
            for rb in tbl.select(want).to_batches(max_chunksize=1 << 20):
                # KILL cancels long LOAD DATA between chunks (MySQL KILL
                # QUERY semantics; same preemption contract as _select)
                self._procs.check_killed(self.conn_id)
                batch = _B.from_arrow(rb, schema=schema_map)
                if auto_col is not None:
                    if auto_col in batch.columns:
                        t.observe_auto(np.asarray(
                            batch.columns[auto_col].data, np.int64))
                    else:
                        n = len(batch)
                        from matrixone_tpu.container.vector import Vector
                        batch.columns[auto_col] = Vector.from_values(
                            [int(v) for v in t.allocate_auto(n)],
                            schema_map[auto_col])
                arrays, validity = t.batch_to_arrays(batch)
                total += txn.write_batch(table, arrays, validity)
            if self.txn is None:
                txn.commit()
        except BaseException:  # noqa: BLE001 — rollback, then re-raised
            if self.txn is None:
                txn.rollback()
            raise
        return total

    # --------------------------------------------------------------- dml
    def _pessimistic(self, txn) -> bool:
        return (self.txn is not None
                and self.variables.get("txn_mode") == "pessimistic")

    def _maybe_lock(self, txn, table: str, gids) -> None:
        """Pessimistic mode (reference: colexec/lockop + lockservice.Lock):
        DML takes exclusive row locks before buffering the write; released
        at commit/rollback. `set txn_mode = 'pessimistic'` arms it. A
        deadlock victim is auto-rolled-back (InnoDB/reference behavior) so
        its locks release immediately and the survivor proceeds."""
        if not self._pessimistic(txn):
            return     # autocommit DML serializes through the commit lock
        from matrixone_tpu.lockservice import DeadlockError
        committed = np.asarray(gids)[np.asarray(gids) >= 0]
        if len(committed):
            timeout = float(self.variables.get("lock_timeout", 10.0))
            try:
                self.catalog.locks.lock(txn.txn_id, table, committed,
                                        timeout=timeout)
            except DeadlockError:
                if self.txn is txn:
                    txn.rollback()
                    self.txn = None
                raise

    def _dml_read_ctx(self, txn) -> ExecContext:
        """Row-planning context for DML. Pessimistic txns plan against the
        CURRENT frontier (MySQL 'current read'): after the lock wait, the
        statement must see the rows the lock winner left behind, not its
        own stale snapshot — otherwise the wait ends in a write-write
        conflict anyway."""
        import types
        if self._pessimistic(txn):
            cur = types.SimpleNamespace(
                snapshot_ts=self.catalog.committed_ts,
                workspace=txn.workspace)
            return ExecContext(catalog=self.catalog, txn=cur,
                               variables=self.variables)
        return ExecContext(catalog=self.catalog, txn=txn,
                           variables=self.variables)

    def _plan_and_lock_rows(self, txn, table: str, run_plan):
        """run_plan(ctx) -> (gids, payload). In pessimistic mode: plan at
        the frontier, lock, re-plan (the frontier may have advanced while
        we waited) until the row set stabilizes."""
        result = run_plan(self._dml_read_ctx(txn))
        if not self._pessimistic(txn):
            return result
        for _ in range(5):
            self._maybe_lock(txn, table, result[0])
            again = run_plan(self._dml_read_ctx(txn))
            if set(np.asarray(again[0]).tolist()) == \
                    set(np.asarray(result[0]).tolist()):
                return again
            result = again
        return result

    def _dml_plan(self, table_name: str, where, extra_exprs=None,
                  extra_names=None):
        """Plan `SELECT __rowid [, extra...] FROM t WHERE ...` for DML,
        its Scan like a SELECT's: the predicate pushed into it (zonemap
        pruning) and its columns narrowed to what the plan reads, the
        predicate's and the row id for a DELETE, every column an UPDATE
        rewrites."""
        from matrixone_tpu.sql.binder import Scope
        from matrixone_tpu.sql.optimize import prune_columns
        from matrixone_tpu.sql.expr import BoundCol
        table = self.catalog.get_table(table_name)
        scope = Scope()
        for col, dtype in table.meta.schema:
            scope.add(table_name, col, dtype)
        binder = Binder(self.catalog)
        scan_cols = [c for c, _ in table.meta.schema] + [ROWID]
        scan_schema = [(f"{table_name}.{c}", d)
                       for c, d in table.meta.schema] + [(ROWID, dt.INT64)]
        node = P.Scan(table_name, scan_cols, scan_schema)
        if where is not None:
            # into the Scan's own filters, as a SELECT's: the zonemaps
            # then leave a keyed DELETE the chunks that can hold its rows
            pred = binder.bind_expr(where, scope)
            node = binder._pushdown_scan_filters(
                P.Filter(node, pred, node.schema))
        exprs = [BoundCol(ROWID, dt.INT64)]
        names = [ROWID]
        out_types = [dt.INT64]
        for e, nm in zip(extra_exprs or [], extra_names or []):
            b = binder.bind_expr(e, scope) if not hasattr(e, "dtype") else e
            exprs.append(b)
            names.append(nm)
            out_types.append(b.dtype)
        proj = prune_columns(P.Project(node, exprs,
                                       list(zip(names, out_types))))
        return proj, binder, scope

    def _dml_find(self, txn, table: str, run_plan):
        """The victims of a DELETE / UPDATE: `_plan_and_lock_rows` under
        the span that times the search for their row ids."""
        from matrixone_tpu.utils import motrace
        with motrace.span("dml.find", table=table):
            return self._plan_and_lock_rows(txn, table, run_plan)

    def _dml_rows(self, proj, ctx):
        """The rows a DELETE's or UPDATE's plan selects, a host Batch a
        scan batch that holds any.  They are picked on the host from the
        batch's mask: a batch is the scan's chunk (2^20 lanes) and holds
        a handful of victims or none, and `_to_host`'s compaction would
        be a program of that length (its cumsum alone compiles for 23 to
        104 s on the chip: PERF.md section 6, PR 35)."""
        from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
        for ex in compile_plan(proj, ctx).execute():
            self._procs.check_killed(self.conn_id)       # KILL during DML
            rows = np.flatnonzero(np.asarray(jax.device_get(ex.mask)))
            if len(rows) == 0:
                continue
            cols = {}
            for name, col in ex.batch.columns.items():
                if not col.is_const:
                    col = DeviceColumn(
                        data=np.asarray(jax.device_get(col.data))[rows],
                        validity=np.asarray(
                            jax.device_get(col.validity))[rows],
                        dtype=col.dtype)
                cols[name] = col
            yield from_device(
                DeviceBatch(columns=cols, n_rows=np.int32(len(rows))),
                ex.dicts, schema=dict(proj.schema))

    def _delete(self, stmt: ast.Delete) -> Result:
        self._reject_mview_write(stmt.table)
        txn = self.txn or self.txn_client.begin()
        proj, _, _ = self._dml_plan(stmt.table, stmt.where)

        def run_plan(ctx):
            gids = [b.columns[ROWID].data
                    for b in self._dml_rows(proj, ctx)]
            return np.concatenate([np.zeros(0, np.int64), *gids]), None

        gids, _ = self._dml_find(txn, stmt.table, run_plan)
        txn.delete_rows(stmt.table, gids)
        if self.txn is None:
            txn.commit()
        return Result(affected=len(gids))

    def _update(self, stmt: ast.Update) -> Result:
        self._reject_mview_write(stmt.table)
        txn = self.txn or self.txn_client.begin()
        table = self.catalog.get_table(stmt.table)
        schema = table.meta.schema
        assigned = dict(stmt.assignments)
        extra_exprs, extra_names = [], []
        for col, dtype in schema:
            e = assigned.get(col, ast.ColumnRef(col, stmt.table))
            extra_exprs.append(e)
            extra_names.append(col)
        proj, _, _ = self._dml_plan(stmt.table, stmt.where,
                                    extra_exprs, extra_names)

        def run_plan(ctx):
            gids, new_cols = [], {c: [] for c, _ in schema}
            for b in self._dml_rows(proj, ctx):
                gids.extend(b.columns[ROWID].data.tolist())
                for c, _ in schema:
                    new_cols[c].extend(b.columns[c].to_pylist())
            return np.asarray(gids, np.int64), new_cols

        gids, new_cols = self._dml_find(txn, stmt.table, run_plan)
        if len(gids) == 0:
            return Result(affected=0)
        # rows must round-trip through the table's SQL types (e.g. the
        # assignment may produce float for a decimal column)
        batch = Batch.from_pydict(new_cols, {c: d for c, d in schema})
        arrays, validity = table.batch_to_arrays(batch)
        txn.delete_rows(stmt.table, gids)
        txn.write_batch(stmt.table, arrays, validity)
        if self.txn is None:
            txn.commit()
        return Result(affected=len(gids))

    def _insert(self, stmt: ast.Insert) -> Result:
        self._reject_mview_write(stmt.table)
        table = self.catalog.get_table(stmt.table)
        schema = table.meta.schema
        cols = stmt.columns or [c for c, _ in schema]
        if stmt.select is not None:
            sub = self._select(stmt.select)
            data = {c: sub.batch.columns[n].to_pylist()
                    for c, n in zip(cols, sub.column_names)}
        else:
            data = {c: [] for c in cols}
            for row in stmt.rows:
                if len(row) != len(cols):
                    raise BindError("INSERT arity mismatch")
                for c, v in zip(cols, row):
                    data[c].append(_literal_value(v))
        full = {}
        n = len(next(iter(data.values()))) if data else 0
        auto_col = table.meta.auto_increment
        for c, d in schema:
            vals = data.get(c, [None] * n)
            if c == auto_col:
                # row order matters: an explicit value advances the counter
                # for subsequent NULLs in the same statement (MySQL behavior)
                vals = list(vals)
                for i, v in enumerate(vals):
                    if v is None:
                        vals[i] = int(table.allocate_auto(1)[0])
                        # MySQL last_insert_id(): FIRST generated id
                        # of the statement
                        if not getattr(self, "_liid_set", False):
                            self.last_insert_id = vals[i]
                            self._liid_set = True
                    else:
                        table.observe_auto(np.asarray([v], np.int64))
            if d.oid == TypeOid.DATE:
                vals = [dt.epoch_days_from_iso(v)
                        if isinstance(v, str) else v for v in vals]
            elif d.oid in (TypeOid.DATETIME, TypeOid.TIMESTAMP):
                vals = [dt.epoch_micros_from_iso(v)
                        if isinstance(v, str) else v for v in vals]
            elif d.is_vector:
                vals = [[float(x) for x in v.strip()[1:-1].split(",")]
                        if isinstance(v, str) else v for v in vals]
                for v in vals:
                    if v is not None and len(v) != d.dim:
                        raise BindError(
                            f"vector literal has {len(v)} dimensions, "
                            f"column {c!r} expects {d.dim}")
            full[c] = vals
        batch = Batch.from_pydict(full, {c: d for c, d in schema})
        if self.txn is not None:
            arrays, validity = table.batch_to_arrays(batch)
            n = self.txn.write_batch(stmt.table, arrays, validity)
        else:
            n = table.insert_batch(batch)
        return Result(affected=n)


class _ServingCtx:
    """Per-execution serving context: one normalized statement routed
    through the plan/result caches (matrixone_tpu/serving).

    Two operating modes: `template_mode` (template activated — plan
    cache participates, parameter literals are tagged) and raw mode
    (first occurrence of a template — only the result cache
    participates, the statement executes through the ordinary parse
    path at zero added cost)."""

    def __init__(self, state, norm, full_params, scope: str):
        self.state = state
        self.norm = norm
        self.full = full_params
        self.scope = scope
        self.template_mode = False
        self._pristine = None      # cached template AST (never mutated)
        self._usable = None        # lazily computed on the template AST

    def make_stmts(self):
        """-> [stmt] from the cached template AST, or None (raw path).
        SELECT/UNION return the PRISTINE template — `_select`
        instantiates lazily, so a plan-cache hit never pays the AST
        deepcopy; other statement kinds instantiate eagerly (their
        executors mutate the AST)."""
        tpl = self.state.plan_cache.template_ast(self.norm.template)
        if tpl is None:
            return None
        # every `?` must surface as an ast.Param: a parser that absorbs
        # one as raw text (e.g. index option values) would execute with
        # a literal '?' — structurally-consumed params mean the template
        # is unusable, not just uncacheable
        if _param_indexes(tpl) != set(range(len(self.full))):
            return None
        self.template_mode = True
        self._pristine = tpl
        if isinstance(tpl, (ast.Select, ast.Union)):
            return [tpl]
        st = self.instantiate()
        return None if st is None else [st]

    def owns_pristine(self, stmt) -> bool:
        return self._pristine is not None and stmt is self._pristine

    def instantiate(self, raise_errors: bool = False):
        """Fresh substituted copy of the template.  Bind-time parameter
        errors raise when `raise_errors` (callers already committed to
        the template path), else return None (the raw path reports
        them properly)."""
        import copy as _copy
        st = _copy.deepcopy(self._pristine)
        try:
            return _substitute_params(st, self.full)
        except BindError:
            if raise_errors:
                raise
            return None

    def usable_for(self, sel) -> bool:
        """Caches are only safe for statements whose execution is fully
        visible in the final plan: uncorrelated subqueries / EXISTS
        execute at prepare time and fold to constants (their tables
        would escape the version key), and @@sysvars read session state."""
        if self._usable is None:
            self._usable = not _ast_has(
                sel, (ast.Subquery, ast.Exists, ast.SysVar))
        return self._usable

    def result_enabled(self) -> bool:
        return self.state.result_cache.enabled

    def plan_enabled(self) -> bool:
        return self.state.plan_cache.enabled

    def _vars_key(self, variables=None):
        s = current_session()
        v = s.variables if s is not None else {}
        return (str(v.get("cbo", 1)), int(v.get("ivf_nprobe", 8) or 8),
                int(v.get("ivf_shards", 0) or 0),
                int(v.get("query_shards", 0) or 0))

    def plan_key(self) -> tuple:
        return ("plan", self.scope, self.norm.template,
                self.norm.sig_for(self.full), self._vars_key())

    def result_key(self) -> tuple:
        # the sig guards numerically-equal params of different types:
        # tuple((1,)) == tuple((1.0,)) but INT64 and decimal results differ
        return ("result", self.scope, self.norm.template,
                self.norm.sig_for(self.full), tuple(self.full),
                self._vars_key())


def _param_indexes(node) -> set:
    """All ast.Param indexes reachable in a statement."""
    from matrixone_tpu.serving.plan_cache import iter_plan_values
    return {x.index for x in iter_plan_values(node)
            if isinstance(x, ast.Param)}


def _ast_has(node, kinds) -> bool:
    """Does any reachable node match `kinds`?"""
    from matrixone_tpu.serving.plan_cache import iter_plan_values
    return any(isinstance(x, kinds) for x in iter_plan_values(node))


def _plan_tables(node) -> set:
    """Base tables a plan reads (SELECT privilege targets)."""
    out = set()
    t = getattr(node, "table", None)
    if isinstance(t, str):
        out.add(t)
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if c is not None:
            out |= _plan_tables(c)
    for c in getattr(node, "children", []) or []:
        out |= _plan_tables(c)
    return out


def _param_literal(v) -> ast.Node:
    if v is None:
        return ast.Literal(None, "null")
    if isinstance(v, bool):
        return ast.Literal(v, "bool")
    if isinstance(v, int):
        return ast.Literal(v, "int")
    if isinstance(v, float):
        return ast.Literal(repr(v), "float")
    if isinstance(v, str):
        return ast.Literal(v, "str")
    if isinstance(v, datetime.date):
        return ast.DateLiteral((v - datetime.date(1970, 1, 1)).days)
    raise BindError(f"unsupported parameter type {type(v).__name__}")


def _resolve_format(fmt: str, location: str) -> str:
    """Shared LOAD/EXTERNAL format defaulting + validation (one place so
    the two DDL paths cannot drift; always a BindError on bad input)."""
    if not fmt:
        fmt = "parquet" if location.endswith(".parquet") else "csv"
    if fmt not in ("csv", "parquet", "iceberg"):
        raise BindError(f"unsupported external format {fmt!r}")
    return fmt


def _substitute_params(node, params: list):
    """Replace ? placeholders (ast.Param) with literal values."""
    import dataclasses as dc
    if isinstance(node, ast.Param):
        if node.index >= len(params):
            raise BindError(f"missing value for parameter {node.index + 1}")
        lit = _param_literal(params[node.index])
        # serving plan cache: remember which parameter produced this
        # literal so a cached plan can be re-parameterized (the tag
        # survives into BoundLiteral via binder._bind_literal)
        lit._param_idx = node.index
        return lit
    if dc.is_dataclass(node) and isinstance(node, ast.Node):
        def sub(x):
            if isinstance(x, ast.Node):
                return _substitute_params(x, params)
            if isinstance(x, tuple):
                return tuple(sub(y) for y in x)
            if isinstance(x, list):
                return [sub(y) for y in x]
            return x
        for f in dc.fields(node):
            setattr(node, f.name, sub(getattr(node, f.name)))
    return node


def _literal_value(v: ast.Node):
    if isinstance(v, ast.Literal):
        if v.kind == "float":
            return float(v.value)
        return v.value
    if isinstance(v, ast.DateLiteral):
        return v.days
    if isinstance(v, ast.UnaryOp) and v.op == "-":
        inner = _literal_value(v.operand)
        return -inner
    if isinstance(v, ast.Cast):
        return _literal_value(v.expr)
    raise BindError("INSERT VALUES must be literals")
