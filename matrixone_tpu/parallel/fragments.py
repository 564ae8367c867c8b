"""General distributed executor: plan fragments shipped to peer CNs.

Reference analogue: `pkg/sql/compile/remoterun.go:86 encodeScope` +
`proto/pipeline.proto:529` — the reference serializes arbitrary operator
subtrees (scans, joins, partial aggregation, top-k) and ships them to
peer CNs over morpc; each peer executes the subtree against its OWN
disttae state and the coordinator merges.

Redesign for the CN/TN split here: every CN holds a full logtail-replayed
replica, so a fragment ships as a JSON plan (sql/serde.plan_to_json) with
ONE scan marked `shard=(i, n)` — peer i reads every n-th chunk of that
scan's deterministic chunk sequence; all other scans (join build sides)
are evaluated from the peer's replica, which IS the broadcast-build: the
build data is already resident on every peer, no wire transfer needed.

Two fragment kinds (both exact):
  * partial_agg — peer runs the subtree below an Aggregate and ships raw
    partial group states (rep keys + decomposable fields); the
    coordinator re-groups them with the same mergegroup kernel AggOp
    uses, so a distributed GROUP BY over joins is bit-identical to local
    for the decomposable aggregates (sum/count/min/max int-exact, avg as
    sum+count).
  * collect — peer runs the subtree (typically ending in a local TopK)
    and ships the resulting rows; the coordinator concatenates and
    re-runs the final TopK: the global top-k of a union of per-shard
    top-(k+offset)s is exact.

Merge safety: the coordinator registers a txn lease for the duration of
the query (Engine.txn_opened), so a background merge cannot rewrite gids
under the peers' pinned snapshot.
"""

from __future__ import annotations

import dataclasses
import itertools
from concurrent import futures
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container import from_device
from matrixone_tpu.ops import agg as A
from matrixone_tpu.sql import plan as P
from matrixone_tpu.sql.serde import (agg_from_json, agg_to_json,
                                     expr_to_json, plan_from_json,
                                     plan_to_json)
from matrixone_tpu.storage import arrowio
from matrixone_tpu.vm.process import ExecContext

from matrixone_tpu.sql.parser import BASIC_AGGS, STDDEV_AGGS

# the second-moment family distributes too: its sum/sumsq/count fields
# merge by addition, same as the classic five's fields
_ALLOWED_AGGS = BASIC_AGGS | STDDEV_AGGS
_dist_ids = itertools.count(1 << 40)


# =====================================================================
# peer side: execute one fragment against the local replica
# =====================================================================

def execute_fragment(catalog, header: dict) -> Tuple[dict, bytes]:
    """Run a fragment header against `catalog` (a CN's RemoteCatalog or a
    plain Engine). Returns (resp_header, arrow_blob)."""
    from matrixone_tpu.vm.compile import compile_plan
    kind = header["kind"]
    snapshot_ts = header.get("snapshot_ts")
    consumer = getattr(catalog, "consumer", None)
    if consumer is not None and snapshot_ts is not None:
        consumer.wait_ts(snapshot_ts)   # peer must reach the snapshot
    if header.get("account"):
        # tenant fragment: resolve names in the tenant's namespace
        from matrixone_tpu.frontend.auth import ScopedCatalog
        catalog = ScopedCatalog(catalog, header["account"])
    ctx = ExecContext(catalog=catalog, frozen_ts=snapshot_ts,
                      variables={"batch_rows":
                                 int(header.get("batch_rows", 1 << 16))})
    plan = plan_from_json(header["plan"])
    child_op = compile_plan(plan, ctx)
    sig = (table_signature(catalog, header["shard_table"], snapshot_ts)
           if header.get("shard_table") else None)
    if kind == "collect":
        resp, blob = _run_collect(child_op, plan.schema)
    elif kind == "partial_agg":
        from matrixone_tpu.sql.serde import expr_from_json
        gk = [expr_from_json(k) for k in header["group_keys"]]
        aggs = [agg_from_json(a) for a in header["aggs"]]
        if gk:
            resp, blob = _run_partial_grouped(child_op, plan, gk, aggs)
        else:
            resp, blob = _run_partial_scalar(child_op, aggs)
    else:
        raise ValueError(f"unknown fragment kind {kind!r}")
    if sig is not None:
        # the layout must not have changed UNDER the scan either (a
        # merge resync swapping segment lists mid-fragment)
        after = table_signature(catalog, header["shard_table"],
                                snapshot_ts)
        if after != sig:
            raise RuntimeError("table layout changed during fragment "
                               "execution (merge resync)")
        resp["table_sig"] = sig
    return resp, blob


def _collect_arrays(op, schema):
    """Materialize the fragment's output rows as HOST arrays (strings
    decoded through each batch's dictionary — peer dicts never leave).
    -> (arrays, valid, n_total); empty dicts when no rows."""
    parts: List[dict] = []
    vparts: List[dict] = []
    n_total = 0
    for ex in op.execute():
        host = _to_host(ex, schema)
        n = len(host)
        if n == 0:
            continue
        n_total += n
        arrays, valid = {}, {}
        for name, dtype in schema:
            vec = host.columns[name]
            if dtype.is_varlen:
                arrays[name] = vec.strings.to_pylist()
            else:
                arrays[name] = np.asarray(vec.data)
            valid[name] = np.asarray(vec.valid_mask())
        parts.append(arrays)
        vparts.append(valid)
    if not parts:
        return {}, {}, 0
    arrays = {}
    valid = {}
    for name, dtype in schema:
        if dtype.is_varlen:
            merged: List[Optional[str]] = []
            for p in parts:
                merged.extend(p[name])
            arrays[name] = merged
        else:
            arrays[name] = np.concatenate([p[name] for p in parts])
        valid[name] = np.concatenate([v[name] for v in vparts])
    return arrays, valid, n_total


def _run_collect(op, schema) -> Tuple[dict, bytes]:
    arrays, valid, n_total = _collect_arrays(op, schema)
    if n_total == 0:
        return {"ok": True, "n": 0}, b""
    return ({"ok": True, "n": n_total},
            arrowio.arrays_to_ipc(arrays, valid))


def _to_host(ex, schema):
    from matrixone_tpu.ops import filter as F
    db = F.compact(ex.batch, ex.mask, ex.padded_len)
    return from_device(db, ex.dicts, schema=dict(schema))


def _run_partial_grouped(child_op, child_plan, group_keys, aggs
                         ) -> Tuple[dict, bytes]:
    """AggOp's accumulation loop, stopped BEFORE finalization: the raw
    partial state (rep keys + decomposable fields) ships to the
    coordinator, exactly like colexec/group's partial results flowing to
    mergegroup."""
    from matrixone_tpu.vm.operators import (AggOp, _agg_value,
                                            _AggDictTracker,
                                            _broadcast_full, _expr_dict)
    nkeys = len(group_keys)
    agg_node = P.Aggregate(child_plan, group_keys, aggs,
                           [("k%d" % i, k.dtype)
                            for i, k in enumerate(group_keys)]
                           + [(a.out_name, a.dtype) for a in aggs])
    helper = AggOp(agg_node, child_op)
    key_dicts: List[Optional[list]] = [None] * nkeys
    tracker = _AggDictTracker(aggs)
    state = None
    for ex in child_op.execute():
        tracker.observe(ex)
        from matrixone_tpu.vm.exprs import eval_expr
        keys = [eval_expr(k, ex) for k in group_keys]
        for i, (k_ast, k) in enumerate(zip(group_keys, keys)):
            d = _expr_dict(k_ast, ex)
            if d is not None:
                key_dicts[i] = d
        kdata = [_broadcast_full(k, ex.padded_len).data for k in keys]
        kvalid = [_broadcast_full(k, ex.padded_len).validity for k in keys]
        values = [None if (a.func == "count" and a.arg is None)
                  else _agg_value(a, ex) for a in aggs]
        part = helper._partial_vals(kdata, kvalid, ex.mask, values,
                                    allow_spill=False)
        state = part if state is None else helper._merge(state, part)
    if state is None:
        return {"ok": True, "n_groups": 0}, b""
    ng = int(jax.device_get(state["n"]))
    arrays, valid = {}, {}
    for i, k in enumerate(group_keys):
        kd = np.asarray(jax.device_get(state["keys"][i]))[:ng]
        kv = np.asarray(jax.device_get(state["kvalid"][i]))[:ng]
        if k.dtype.is_varlen:
            d = key_dicts[i] or []
            arrays[f"_g{i}"] = arrowio.to_dict_encoded(d, kd, kv)
        else:
            arrays[f"_g{i}"] = kd
        valid[f"_g{i}"] = kv
        arrays[f"_gv{i}"] = kv
        valid[f"_gv{i}"] = np.ones(ng, np.bool_)
    for j, part in enumerate(state["partials"]):
        for field, arr in part.items():
            a = np.asarray(jax.device_get(arr))[:ng]
            arrays[f"_a{j}_{field}"] = a
            valid[f"_a{j}_{field}"] = np.ones(ng, np.bool_)
    return ({"ok": True, "n_groups": ng},
            arrowio.arrays_to_ipc(arrays, valid))


def _run_partial_scalar(child_op, aggs) -> Tuple[dict, bytes]:
    from matrixone_tpu.vm.operators import _scalar_step
    states = [None] * len(aggs)
    for ex in child_op.execute():
        for i, a in enumerate(aggs):
            states[i] = _scalar_step(a, ex, states[i])
    arrays, valid = {}, {}
    have = False
    for j, (a, st) in enumerate(zip(aggs, states)):
        if st is None:
            continue
        have = True
        if a.func == "count":
            fields = {"count": st}
        elif a.func in STDDEV_AGGS:
            fields = {"sum": st[0], "sumsq": st[1], "count": st[2]}
        elif a.func in ("sum", "avg"):
            fields = {"sum": st[0], "count": st[1]}
        else:
            fields = {a.func: st[0], "count": st[1]}
        for f, v in fields.items():
            arr = np.asarray(jax.device_get(v)).reshape(1)
            arrays[f"_a{j}_{f}"] = arr
            valid[f"_a{j}_{f}"] = np.ones(1, np.bool_)
    if not have:
        return {"ok": True, "n_groups": 0}, b""
    return ({"ok": True, "n_groups": 1},
            arrowio.arrays_to_ipc(arrays, valid))


# =====================================================================
# coordinator side: split, ship, merge
# =====================================================================

_UPPER = (P.Project, P.TopK, P.Sort, P.Limit, P.Filter, P.Distinct)


@dataclasses.dataclass
class _Split:
    kind: str                    # "agg" | "topk" | "join"
    uppers: List[P.PlanNode]     # nodes above the split, root first
    split: P.PlanNode            # the Aggregate / TopK / Join at the split
    scan_path: List[str]         # attr path from fragment child to scan
    scan_table: str
    # shuffle join only: the build (right) side's own sharded scan
    right_path: Optional[List[str]] = None
    right_table: Optional[str] = None


def _find_scan_path(node) -> Optional[Tuple[List[str], str]]:
    """Path of child attrs from `node` down to a scan that is on the
    probe (left) side of every join on the way — the side whose row
    partition partitions the join output."""
    path: List[str] = []
    cur = node
    while True:
        if isinstance(cur, P.Scan):
            return path, cur.table
        if isinstance(cur, (P.Filter, P.Project)):
            path.append("child")
            cur = cur.child
            continue
        if isinstance(cur, P.Join):
            if cur.kind == "full":
                return None      # build-side unmatched rows aren't
            path.append("left")  # partitionable by probe shard
            cur = cur.left
            continue
        return None


def _has_full_join(node) -> bool:
    if isinstance(node, P.Join):
        if node.kind == "full":
            return True
        return _has_full_join(node.left) or _has_full_join(node.right)
    for attr in ("child",):
        c = getattr(node, attr, None)
        if c is not None:
            return _has_full_join(c)
    return False


def plan_split(node, catalog, min_rows: int = 0) -> Optional[_Split]:
    """Decide whether/where to distribute `node` (the compiler's Magic:
    Remote decision, compile/types.go:162). Returns None -> run local."""
    uppers: List[P.PlanNode] = []
    cur = node
    topk_at: Optional[int] = None
    while isinstance(cur, _UPPER):
        if isinstance(cur, P.TopK) and topk_at is None:
            topk_at = len(uppers)
        uppers.append(cur)
        cur = cur.child
    if isinstance(cur, P.Aggregate):
        aggs = cur.aggs
        if any(a.distinct for a in aggs):
            return None
        if any(a.func not in _ALLOWED_AGGS for a in aggs):
            return None
        if any(a.arg is not None and (a.arg.dtype.is_varlen
                                      or a.arg.dtype.is_vector)
               for a in aggs):
            return None
        if _has_full_join(cur.child):
            return None
        found = _find_scan_path(cur.child)
        if found is None:
            return None
        path, table = found
        if not _table_big_enough(catalog, table, min_rows):
            return None
        try:
            plan_to_json(cur.child)
        except TypeError:
            return None
        return _Split("agg", uppers, cur, path, table)
    if topk_at is not None:
        tk = uppers[topk_at]
        if any(k.dtype.is_varlen for k in tk.keys):
            return None
        if _has_full_join(tk.child):
            return None
        found = _find_scan_path(tk.child)
        if found is None:
            return None
        path, table = found
        if not _table_big_enough(catalog, table, min_rows):
            return None
        try:
            plan_to_json(tk)
        except TypeError:
            return None
        return _Split("topk", uppers[:topk_at], tk, path, table)
    # shuffle join (reference: plan/shuffle.go + colexec/shuffle): BOTH
    # sides big — a broadcast/replica-resident build would be the wrong
    # shape, so hash-repartition both sides across the peers by join key
    # and join each bucket locally
    if isinstance(cur, P.Join) and cur.kind == "inner" \
            and cur.left_keys and not cur.residual:
        from matrixone_tpu.sql.expr import BoundCol
        if not all(isinstance(k, BoundCol)
                   for k in cur.left_keys + cur.right_keys):
            return None
        lf = _scan_only_path(cur.left)
        rf = _scan_only_path(cur.right)
        if lf is None or rf is None:
            return None
        (lpath, ltab), (rpath, rtab) = lf, rf
        if not (_table_big_enough(catalog, ltab, min_rows)
                and _table_big_enough(catalog, rtab, min_rows)):
            return None
        try:
            plan_to_json(cur.left)
            plan_to_json(cur.right)
        except TypeError:
            return None
        return _Split("join", uppers, cur, lpath, ltab,
                      right_path=rpath, right_table=rtab)
    return None


def _scan_only_path(node) -> Optional[Tuple[List[str], str]]:
    """Scan path through Filter/Project ONLY (no joins below): each
    shuffle side must be a single sharded table scan subtree."""
    path: List[str] = []
    cur = node
    while True:
        if isinstance(cur, P.Scan):
            return path, cur.table
        if isinstance(cur, (P.Filter, P.Project)):
            path.append("child")
            cur = cur.child
            continue
        return None


def _table_big_enough(catalog, table: str, min_rows: int) -> bool:
    try:
        t = catalog.get_table(table)
        return t.n_rows >= min_rows
    except Exception:          # noqa: BLE001  (e.g. external table)
        return False


def shard_of_peer(addrs, table: str) -> Dict[int, int]:
    """Stable shard ownership (reference: pkg/shardservice
    types.go:67 — table shards placed on CN subsets, reads routed to
    owners). The peer membership comes from the keeper (launch.py wires
    --peers from registered CNs); on top of it, each table's shards map
    to peers by a deterministic hash permutation — so the SAME peer
    always scans the SAME shard of a table across queries and
    coordinators, keeping that shard's blocks warm in exactly one CN's
    block cache (cache-sharded data placement: storage holds one copy
    in the object store; ownership shards the CACHE, not the truth)."""
    import hashlib
    n = len(addrs)
    perm = sorted(range(n), key=lambda i: hashlib.sha1(
        f"{addrs[i]}|{table}".encode()).digest())
    # perm[s] = peer owning shard s  ->  invert to peer -> shard
    return {perm[s]: s for s in range(n)}


def _set_shard(plan_json: dict, path: List[str], i: int, n: int) -> dict:
    import copy
    out = copy.deepcopy(plan_json)
    cur = out
    for attr in path:
        cur = cur[attr]
    cur["shard"] = [i, n]
    return out


def _rebuild_uppers(uppers: List[P.PlanNode], leaf: P.PlanNode):
    node = leaf
    for up in reversed(uppers):
        node = dataclasses.replace(up, child=node)
    return node


import threading

from matrixone_tpu.utils import san

_pool_guard = san.lock("matrixone_tpu.parallel.fragments._pool_guard")


def pool_for(catalog) -> "FragmentPeers":
    """The catalog's shared FragmentPeers pool (double-checked creation:
    concurrent first queries must not each build and leak a pool)."""
    pool = getattr(catalog, "_frag_pool", None)
    if pool is None:
        with _pool_guard:
            pool = getattr(catalog, "_frag_pool", None)
            if pool is None:
                pool = FragmentPeers(catalog.dist_peers)
                catalog._frag_pool = pool
    return pool


class FragmentPeers:
    """Connection pool over the peer CNs' fragment endpoints (pooled
    RpcClient per peer, LANES warm sockets each — shuffle L/R overlap).
    The default timeout is generous: a cold peer jit-compiles every
    fragment shape on its first query, and a premature timeout silently
    downgrades the cluster to local execution. `MO_FRAG_TIMEOUT`
    overrides it (the chaos drills shrink it so a dead peer trips the
    breaker in seconds, after which queries degrade to local execution
    instantly instead of hanging)."""

    LANES = 2     # concurrent fragments per peer (shuffle L/R overlap)

    def __init__(self, addrs, timeout: Optional[float] = None):
        from matrixone_tpu.cluster.rpc import RpcClient, _env_float
        if timeout is None:
            timeout = _env_float("MO_FRAG_TIMEOUT", 180.0)
        self.timeout = timeout
        self.addrs = list(addrs)
        self.clients = [RpcClient(a, timeout=timeout,
                                  pool_size=self.LANES)
                        for a in self.addrs]

    def close(self) -> None:
        for c in self.clients:
            c.close()

    def run(self, headers: List[dict]) -> List[Tuple[dict, bytes]]:
        from matrixone_tpu.cluster.rpc import deadline_scope
        n = len(self.addrs)

        def one(i):
            c = self.clients[i % n]
            # fragments are read-only: transport retries are safe, and
            # a peer whose breaker is open fails the batch instantly
            # (BreakerOpen) -> try_distribute falls back to local
            with deadline_scope(self.timeout):
                resp, blob = c.call({"op": "run_fragment", **headers[i]},
                                    retryable=True)
            if not resp.get("ok"):
                raise RuntimeError(
                    f"fragment on {self.addrs[i % n]}: "
                    f"{resp.get('err')}")
            return resp, blob
        with futures.ThreadPoolExecutor(
                max_workers=max(2, len(headers))) as pool:
            return list(pool.map(one, range(len(headers))))


def table_signature(catalog, table: str, snap: Optional[int]) -> str:
    """Fingerprint of the chunk-sequence-determining layout visible at
    `snap`: every peer must report the same one, or the shard strides do
    not partition the table (an in-flight merge resync)."""
    import hashlib
    import json as _json
    t = catalog.get_table(table)
    segs = [(s.seg_id, s.base_gid, s.n_rows) for s in t.segments
            if snap is None or s.commit_ts <= snap]
    return hashlib.sha1(_json.dumps(segs).encode()).hexdigest()


def try_distribute(node, catalog, ctx, peers: FragmentPeers,
                   min_rows: int = 0, batch_rows: int = 1 << 16):
    """If the plan qualifies, execute its lower fragment across `peers`
    and return a rebuilt plan whose split subtree is a Materialized node;
    None -> caller runs the original plan locally. Any failure —
    including the merge lease RPC — falls back to local (never wrong,
    possibly slower)."""
    if ctx.txn is not None:
        return None       # peers cannot see an open txn's workspace
    split = plan_split(node, catalog, min_rows)
    if split is None:
        return None
    did = next(_dist_ids)
    opened = False
    try:
        # lease FIRST, snapshot second: a merge committing between the
        # two would rewrite chunk sequences under the peers; with the
        # lease held no new merge can start, and the signature check in
        # _dist_* catches one already in flight
        catalog.txn_opened(did)
        opened = True
        consumer = getattr(catalog, "consumer", None)
        if consumer is not None:
            # coordinator is a CN replica: its committed_ts includes
            # LOCAL-only commits (statement tracing writes into the
            # replica's system tables) that never ride the logtail — a
            # peer can never reach that ts. The replicated frontier is
            # the consumer's applied position; everything the
            # coordinator has seen of the SHARED tables is <= it.
            snap = consumer.applied_ts or None
        else:
            snap = max(ctx.snapshot_ts or 0,
                       getattr(catalog, "committed_ts", 0)) or None
        if split.kind == "agg":
            mat = _dist_aggregate(split, catalog, snap, peers, batch_rows)
        elif split.kind == "join":
            mat = _dist_shuffle_join(split, catalog, snap, peers,
                                     batch_rows)
        else:
            mat = _dist_topk(split, catalog, snap, peers, batch_rows)
    except Exception as e:     # noqa: BLE001 — fall back to local
        import sys
        print(f"[dist] fragment execution failed, running locally: "
              f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return None
    finally:
        if opened:
            try:
                catalog.txn_closed(did)
            except Exception:  # noqa: BLE001 — lease expires on its own
                pass
    return _rebuild_uppers(split.uppers, mat)


def _check_sigs(results, addrs) -> None:
    sigs = {r[0].get("table_sig") for r in results}
    if len(sigs) > 1:
        raise RuntimeError(
            f"peers disagree on the sharded table's layout ({sigs}) — "
            f"a merge resync is in flight; falling back to local")


def _dist_aggregate(split: _Split, catalog, snap, peers: FragmentPeers,
                    batch_rows: int) -> P.Materialized:
    agg: P.Aggregate = split.split
    n = len(peers.addrs)
    child_json = plan_to_json(agg.child)
    owners = shard_of_peer(peers.addrs, split.scan_table)
    headers = []
    for i in range(n):
        headers.append({
            "kind": "partial_agg",
            "plan": _set_shard(child_json, split.scan_path,
                               owners[i], n),
            "group_keys": [expr_to_json(k) for k in agg.group_keys],
            "aggs": [agg_to_json(a) for a in agg.aggs],
            "snapshot_ts": snap,
            "batch_rows": batch_rows,
            "shard_table": split.scan_table,
            "account": getattr(catalog, "_acct", None),
        })
    results = peers.run(headers)
    _check_sigs(results, peers.addrs)
    if agg.group_keys:
        return _merge_grouped(agg, results)
    return _merge_scalar(agg, results)


def _merge_grouped(agg: P.Aggregate, results) -> P.Materialized:
    """mergegroup at the coordinator: re-encode varlen keys into a
    coordinator dictionary, concatenate all peers' partial rows, re-group
    once, finalize with the same kernels the local AggOp uses."""
    from matrixone_tpu.vm.operators import _grouped_final
    nkeys, naggs = len(agg.group_keys), len(agg.aggs)
    live = []
    for resp, blob in results:
        if resp.get("n_groups", 0) > 0:
            arrays, _valid = arrowio.ipc_to_arrays(blob)
            live.append((resp["n_groups"], arrays))
    if not live:
        arrays = {n_: [] if d_.is_varlen else np.zeros(0, d_.np_dtype)
                  for n_, d_ in agg.schema}
        return P.Materialized(arrays, {n_: np.zeros(0, np.bool_)
                                       for n_, _ in agg.schema},
                              agg.schema)
    coord_dicts: List[Optional[list]] = [None] * nkeys
    keys, kvalid = [], []
    for i, k in enumerate(agg.group_keys):
        parts = []
        if k.dtype.is_varlen:
            d: list = []
            lut: Dict[str, int] = {}
            coord_dicts[i] = d
            for ng, arrays in live:
                de = arrays[f"_g{i}"]
                enc = np.empty(len(de.cats), np.int32)
                for ci, s in enumerate(de.cats):
                    code = lut.get(s)
                    if code is None:
                        code = len(d)
                        lut[s] = code
                        d.append(s)
                    enc[ci] = code
                parts.append(enc[np.asarray(de.codes, np.int64)][:ng]
                             if len(de.cats)
                             else np.zeros(ng, np.int32))
        else:
            for ng, arrays in live:
                parts.append(np.asarray(arrays[f"_g{i}"])[:ng])
        keys.append(np.concatenate(parts))
        kvalid.append(np.concatenate(
            [np.asarray(arrays[f"_gv{i}"], bool)[:ng]
             for ng, arrays in live]))
    fields: List[Dict[str, np.ndarray]] = []
    for j in range(naggs):
        fs: Dict[str, np.ndarray] = {}
        names = {k.split("_", 2)[2] for _, arrays in live
                 for k in arrays if k.startswith(f"_a{j}_")}
        for f in names:
            fs[f] = np.concatenate(
                [np.asarray(arrays[f"_a{j}_{f}"])[:ng]
                 for ng, arrays in live])
        fields.append(fs)
    # one mergegroup pass over the concatenated partial rows
    total = len(keys[0])
    mg = 1 << max(total - 1, 1).bit_length()
    kd = [jnp.asarray(k) for k in keys]
    kv = [jnp.asarray(v) for v in kvalid]
    mask = jnp.ones((total,), jnp.bool_)
    gi = A.group_ids(kd, kv, mask, mg)
    ng = int(jax.device_get(gi.num_groups))
    if ng > mg:
        raise RuntimeError(f"merged group count {ng} > bucket {mg}")
    rep_k, rep_v = A.gather_keys(kd, kv, gi.rep_rows)
    out_arrays: Dict[str, object] = {}
    out_valid: Dict[str, np.ndarray] = {}
    out_dicts: Dict[str, list] = {}
    for i, (name, dtype) in enumerate(agg.schema[:nkeys]):
        codes = np.asarray(jax.device_get(rep_k[i]))[:ng]
        vmask = np.asarray(jax.device_get(rep_v[i]))[:ng]
        if dtype.is_varlen:
            # carry codes + the coordinator dictionary straight through
            # (MaterializedOp consumes them without per-row decode)
            out_arrays[name] = np.clip(codes, 0, None).astype(np.int32)
            out_dicts[name] = coord_dicts[i] or [""]
        else:
            out_arrays[name] = codes.astype(dtype.np_dtype)
        out_valid[name] = vmask
    for j, ((name, dtype), a) in enumerate(zip(agg.schema[nkeys:],
                                               agg.aggs)):
        merged: Dict[str, jnp.ndarray] = {}
        for f, vals in fields[j].items():
            v = jnp.asarray(vals)
            if f in ("sum", "count", "sumsq"):
                merged[f] = A.seg_sum(v, gi.gids, mask, mg)
            elif f == "min":
                merged[f] = A.seg_min(v, gi.gids, mask, mg)
            elif f == "max":
                merged[f] = A.seg_max(v, gi.gids, mask, mg)
        col = _grouped_final(a, merged, dtype)
        out_arrays[name] = np.asarray(jax.device_get(col.data))[:ng]
        out_valid[name] = np.asarray(jax.device_get(col.validity))[:ng]
    return P.Materialized(out_arrays, out_valid, agg.schema,
                          dicts=out_dicts)


def _merge_scalar(agg: P.Aggregate, results) -> P.Materialized:
    from matrixone_tpu.vm.operators import _scalar_final
    live = []
    for resp, blob in results:
        if resp.get("n_groups", 0) > 0:
            arrays, _ = arrowio.ipc_to_arrays(blob)
            live.append(arrays)
    out_arrays: Dict[str, object] = {}
    out_valid: Dict[str, np.ndarray] = {}
    for j, ((name, dtype), a) in enumerate(zip(agg.schema, agg.aggs)):
        fields: Dict[str, list] = {}
        for arrays in live:
            for k, v in arrays.items():
                if k.startswith(f"_a{j}_"):
                    fields.setdefault(k.split("_", 2)[2], []).append(
                        np.asarray(v)[0])
        if not fields:
            state = None
        elif a.func == "count":
            state = jnp.asarray(np.sum(fields["count"]))
        elif "sumsq" in fields:       # stddev/variance family
            state = (jnp.asarray(np.sum(fields["sum"], axis=0)),
                     jnp.asarray(np.sum(fields["sumsq"], axis=0)),
                     jnp.asarray(np.sum(fields["count"])))
        else:
            cnt = jnp.asarray(np.sum(fields["count"]))
            if a.func in ("sum", "avg"):
                val = jnp.asarray(np.sum(np.asarray(fields["sum"],
                                                    dtype=None), axis=0))
            elif a.func == "min":
                val = jnp.asarray(np.min(fields["min"]))
            else:
                val = jnp.asarray(np.max(fields["max"]))
            state = (val, cnt)
        col = _scalar_final(a, state, dtype)
        out_arrays[name] = np.asarray(jax.device_get(col.data))
        out_valid[name] = np.asarray(jax.device_get(col.validity))
    return P.Materialized(out_arrays, out_valid, agg.schema)


def _dist_topk(split: _Split, catalog, snap, peers: FragmentPeers,
               batch_rows: int) -> P.PlanNode:
    """Per-peer local top-(k+offset) over its shard, concatenated; the
    ORIGINAL TopK re-runs at the coordinator over the union (exact: every
    global top-k row is in its shard's local top-(k+offset))."""
    tk: P.TopK = split.split
    local = dataclasses.replace(tk, k=tk.k + tk.offset, offset=0)
    n = len(peers.addrs)
    tk_json = plan_to_json(local)
    owners = shard_of_peer(peers.addrs, split.scan_table)
    # the sharded scan sits below the TopK: path starts at tk.child
    headers = [{
        "kind": "collect",
        "plan": _set_shard(tk_json, ["child"] + split.scan_path,
                           owners[i], n),
        "snapshot_ts": snap,
        "batch_rows": batch_rows,
        "shard_table": split.scan_table,
        "account": getattr(catalog, "_acct", None),
    } for i in range(n)]
    results = peers.run(headers)
    _check_sigs(results, peers.addrs)
    arrays: Dict[str, object] = {}
    valid: Dict[str, np.ndarray] = {}
    parts = [arrowio.ipc_to_arrays(blob) for resp, blob in results
             if resp.get("n", 0) > 0]
    if not parts:
        arrays = {n_: [] if d_.is_varlen else np.zeros(0, d_.np_dtype)
                  for n_, d_ in tk.schema}
        mat = P.Materialized(arrays, {n_: np.zeros(0, np.bool_)
                                      for n_, _ in tk.schema}, tk.schema)
        return dataclasses.replace(tk, child=mat)
    for name, dtype in tk.schema:
        if dtype.is_varlen:
            merged: List[Optional[str]] = []
            for a, v in parts:
                col = a[name]
                if isinstance(col, arrowio.DictEncoded):
                    vs = np.asarray(v[name], bool)
                    merged.extend(
                        col.cats[int(c)] if ok else None
                        for c, ok in zip(col.codes.tolist(), vs.tolist()))
                else:
                    merged.extend(col)
            arrays[name] = merged
        else:
            arrays[name] = np.concatenate(
                [np.asarray(a[name]) for a, _ in parts])
        valid[name] = np.concatenate(
            [np.asarray(v[name], bool) for _, v in parts])
    mat = P.Materialized(arrays, valid, tk.schema)
    return dataclasses.replace(tk, child=mat)


# =====================================================================
# shuffle join (reference: plan/shuffle.go determineShuffleMethod +
# colexec/shuffle + dispatch): hash-repartition BOTH sides across the
# peers by join key, each peer joins its bucket locally, the
# coordinator concatenates. Exact for inner equi-joins: equal keys land
# in the same bucket on both sides.
# =====================================================================



def _stable_row_hash(cols: List[object]) -> np.ndarray:
    """Deterministic cross-process row hash of the join key columns
    (strings included) — pandas' siphash with its fixed key, combined
    across columns with an odd multiplier."""
    import pandas as pd
    out = None
    for c in cols:
        if isinstance(c, list):
            arr = np.asarray(c, dtype=object)
        else:
            arr = np.asarray(c)
            # width-normalize: hash_array(int32(-1)) != hash_array(
            # int64(-1)) (pandas zero-extends small ints) — an
            # int32-vs-bigint equi-join would silently drop matches
            if arr.dtype.kind in ("i", "u", "b"):
                arr = arr.astype(np.int64)
            elif arr.dtype.kind == "f":
                arr = arr.astype(np.float64)
        h = pd.util.hash_array(arr, categorize=False)
        out = h if out is None else (out * np.uint64(0x9E3779B1)) ^ h
    return out


class ShuffleStore:
    """Peer-side mailbox for in-flight shuffle buckets, keyed by
    (shuffle_id, side, to): receives pushes from every peer (including
    the local short-circuit) and hands the join phase a complete set.
    The destination index rides in the key so engines SHARED by several
    in-process fragment servers (tests, embed clusters) keep each
    recipient's buckets separate."""

    def __init__(self):
        self._lock = san.lock("ShuffleStore._lock")
        self._cond = san.condition(self._lock)
        self._buckets: Dict[tuple, Dict[int, bytes]] = {}
        self._born: Dict[tuple, float] = {}

    #: stale-mailbox TTL: buckets orphaned by a failed phase 1 (the
    #: coordinator also sends an explicit shuffle_drop, but a dead
    #: coordinator can't) are evicted on later traffic
    TTL_S = 600.0

    def put(self, shuffle_id, side: str, frm: int, to: int,
            blob: bytes) -> None:
        import time as _time
        now = _time.monotonic()
        with self._cond:
            self._prune_locked(now)
            self._buckets.setdefault(
                (shuffle_id, side, to), {})[frm] = blob
            self._born.setdefault((shuffle_id, side, to), now)
            self._cond.notify_all()

    def _prune_locked(self, now: float) -> None:
        for k in [k for k, t0 in self._born.items()
                  if now - t0 > self.TTL_S]:
            self._buckets.pop(k, None)
            self._born.pop(k, None)

    def wait_all(self, shuffle_id, side: str, to: int, expect: int,
                 timeout: float = 120.0) -> Dict[int, bytes]:
        import time as _time
        deadline = _time.monotonic() + timeout
        with self._cond:
            while True:
                got = self._buckets.get((shuffle_id, side, to), {})
                if len(got) >= expect:
                    return dict(got)
                left = deadline - _time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"shuffle {shuffle_id}/{side}->{to}: "
                        f"{len(got)}/{expect} buckets after timeout")
                self._cond.wait(left)

    def drop(self, shuffle_id, to: int) -> None:
        with self._cond:
            for k in [k for k in self._buckets
                      if k[0] == shuffle_id and k[2] == to]:
                del self._buckets[k]
                self._born.pop(k, None)

    def drop_sid(self, shuffle_id) -> None:
        """Coordinator-ordered cleanup after a failed shuffle: every
        bucket of the id, all destinations."""
        with self._cond:
            for k in [k for k in self._buckets if k[0] == shuffle_id]:
                del self._buckets[k]
                self._born.pop(k, None)


def shuffle_store_for(catalog) -> ShuffleStore:
    st = getattr(catalog, "_shuffle_store", None)
    if st is None:
        st = ShuffleStore()
        catalog._shuffle_store = st
    return st


def _schema_to_json(schema) -> list:
    from matrixone_tpu.storage.engine import schema_to_json
    return schema_to_json(schema)


def _schema_from_json(rows):
    from matrixone_tpu.storage.engine import schema_from_json
    return schema_from_json(rows)


def run_shuffle_scan(catalog, header: dict) -> Tuple[dict, bytes]:
    """Phase 1 (peer side): execute the sharded scan subtree, hash rows
    into n buckets by join key, push each bucket to its owner peer
    (direct CN->CN, not through the coordinator), keep own bucket."""
    from matrixone_tpu.cluster.rpc import RpcClient
    from matrixone_tpu.vm.compile import compile_plan
    snapshot_ts = header.get("snapshot_ts")
    consumer = getattr(catalog, "consumer", None)
    if consumer is not None and snapshot_ts is not None:
        consumer.wait_ts(snapshot_ts)
    # the mailbox lives on the BASE catalog — the same object the
    # fragment server uses for incoming shuffle_put pushes (a
    # tenant-scoped wrapper would orphan the local bucket)
    store = shuffle_store_for(catalog)
    if header.get("account"):
        from matrixone_tpu.frontend.auth import ScopedCatalog
        catalog = ScopedCatalog(catalog, header["account"])
    ctx = ExecContext(catalog=catalog, frozen_ts=snapshot_ts,
                      variables={"batch_rows":
                                 int(header.get("batch_rows", 1 << 16))})
    plan = plan_from_json(header["plan"])
    op = compile_plan(plan, ctx)
    schema = plan.schema
    key_names = header["key_names"]
    n = int(header["n_buckets"])
    me = int(header["my_index"])
    sid = str(header["shuffle_id"])
    side = header["side"]
    sig = (table_signature(catalog, header["shard_table"], snapshot_ts)
           if header.get("shard_table") else None)
    # materialize the shard's rows host-side (strings decoded) —
    # directly as arrays: only the per-destination BUCKETS serialize
    arrays, valid, n_rows = _collect_arrays(op, schema)
    if n_rows == 0:
        arrays = {nm: ([] if d.is_varlen else np.zeros(0, d.np_dtype))
                  for nm, d in schema}
        valid = {nm: np.zeros(0, np.bool_) for nm, _ in schema}
    if n_rows:
        hashes = _stable_row_hash([arrays[k] for k in key_names])
        buckets = (hashes % np.uint64(n)).astype(np.int64)
    else:
        buckets = np.zeros(0, np.int64)
    sent = 0
    for j in range(n):
        rowsel = np.nonzero(buckets == j)[0]
        ba = {}
        bv = {}
        for nm, d in schema:
            if d.is_varlen:
                src = arrays[nm]
                ba[nm] = [src[int(r)] for r in rowsel]
            else:
                ba[nm] = np.asarray(arrays[nm])[rowsel]
            bv[nm] = np.asarray(valid[nm])[rowsel]
        bblob = arrowio.arrays_to_ipc(ba, bv)
        if j == me:
            store.put(sid, side, me, me, bblob)
        else:
            c = RpcClient(tuple(header["peer_addrs"][j]), timeout=60.0)
            try:
                # idempotent: a retried put overwrites the same bucket
                # key with the same bytes
                r, _ = c.call({"op": "shuffle_put", "shuffle_id": sid,
                               "side": side, "from": me, "to": j},
                              bblob, retryable=True)
                if not r.get("ok"):
                    raise RuntimeError(r.get("err"))
            finally:
                c.close()
            sent += len(rowsel)
    out = {"ok": True, "n": n_rows, "pushed": sent}
    if sig is not None:
        after = table_signature(catalog, header["shard_table"],
                                snapshot_ts)
        if after != sig:
            raise RuntimeError("table layout changed during shuffle "
                               "scan (merge resync)")
        out["table_sig"] = sig
    return out, b""


def run_shuffle_join(catalog, header: dict) -> Tuple[dict, bytes]:
    """Phase 2 (peer side): assemble this peer's buckets of both sides,
    run the join locally, return the joined rows."""
    from matrixone_tpu.sql.serde import expr_from_json
    from matrixone_tpu.vm.compile import compile_plan
    store = shuffle_store_for(catalog)   # base catalog: same mailbox
    # as the fragment server's shuffle_put handler
    sid = str(header["shuffle_id"])
    expect = int(header["n_buckets"])
    me = int(header["my_index"])
    lschema = _schema_from_json(header["left_schema"])
    rschema = _schema_from_json(header["right_schema"])
    try:
        lparts = store.wait_all(sid, "L", me, expect)
        rparts = store.wait_all(sid, "R", me, expect)
        lmat = _concat_ipc_parts(lparts, lschema)
        rmat = _concat_ipc_parts(rparts, rschema)
    finally:
        store.drop(sid, me)
    join = P.Join(
        kind="inner",
        left=P.Materialized(lmat[0], lmat[1], lschema),
        right=P.Materialized(rmat[0], rmat[1], rschema),
        left_keys=[expr_from_json(k) for k in header["left_keys"]],
        right_keys=[expr_from_json(k) for k in header["right_keys"]],
        residual=None,
        schema=_schema_from_json(header["out_schema"]))
    ctx = ExecContext(catalog=catalog,
                      variables={"batch_rows":
                                 int(header.get("batch_rows", 1 << 16))})
    op = compile_plan(join, ctx)
    return _run_collect(op, join.schema)


def _concat_ipc_parts(parts: Dict[int, bytes], schema):
    arrays_l: Dict[str, list] = {nm: [] for nm, _ in schema}
    valid_l: Dict[str, list] = {nm: [] for nm, _ in schema}
    for frm in sorted(parts):
        a, v = arrowio.ipc_to_arrays(parts[frm])
        if not v:
            continue
        for nm, d in schema:
            arrays_l[nm].append(a[nm])
            valid_l[nm].append(np.asarray(v[nm]))
    arrays = {}
    valid = {}
    for nm, d in schema:
        if d.is_varlen:
            merged: list = []
            for p in arrays_l[nm]:
                merged.extend(p)
            arrays[nm] = merged
        else:
            arrays[nm] = (np.concatenate(arrays_l[nm]) if arrays_l[nm]
                          else np.zeros(0, d.np_dtype))
        valid[nm] = (np.concatenate(valid_l[nm]) if valid_l[nm]
                     else np.zeros(0, np.bool_))
    return arrays, valid


def _shuffle_cleanup(peers: "FragmentPeers", sid) -> None:
    """Best-effort mailbox cleanup after a failed shuffle: peers with
    delivered buckets must not hold them until TTL (leak under repeated
    failing queries)."""
    from matrixone_tpu.cluster.rpc import RpcClient, parse_addr
    for a in peers.addrs:
        try:
            c = RpcClient(parse_addr(a), timeout=5.0)
            try:
                c.call({"op": "shuffle_drop", "shuffle_id": sid})
            finally:
                c.close()
        except Exception:      # noqa: BLE001 — cleanup is best-effort
            pass


def _dist_shuffle_join(split: _Split, catalog, snap,
                       peers: FragmentPeers,
                       batch_rows: int) -> P.Materialized:
    from matrixone_tpu.cluster.rpc import parse_addr
    import uuid as _uuid
    join: P.Join = split.split
    n = len(peers.addrs)
    # globally unique: several CN coordinators may shuffle concurrently
    # through the same peers — a per-process counter would mix their
    # mailboxes
    sid = _uuid.uuid4().hex
    peer_addrs = [list(parse_addr(a)) for a in peers.addrs]
    lkeys = [k.name for k in join.left_keys]
    rkeys = [k.name for k in join.right_keys]
    ljson = plan_to_json(join.left)
    rjson = plan_to_json(join.right)
    common = {
        "snapshot_ts": snap, "batch_rows": batch_rows,
        "account": getattr(catalog, "_acct", None),
        "shuffle_id": sid, "n_buckets": n, "peer_addrs": peer_addrs,
    }
    # phase 1: both sides scatter concurrently (all 2n fragments in one
    # pool run — the left side's buckets stream while the right scans)
    lowners = shard_of_peer(peers.addrs, split.scan_table)
    rowners = shard_of_peer(peers.addrs, split.right_table)
    headers = []
    for i in range(n):
        headers.append({**common, "kind": "shuffle_scan",
                        "plan": _set_shard(ljson, split.scan_path,
                                           lowners[i], n),
                        "side": "L", "my_index": i,
                        "key_names": lkeys,
                        "shard_table": split.scan_table})
    for i in range(n):
        headers.append({**common, "kind": "shuffle_scan",
                        "plan": _set_shard(rjson, split.right_path,
                                           rowners[i], n),
                        "side": "R", "my_index": i,
                        "key_names": rkeys,
                        "shard_table": split.right_table})
    try:
        results = peers.run(headers)
        _check_sigs(results[:n], peers.addrs)
        _check_sigs(results[n:], peers.addrs)
    except Exception:   # noqa: BLE001 — peer-side shuffle-state GC for
        # ANY phase-1 failure (transport, sig mismatch); re-raised
        _shuffle_cleanup(peers, sid)
        raise
    # phase 2: every peer joins its bucket
    jheaders = [{**common, "kind": "shuffle_join", "my_index": i,
                 "left_schema": _schema_to_json(join.left.schema),
                 "right_schema": _schema_to_json(join.right.schema),
                 "out_schema": _schema_to_json(join.schema),
                 "left_keys": [expr_to_json(k) for k in join.left_keys],
                 "right_keys": [expr_to_json(k) for k in join.right_keys]}
                for i in range(n)]
    try:
        jres = peers.run(jheaders)
    except Exception:   # noqa: BLE001 — peer-side shuffle-state GC,
        _shuffle_cleanup(peers, sid)    # then re-raised
        raise
    parts = {i: blob for i, (resp, blob) in enumerate(jres)
             if resp.get("n", 0) > 0}
    arrays, valid = _concat_ipc_parts(parts, join.schema)
    return P.Materialized(arrays, valid, join.schema)
