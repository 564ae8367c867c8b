"""Distributed query execution over a device mesh.

TPU-native re-architecture of the reference's multi-CN execution
(`compile/scope.go:504 ParallelRun`, `:423 RemoteRun`, `colexec/shuffle` +
`dispatch` + `merge*`): instead of serializing operator subtrees over morpc,
the whole plan is one `shard_map`-ed XLA program and the exchange operators
become collectives on the ICI:

  reference                      here
  ---------------------------    -----------------------------------
  ParallelRun DOP pipelines      rows sharded over mesh axis "shard"
  shuffle (hash repartition)     ppermute/all_to_all inside shard_map
  broadcast join / joinmap       all_gather of build side
  merge group (two-phase agg)    local segment agg + psum
  merge top-k                    local top_k + all_gather + global top_k

Three canonical steps live here:
  * sharded_group_aggregate — two-phase distributed GROUP BY
  * sharded_topk            — distributed vector search (cuvs "sharded
                              multi-GPU" mode, cgo/cuvs/README.md)
  * hash_shuffle            — all_to_all repartition by key hash
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from matrixone_tpu.parallel.mesh import make_mesh

from matrixone_tpu.ops import agg as A, distance as D, hash as H


# ---------------------------------------------------------------- group by

def sharded_group_aggregate(mesh: Mesh, keys: jnp.ndarray, values: jnp.ndarray,
                            row_mask: jnp.ndarray, max_groups: int,
                            axis: str = "shard"):
    """Distributed `SELECT key, sum(v), count(*) GROUP BY key`.

    Phase 1 (per shard): local dense-bucket segment aggregation.
    Phase 2: psum of the partial group tables across shards — the two-phase
    group/mergegroup pattern (`colexec/group` + `colexec/mergegroup`),
    with psum playing mergegroup.

    EXACT when keys are dense codes in [0, max_groups) — which is how the
    SQL layer calls it (group keys are dictionary codes / small ints). For
    large-domain keys use hash_shuffle + per-shard ops.agg.group_ids
    instead (co-locates equal keys, stays exact).

    Returns (group_keys [max_groups], sums, counts, present_mask) replicated.
    """
    def step(k_sh, v_sh, m_sh):
        bucket = jnp.clip(k_sh, 0, max_groups - 1).astype(jnp.int32)
        sums = jax.ops.segment_sum(jnp.where(m_sh, v_sh, 0), bucket,
                                   num_segments=max_groups)
        counts = jax.ops.segment_sum(m_sh.astype(jnp.int64), bucket,
                                     num_segments=max_groups)
        keys_tbl = jax.ops.segment_max(
            jnp.where(m_sh, k_sh, jnp.iinfo(k_sh.dtype).min), bucket,
            num_segments=max_groups)
        # merge partial tables across shards (mergegroup)
        sums = jax.lax.psum(sums, axis)
        counts = jax.lax.psum(counts, axis)
        keys_tbl = jax.lax.pmax(keys_tbl, axis)
        return keys_tbl, sums, counts, counts > 0

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P(), P()))
    return fn(keys, values, row_mask)


# ----------------------------------------------------------------- top-k

def sharded_topk(mesh: Mesh, vectors: jnp.ndarray, queries: jnp.ndarray,
                 k: int, axis: str = "shard") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Distributed exact top-k: vectors row-sharded, queries replicated.

    Local matmul distances + local top_k, then all_gather(k per shard) and a
    global top_k — the cuvs sharded-mode consolidation
    (`pkg/cuvs/multi_index.go`) as two XLA collectives.
    """
    n_per, d = vectors.shape[0] // mesh.devices.size, vectors.shape[1]

    def step(v_sh, q):
        dist = D.l2_distance_sq(v_sh, q)                  # [n_sh, b]
        top_s, top_i = jax.lax.top_k(-dist.T, k)          # [b, k] local
        shard_no = jax.lax.axis_index(axis)
        gids = top_i + shard_no * n_per                   # global row ids
        all_s = jax.lax.all_gather(top_s, axis, axis=1).reshape(q.shape[0], -1)
        all_i = jax.lax.all_gather(gids, axis, axis=1).reshape(q.shape[0], -1)
        best_s, pos = jax.lax.top_k(all_s, k)
        best_i = jnp.take_along_axis(all_i, pos, axis=1)
        return -best_s, best_i

    fn = jax.shard_map(step, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=(P(), P()), check_vma=False)
    return fn(vectors, queries)


# ---------------------------------------------------------------- shuffle

class ShuffleOverflow(RuntimeError):
    """cap_per_dest was too small for the key skew; re-run with the
    reported capacity."""

    def __init__(self, needed: int):
        super().__init__(
            f"hash_shuffle bucket overflow: a destination needs capacity "
            f"{needed}; re-run with cap_per_dest >= {needed}")
        self.needed = needed


def hash_shuffle(mesh: Mesh, keys: jnp.ndarray, values: jnp.ndarray,
                 axis: str = "shard",
                 cap_per_dest: int | None = None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """all_to_all hash repartition: row (k,v) moves to shard hash(k)%P.

    The reference's `colexec/shuffle` (hash mode, shuffle.go:200) + dispatch
    over morpc, as one ICI all_to_all. `cap_per_dest` is each destination
    bucket's capacity per source shard: default n_per_shard (lossless but
    output is n_dev x input rows per shard — all padding); size it to
    ~ (n_per_shard / n_dev) * skew_factor to bound memory. Undersized caps
    raise ShuffleOverflow with the needed capacity — rows are NEVER
    silently dropped (a shuffle that loses rows is a wrong-answer machine).

    Returns (keys', values') re-sharded so equal keys are co-located, with
    key == -1 marking padding slots.
    """
    n_dev = mesh.devices.size

    def step(k_sh, v_sh):
        n = k_sh.shape[0]
        cap = n if cap_per_dest is None else cap_per_dest
        dest = (H.hash_column(k_sh) % jnp.uint64(n_dev)).astype(jnp.int32)
        # stable order by destination, then slot within destination
        order = jnp.argsort(dest, stable=True)
        k_srt, v_srt, d_srt = k_sh[order], v_sh[order], dest[order]
        # position within destination bucket
        same = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                (d_srt[1:] == d_srt[:-1]).astype(jnp.int32)])
        # rank via cumsum segmented by destination
        idx = jnp.arange(n)
        seg_start = jnp.where(same == 0, idx, 0)
        start_of_dest = jax.lax.associative_scan(jnp.maximum, seg_start)
        rank = idx - start_of_dest
        # largest bucket demand (global): the overflow signal
        max_rank = jax.lax.pmax(jnp.max(rank) + 1, axis)
        slot_k = jnp.full((n_dev, cap), -1, k_sh.dtype)
        slot_v = jnp.zeros((n_dev, cap), v_sh.dtype)
        ok = rank < cap
        slot_k = slot_k.at[d_srt, jnp.where(ok, rank, cap - 1)].set(
            jnp.where(ok, k_srt, -1), mode="drop")
        slot_v = slot_v.at[d_srt, jnp.where(ok, rank, cap - 1)].set(
            jnp.where(ok, v_srt, 0), mode="drop")
        # exchange: bucket p goes to device p
        k_out = jax.lax.all_to_all(slot_k, axis, split_axis=0, concat_axis=0)
        v_out = jax.lax.all_to_all(slot_v, axis, split_axis=0, concat_axis=0)
        return k_out.reshape(-1), v_out.reshape(-1), max_rank

    fn = jax.shard_map(step, mesh=mesh, in_specs=(P(axis), P(axis)),
                       out_specs=(P(axis), P(axis), P()))
    k_out, v_out, max_need = fn(keys, values)
    if cap_per_dest is not None:
        needed = int(jax.device_get(jnp.max(max_need)))
        if needed > cap_per_dest:
            raise ShuffleOverflow(needed)
    return k_out, v_out


# ----------------------------------------------------------- full Q1 step

def distributed_q1(mesh: Mesh, cols: dict, n_flags: int = 4,
                   n_status: int = 2, axis: str = "shard"):
    """TPC-H Q1 as ONE shard_map program over the mesh: per-shard masked
    segment aggregation into the dense (returnflag x linestatus) group
    table, merged with psum — the distributed form of the Session's Q1
    pipeline (scan rows are sharded across devices like ParallelRun DOP
    pipelines, mergegroup is a psum over ICI).

    cols: row-sharded device arrays {shipdate i32, flag i32 codes,
    status i32 codes, qty/price/disc/tax int64 scaled}, plus 'mask' bool.
    Returns replicated dense arrays keyed by group slot
    g = flag * n_status + status: sum_qty, sum_base, sum_disc, sum_charge,
    count, present.
    """
    n_groups = n_flags * n_status

    def step(flag, status, qty, price, disc, tax, mask):
        gid = (flag * n_status + status).astype(jnp.int32)
        m = mask
        disc_price = price * (100 - disc)              # scale 4
        charge = disc_price * (100 + tax)              # scale 6

        def seg(v):
            return jax.lax.psum(
                jax.ops.segment_sum(jnp.where(m, v, 0), gid,
                                    num_segments=n_groups), axis)
        out = {
            "sum_qty": seg(qty),
            "sum_base": seg(price),
            "sum_disc": seg(disc_price),
            "sum_charge": seg(charge),
            "count": jax.lax.psum(
                jax.ops.segment_sum(m.astype(jnp.int64), gid,
                                    num_segments=n_groups), axis),
        }
        out["present"] = out["count"] > 0
        return (out["sum_qty"], out["sum_base"], out["sum_disc"],
                out["sum_charge"], out["count"], out["present"])

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=tuple([P(axis)] * 7),
        out_specs=tuple([P()] * 6))
    return fn(cols["flag"], cols["status"], cols["qty"], cols["price"],
              cols["disc"], cols["tax"], cols["mask"])


# =====================================================================
# SQL shard executor: parallel/fragments.py's coordinator retargeted
# from host peers (morpc) to the device mesh.  plan_split decides the
# fragment exactly as for remote CNs; instead of shipping plan JSON to
# peers, each shard's fragment is compiled locally (PR-13 fusion intact)
# against a shard-routed scan and dispatched under that shard's device;
# the partial results merge in ONE traced program (psum over the mesh
# for dense group tables, a single jitted mergegroup otherwise).
# =====================================================================

import dataclasses
import os
import sys
from typing import Dict, List, Optional

import numpy as np

from matrixone_tpu.sql import plan as PL
from matrixone_tpu.utils import motrace

# the mergegroup kernels + their audited compile cache (re-exported:
# tests and tools reach the cache/site/counter through this module)
from matrixone_tpu.parallel.merge_exec import (      # noqa: F401
    SITE_MERGE, _MERGE_CACHE, _MERGE_CALLS, ShardDegrade,
    _dense_merge, _general_merge, _merge_key_dicts, _merge_trackers,
    _scalar_combine)


def _shuffle_min_build() -> int:
    return int(os.environ.get("MO_SHUFFLE_BUILD_ROWS", "65536") or 65536)


@dataclasses.dataclass
class _JoinX:
    """One spine join's exchange decision."""
    prefix: tuple                  # attr path from the fragment root
    node: object                   # the ORIGINAL join node (read-only)
    mode: str                      # broadcast | shuffle | local
    lcol: Optional[str] = None     # probe-scan raw hash column (shuffle)
    rpath: Optional[tuple] = None  # path inside node.right to its scan
    rcol: Optional[str] = None     # build-scan raw hash column (shuffle)


@dataclasses.dataclass
class _XPlan:
    joins: List[_JoinX]
    probe_mode: str                # "rr" (chunk round-robin) | "hash"
    probe_col: Optional[str]
    modes_by_id: dict              # id(original node) -> mode (EXPLAIN)


def _node_at(root, path):
    cur = root
    for attr in path:
        cur = getattr(cur, attr)
    return cur


def _spine_joins(root, scan_path):
    """[(prefix, join_node)] for every join on the probe spine, top
    first, plus the probe scan node itself."""
    out = []
    cur = root
    for i, step in enumerate(scan_path):
        if step == "left":
            out.append((tuple(scan_path[:i]), cur))
        cur = getattr(cur, step)
    return out, cur


def _filter_only_scan(node):
    """(path, scan) walking Filter nodes ONLY — the join key's name maps
    1:1 onto the scan schema (a Project rename would break it)."""
    path = []
    cur = node
    while True:
        if isinstance(cur, PL.Scan):
            return tuple(path), cur
        if isinstance(cur, PL.Filter):
            path.append("child")
            cur = cur.child
            continue
        return None


def _qcol_to_raw(scan, qname: str) -> Optional[str]:
    """Qualified column name -> the scan's raw storage column, when the
    column is int-backed (hash routing domain)."""
    for (qn, d), raw in zip(scan.schema, scan.columns):
        if qn == qname:
            if d.is_varlen or not np.issubdtype(np.dtype(d.np_dtype),
                                                np.integer):
                return None
            return raw
    return None


def _partition_spec(catalog, table: str):
    try:
        return catalog.get_table(table).meta.partition
    except Exception:           # noqa: BLE001
        return None


def _co_partitioned(catalog, table: str, col: str, n_shards: int) -> bool:
    spec = _partition_spec(catalog, table)
    return (spec is not None and spec.kind == "hash"
            and spec.column == col and spec.n_parts == n_shards)


def _partition_sig(catalog, table: str):
    spec = _partition_spec(catalog, table)
    return None if spec is None else tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in spec.to_json().items()))


def _shuffle_choice(j, catalog, n_shards: int):
    """Shuffle-vs-broadcast for the bottom spine join (CBO: build-side
    cardinality + the PR-13 runtime-filter key ranges).  Returns
    (mode, probe_raw_col, right_path, right_raw_col) or None ->
    broadcast."""
    from matrixone_tpu.sql import cbo
    from matrixone_tpu.sql.expr import BoundCol
    from matrixone_tpu.sql.stats import provider_for
    if j.kind != "inner" or not j.left_keys or j.residual is not None:
        return None
    lk, rk = j.left_keys[0], j.right_keys[0]
    if not (isinstance(lk, BoundCol) and isinstance(rk, BoundCol)):
        return None
    lwalk = _filter_only_scan(j.left)
    rwalk = _filter_only_scan(j.right)
    if lwalk is None or rwalk is None:
        return None
    (_lpath, lscan), (rpath, rscan) = lwalk, rwalk
    lraw = _qcol_to_raw(lscan, lk.name)
    rraw = _qcol_to_raw(rscan, rk.name)
    if lraw is None or rraw is None:
        return None
    sp = provider_for(catalog)
    est_r = cbo.estimate(j.right, sp)
    if est_r.rows < _shuffle_min_build():
        return None            # small build: replicate it, keep rr scans
    # runtime-filter bias: a build whose key range is much narrower than
    # the probe's already prunes most probe rows shard-locally through
    # the runtime filter — broadcast keeps that pruning movement-free
    est_l = cbo.estimate(j.left, sp)
    br = est_r.cols.get(rk.name)
    pr = est_l.cols.get(lk.name)
    if br and pr and None not in (br[1], br[2], pr[1], pr[2]):
        bw, pw = br[2] - br[1], pr[2] - pr[1]
        if pw > 0 and bw < pw / 4:
            return None
    mode = "local" if (_co_partitioned(catalog, lscan.table, lraw,
                                       n_shards)
                       and _co_partitioned(catalog, rscan.table, rraw,
                                           n_shards)) else "shuffle"
    return mode, lraw, rpath, rraw


def _plan_exchanges(split, catalog, n_shards: int) -> _XPlan:
    """Classify every exchange in the fragment: each spine join gets
    broadcast/shuffle/local; the probe scan gets rr or hash routing."""
    modes: dict = {}
    if split.kind == "join":
        j = split.split
        ch = _shuffle_choice(j, catalog, n_shards)
        if ch is None:
            jx = _JoinX((), j, "broadcast")
            probe_mode, probe_col = "rr", None
        else:
            mode, lraw, rpath, rraw = ch
            jx = _JoinX((), j, mode, lcol=lraw, rpath=rpath, rcol=rraw)
            probe_mode, probe_col = "hash", lraw
        modes[id(j)] = jx.mode
        lscan = _node_at(j.left, split.scan_path)
        modes[id(lscan)] = "local" if jx.mode in ("broadcast", "local") \
            else "shuffle"
        return _XPlan([jx], probe_mode, probe_col, modes)
    root = split.split.child
    joins, scan = _spine_joins(root, split.scan_path)
    xj: List[_JoinX] = []
    probe_mode, probe_col = "rr", None
    for i, (prefix, j) in enumerate(joins):
        mode, lraw, rpath, rraw = "broadcast", None, None, None
        if i == len(joins) - 1:
            ch = _shuffle_choice(j, catalog, n_shards)
            if ch is not None:
                mode, lraw, rpath, rraw = ch
                probe_mode, probe_col = "hash", lraw
        xj.append(_JoinX(prefix, j, mode, lcol=lraw, rpath=rpath,
                         rcol=rraw))
        modes[id(j)] = mode
    if probe_mode == "rr":
        modes[id(scan)] = "local"
    else:
        modes[id(scan)] = "local" if xj[-1].mode == "local" else "shuffle"
    return _XPlan(xj, probe_mode, probe_col, modes)


# ------------------------------------------------------- materialization

def _materialize(op, schema) -> PL.Materialized:
    from matrixone_tpu.parallel import fragments as FR
    arrays, valid, n = FR._collect_arrays(op, schema)
    if n == 0:
        arrays = {nm: ([] if d.is_varlen else np.zeros(0, d.np_dtype))
                  for nm, d in schema}
        valid = {nm: np.zeros(0, np.bool_) for nm, _ in schema}
    return PL.Materialized(arrays, valid, schema)


def _mat_nbytes(mat: PL.Materialized) -> int:
    total = 0
    for nm, _d in mat.schema:
        a = mat.arrays[nm]
        if isinstance(a, np.ndarray):
            total += a.nbytes
        else:
            total += len(a) + sum(len(s) for s in a if s is not None)
        v = mat.validity.get(nm)
        if isinstance(v, np.ndarray):
            total += v.nbytes
    return total


def _concat_materialized(parts, vparts, n_total, schema) -> PL.Materialized:
    if not n_total:
        arrays = {nm: ([] if d.is_varlen else np.zeros(0, d.np_dtype))
                  for nm, d in schema}
        valid = {nm: np.zeros(0, np.bool_) for nm, _ in schema}
        return PL.Materialized(arrays, valid, schema)
    arrays, valid = {}, {}
    for nm, d in schema:
        if d.is_varlen:
            merged: list = []
            for p in parts:
                merged.extend(p[nm])
            arrays[nm] = merged
        else:
            arrays[nm] = np.concatenate([p[nm] for p in parts])
        valid[nm] = np.concatenate([v[nm] for v in vparts])
    return PL.Materialized(arrays, valid, schema)


def _ex_to_materialized(ex, schema) -> PL.Materialized:
    """Finalized merge ExecBatch -> host Materialized (varlen columns
    carried as codes + their dictionary, like the peer coordinator)."""
    pres = np.asarray(jax.device_get(ex.mask)).astype(bool)
    arrays, valid, dicts = {}, {}, {}
    for name, dtype in schema:
        col = ex.batch.columns[name]
        data = np.asarray(jax.device_get(col.data))[pres]
        vm = np.asarray(jax.device_get(col.validity))[pres]
        if dtype.is_varlen:
            d = ex.dicts.get(name)
            if d is None:
                raise ShardDegrade(
                    f"varlen column {name!r} finalized without a "
                    f"dictionary")
            arrays[name] = np.clip(data.astype(np.int64), 0,
                                   max(len(d) - 1, 0)).astype(np.int32)
            dicts[name] = list(d)
        else:
            arrays[name] = data
        valid[name] = vm
    return PL.Materialized(arrays, valid, schema, dicts=dicts)


# ------------------------------------------------------------- execution

def _broadcast_builds(xp: _XPlan, ctx, n_shards: int) -> dict:
    """Materialize every broadcast join's build side ONCE; the shared
    Materialized node substitutes into all shard plans (bytes counted
    once per non-owning shard)."""
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.vm.compile import compile_plan
    out = {}
    for jx in xp.joins:
        if jx.mode != "broadcast":
            continue
        with motrace.span("shard.broadcast"):
            op = compile_plan(jx.node.right, ctx)
            mat = _materialize(op, jx.node.right.schema)
        M.exchange_broadcast_bytes.inc(_mat_nbytes(mat) * (n_shards - 1))
        out[jx.prefix] = mat
    return out


def _apply_exchanges(root, xp: _XPlan, bc: dict, s: int, n_shards: int,
                     scan_path):
    for jx in xp.joins:
        j = _node_at(root, jx.prefix)
        if jx.mode == "broadcast":
            j.right = bc[jx.prefix]
        else:
            rscan = _node_at(j.right, jx.rpath)
            rscan.hash_shard = (jx.rcol, s, n_shards)
    sc = _node_at(root, scan_path)
    if xp.probe_mode == "hash":
        sc.hash_shard = (xp.probe_col, s, n_shards)
    else:
        sc.shard = (s, n_shards)


def _exec_agg(split, xp, catalog, ctx, n_shards: int):
    from matrixone_tpu.sql.serde import plan_from_json, plan_to_json
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.vm.compile import compile_plan
    from matrixone_tpu.vm.operators import AggOp
    agg = split.split
    child_json = plan_to_json(agg.child)
    bc = _broadcast_builds(xp, ctx, n_shards)
    psig = _partition_sig(catalog, split.scan_table)
    devs = jax.devices()[:n_shards]
    parts = []
    for s in range(n_shards):
        plan_s = plan_from_json(child_json)
        _apply_exchanges(plan_s, xp, bc, s, n_shards, split.scan_path)
        with jax.default_device(devs[s]), \
                motrace.span("shard.partial", shard=s):
            child_op = compile_plan(plan_s, ctx)
            helper = AggOp(PL.Aggregate(plan_s, agg.group_keys, agg.aggs,
                                        agg.schema), child_op)
            if agg.group_keys:
                parts.append(helper.partial_state())
            else:
                parts.append(helper.partial_scalar_state())
    merger = AggOp(PL.Aggregate(agg.child, agg.group_keys, agg.aggs,
                                agg.schema), None)
    if not agg.group_keys:
        tracker = _merge_trackers([p[1] for p in parts], agg.aggs)
        merged = [None] * len(agg.aggs)
        with motrace.span("shard.merge", kind="scalar"):
            for states, _tr in parts:
                for j, a in enumerate(agg.aggs):
                    if states[j] is None:
                        continue
                    merged[j] = states[j] if merged[j] is None else \
                        _scalar_combine(a, merged[j], states[j])
            ex = merger._scalar_result(merged, tracker)
        M.exchange_partial_merge.inc(1, kind="scalar")
        return _ex_to_materialized(ex, agg.schema)
    key_dicts = _merge_key_dicts([p[2] for p in parts],
                                 len(agg.group_keys))
    tracker = _merge_trackers([p[3] for p in parts], agg.aggs)
    denses = [p[1] for p in parts if p[0] == "dense"]
    states = [p[1] for p in parts if p[0] == "general"]
    if denses and not states \
            and len({d["sizes"] for d in denses}) == 1 and len(denses) > 1:
        with motrace.span("shard.merge", kind="dense"):
            state = _dense_merge(merger, denses, psig)
        mkind = "dense"
    else:
        states = states + [merger._dense_to_state(d) for d in denses]
        if not states:
            state = merger._empty_state()
            mkind = "empty"
        elif len(states) == 1:
            state = states[0]
            mkind = "single"
        else:
            with motrace.span("shard.merge", kind="general"):
                state = _general_merge(states, agg.aggs, psig)
            mkind = "general"
    M.exchange_partial_merge.inc(1, kind=mkind)
    merger._agg_tracker = tracker
    ex = merger._finalize(state, key_dicts)
    return _ex_to_materialized(ex, agg.schema)


def _exec_topk(split, xp, catalog, ctx, n_shards: int):
    from matrixone_tpu.parallel import fragments as FR
    from matrixone_tpu.sql.serde import plan_from_json, plan_to_json
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.vm.compile import compile_plan
    tk = split.split
    tk_json = plan_to_json(tk)
    bc = _broadcast_builds(xp, ctx, n_shards)
    devs = jax.devices()[:n_shards]
    parts, vparts, n_total = [], [], 0
    for s in range(n_shards):
        loc = plan_from_json(tk_json)
        loc = dataclasses.replace(loc, k=tk.k + tk.offset, offset=0)
        _apply_exchanges(loc.child, xp, bc, s, n_shards, split.scan_path)
        with jax.default_device(devs[s]), \
                motrace.span("shard.partial", shard=s):
            op = compile_plan(loc, ctx)
            arrays, valid, n = FR._collect_arrays(op, tk.schema)
        if n:
            parts.append(arrays)
            vparts.append(valid)
            n_total += n
    mat = _concat_materialized(parts, vparts, n_total, tk.schema)
    M.exchange_partial_merge.inc(1, kind="topk")
    # the ORIGINAL TopK re-runs over the union: every global top-k row
    # is inside its shard's local top-(k+offset)
    return dataclasses.replace(tk, child=mat)


def _exec_join(split, xp, catalog, ctx, n_shards: int):
    from matrixone_tpu.parallel import fragments as FR
    from matrixone_tpu.sql.serde import plan_from_json, plan_to_json
    from matrixone_tpu.utils import metrics as M
    from matrixone_tpu.vm.compile import compile_plan
    j = split.split
    jx = xp.joins[0]
    j_json = plan_to_json(j)
    bc = _broadcast_builds(xp, ctx, n_shards)
    devs = jax.devices()[:n_shards]
    parts, vparts, n_total = [], [], 0
    for s in range(n_shards):
        loc = plan_from_json(j_json)
        lscan = _node_at(loc.left, split.scan_path)
        if jx.mode == "broadcast":
            loc.right = bc[jx.prefix]
            lscan.shard = (s, n_shards)
        else:
            lscan.hash_shard = (jx.lcol, s, n_shards)
            rscan = _node_at(loc.right, jx.rpath)
            rscan.hash_shard = (jx.rcol, s, n_shards)
        with jax.default_device(devs[s]), \
                motrace.span("shard.partial", shard=s):
            op = compile_plan(loc, ctx)
            arrays, valid, n = FR._collect_arrays(op, j.schema)
        if n:
            parts.append(arrays)
            vparts.append(valid)
            n_total += n
    M.exchange_partial_merge.inc(1, kind="join")
    return _concat_materialized(parts, vparts, n_total, j.schema)


# -------------------------------------------------------------- entrypoint

def try_shard(node, catalog, ctx, n_shards: int,
              min_rows: int = 100_000):
    """Execute `node`'s distributable fragment across n_shards device
    shards and return the rewritten plan (uppers over a Materialized
    merge result), or None to run single-device.  The degrade ladder:
    mesh absent, small inputs, non-shardable operators, or any
    shard-side failure -> None (never a wrong answer)."""
    from matrixone_tpu.parallel import fragments as FR
    if n_shards < 2 or len(jax.devices()) < n_shards:
        return None
    split = FR.plan_split(node, catalog, min_rows=min_rows)
    if split is None:
        return None
    try:
        xp = _plan_exchanges(split, catalog, n_shards)
        with motrace.span("shard.exec", kind=split.kind,
                          shards=n_shards):
            if split.kind == "agg":
                leaf = _exec_agg(split, xp, catalog, ctx, n_shards)
            elif split.kind == "topk":
                leaf = _exec_topk(split, xp, catalog, ctx, n_shards)
            else:
                leaf = _exec_join(split, xp, catalog, ctx, n_shards)
    except Exception as e:      # noqa: BLE001 — degrade, never fail
        from matrixone_tpu.utils import metrics as M
        M.exchange_degrade.inc()
        print(f"[shard] degrading to single-device execution: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return None
    return FR._rebuild_uppers(split.uppers, leaf)


def explain_exchanges(node, catalog, n_shards: int,
                      min_rows: int = 100_000) -> dict:
    """id(plan node) -> exchange mode for EXPLAIN annotation; empty when
    the plan would not shard."""
    from matrixone_tpu.parallel import fragments as FR
    if n_shards < 2 or len(jax.devices()) < n_shards:
        return {}
    split = FR.plan_split(node, catalog, min_rows=min_rows)
    if split is None:
        return {}
    try:
        return _plan_exchanges(split, catalog, n_shards).modes_by_id
    except Exception:           # noqa: BLE001
        return {}
