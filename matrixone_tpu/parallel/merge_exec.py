"""Cross-shard partial-aggregate merge kernels (mergegroup).

The device-shard executor (`parallel/dist_query.py`) runs one fused
fragment per shard and collects partial group tables; the kernels here
fold those partials in ONE traced dispatch — the reference's
`colexec/mergegroup` stage:

  * `_general_merge` — sorted-hash group tables of any key shape:
    concatenate every shard's rep rows inside the trace, re-group once
    (`ops.agg.group_ids`), segment-reduce each partial field.  One
    `jax.jit` program.
  * `_dense_merge`   — same-key-space dense accumulators: elementwise
    `psum` over the mesh, one `shard_map` program.
  * `_scalar_combine`— scalar (ungrouped) aggregate algebra.

Compiled merge programs live in `_MERGE_CACHE`, keyed by (kind,
n_shards, per-shard state layout, mesh axis, partition spec) and
audited per hit as the mokey site `parallel/merge_exec.py:merge` —
every static shape a program bakes (`mg_out`, field layout) is a
runtime-audited dep, so a key collision is caught at the colliding hit.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from matrixone_tpu.ops import agg as A
from matrixone_tpu.parallel.mesh import make_mesh
from matrixone_tpu.utils import keys as keyaudit

SITE_MERGE = "parallel/merge_exec.py:merge"

#: compiled cross-shard merge programs, keyed by (kind, n_shards,
#: per-shard state layout, mesh axis, partition spec) — the sharded-
#: fragment compile-cache site audited by mokey
_MERGE_CACHE: dict = {}

#: test hook: merge-program invocations (the one-dispatch contract)
_MERGE_CALLS = {"count": 0}


class ShardDegrade(RuntimeError):
    """A shard-side condition the merge cannot absorb (divergent
    dictionaries, unmergeable partial fields): the caller re-runs the
    whole query single-device — degrade, never a wrong answer."""


def _merge_program(key, build, deps_fn):
    fn = _MERGE_CACHE.get(key)
    if fn is None:
        fn = build()
        _MERGE_CACHE[key] = fn
    if keyaudit.armed():
        keyaudit.audit(SITE_MERGE, key, deps_fn())
    return fn


def _seg_op(field: str):
    if field in ("sum", "count", "sumsq"):
        return A.seg_sum
    if field == "min":
        return A.seg_min
    if field == "max":
        return A.seg_max
    raise ShardDegrade(f"unmergeable partial field {field!r}")


def _general_merge(states, aggs, psig):
    """mergegroup over the shards' general group tables as ONE jitted
    program: concatenate every shard's rep rows (inside the trace),
    re-group once, segment-reduce each partial field."""
    n_sh = len(states)
    nkeys = len(states[0]["keys"])
    mgs = tuple(int(st["present"].shape[0]) for st in states)
    mg_out = 1 << max(sum(mgs) - 1, 1).bit_length()
    kdts = tuple(str(states[0]["keys"][i].dtype) for i in range(nkeys))
    fl = tuple(tuple(sorted(states[0]["partials"][j].keys()))
               for j in range(len(aggs)))
    fdts = tuple(tuple(str(states[0]["partials"][j][f].dtype)
                       for f in fs) for j, fs in enumerate(fl))
    for f in (f for fs in fl for f in fs):
        _seg_op(f)              # reject unmergeable layouts up front
    key = ("general", n_sh, mgs, mg_out, kdts, fl, fdts, "shard", psig)

    def build():
        def merge_general(keys_ss, kvalid_ss, present_s, fields_ss):
            kd = [jnp.concatenate(ks) for ks in keys_ss]
            kv = [jnp.concatenate(vs) for vs in kvalid_ss]
            mask = jnp.concatenate(present_s)
            gi = A.group_ids(kd, kv, mask, mg_out)
            rep_k, rep_v = A.gather_keys(kd, kv, gi.rep_rows)
            present = jnp.arange(mg_out, dtype=jnp.int32) < gi.num_groups
            outs = []
            for fs, per_field in zip(fl, fields_ss):
                outs.append(tuple(
                    _seg_op(f)(jnp.concatenate(arrs), gi.gids, mask,
                               mg_out)
                    for f, arrs in zip(fs, per_field)))
            return (tuple(rep_k), tuple(rep_v), present, tuple(outs),
                    gi.num_groups)
        return jax.jit(merge_general)

    def deps():
        return {"mesh_shape": (n_sh,), "shard_axis": "shard",
                "partition_spec": psig, "mg_out": mg_out, "fl": fl,
                "state_layout": (mgs, kdts, fl, fdts)}

    fn = _merge_program(key, build, deps)
    args = (tuple(tuple(st["keys"][i] for st in states)
                  for i in range(nkeys)),
            tuple(tuple(st["kvalid"][i] for st in states)
                  for i in range(nkeys)),
            tuple(st["present"] for st in states),
            tuple(tuple(tuple(st["partials"][j][f] for st in states)
                        for f in fl[j]) for j in range(len(aggs))))
    _MERGE_CALLS["count"] += 1
    rep_k, rep_v, present, outs, ng = fn(*args)
    partials = [{f: o for f, o in zip(fl[j], outs[j])}
                for j in range(len(aggs))]
    return {"keys": list(rep_k), "kvalid": list(rep_v),
            "present": present, "partials": partials, "n": ng}


def _dense_merge(helper, denses, psig):
    """Merge same-shape dense accumulators with a psum over the mesh —
    the mview delta partial-aggregate merge kernel family: elementwise
    adds of (G,)-sized partials, one shard_map program."""
    n_sh = len(denses)
    sizes = denses[0]["sizes"]
    aggs = helper.node.aggs
    layout = [("rows", None)]
    for j, a in enumerate(aggs):
        for _c, f in type(helper)._dense_fields(a):
            layout.append((f, j))

    def flat(d):
        out = [d["rows"]]
        for f, j in layout[1:]:
            out.append(d["partials"][j][f])
        return out

    flats = [flat(d) for d in denses]
    dts = tuple(str(a.dtype) for a in flats[0])
    g = int(flats[0][0].shape[0])
    key = ("dense", n_sh, g, dts, "shard", psig)

    def build():
        mesh = make_mesh(n_sh)

        def merge_dense(*cols):
            return tuple(jax.lax.psum(c[0], "shard") for c in cols)

        return jax.shard_map(
            merge_dense, mesh=mesh,
            in_specs=tuple([P("shard")] * len(dts)),
            out_specs=tuple([P()] * len(dts)))

    def deps():
        return {"mesh_shape": (n_sh,), "shard_axis": "shard",
                "partition_spec": psig,
                "state_layout": (g, dts)}

    fn = _merge_program(key, build, deps)
    stacked = [jnp.stack([fl[i] for fl in flats])
               for i in range(len(dts))]
    _MERGE_CALLS["count"] += 1
    merged = fn(*stacked)
    out = {"sizes": sizes, "rows": merged[0],
           "partials": [dict(p) for p in denses[0]["partials"]]}
    for (f, j), arr in zip(layout[1:], merged[1:]):
        out["partials"][j][f] = arr
    return helper._dense_to_state(out)


def _merge_key_dicts(kds, nkeys: int):
    out: List[Optional[list]] = [None] * nkeys
    for i in range(nkeys):
        for kd in kds:
            d = kd[i]
            if d is None:
                continue
            cur = out[i]
            if cur is None or (d is not cur and len(d) > len(cur)):
                if cur is not None and list(d[:len(cur)]) != list(cur):
                    raise ShardDegrade(
                        "divergent group-key dictionaries across shards")
                out[i] = d
            elif d is not cur and list(d) != list(cur[:len(d)]):
                raise ShardDegrade(
                    "divergent group-key dictionaries across shards")
    return out


def _merge_trackers(trackers, aggs):
    """min/max-over-strings dictionaries must AGREE across shards:
    collation ranks are only comparable against one frozen dict."""
    from matrixone_tpu.vm.operators import _AggDictTracker
    out = _AggDictTracker(aggs)
    for tr in trackers:
        for name, d in tr.dicts.items():
            cur = out.dicts.get(name)
            if cur is None:
                out.dicts[name] = d
                out._sizes[name] = len(d)
            elif d is not cur and list(d) != list(cur):
                raise ShardDegrade(
                    "divergent min/max string dictionaries across shards")
    return out


def _scalar_combine(a, s1, s2):
    if a.func == "count" and a.arg is None:
        return s1 + s2
    if a.func == "count":
        return s1 + s2
    if a.func in ("sum", "avg"):
        return (s1[0] + s2[0], s1[1] + s2[1])
    if a.func == "min":
        return (jnp.minimum(s1[0], s2[0]), s1[1] + s2[1])
    if a.func == "max":
        return (jnp.maximum(s1[0], s2[0]), s1[1] + s2[1])
    # stddev/variance family: (sum, sumsq, count)
    return (s1[0] + s2[0], s1[1] + s2[1], s1[2] + s2[2])
