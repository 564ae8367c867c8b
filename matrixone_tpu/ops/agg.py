"""Group-by aggregation as sort/segment kernels — no hash tables.

TPU-native replacement for the reference's hash-table group-by
(`pkg/sql/colexec/group` + `pkg/container/hashtable` + `aggexec`). Pointer-
chasing hash maps don't map to a systolic/vector machine; instead:

    row hash (ops.hash) -> argsort -> boundary detect -> cumsum group ids
    -> jax.ops.segment_{sum,min,max} scatter reductions

which is sorts + scans + scatters, all native XLA ops. `max_groups` is a
static upper bound (compile-time); exceeding it is detected and the caller
re-runs with the next bucket — the analogue of the reference growing its
hash table, quantized to keep the jit cache small.

Sums over integers/decimals are exact (int64): bit-identical to the CPU
oracle regardless of reduction order — this is why Q1's money columns are
DECIMAL(scaled int64), matching the reference's decimal aggregators
(`colexec/aggexec/sum.go`).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from matrixone_tpu.ops import hash as mohash

import numpy as _np

_NULL_GROUP_SENTINEL = _np.uint64(0xFFFFFFFFFFFFFFFF)


class GroupIds(NamedTuple):
    gids: jnp.ndarray        # int32 [n]: group id per row (garbage for padding rows)
    num_groups: jnp.ndarray  # int32 scalar: number of distinct groups
    rep_rows: jnp.ndarray    # int32 [max_groups]: a representative row per group


def group_ids(key_columns: Sequence[jnp.ndarray],
              key_validities: Sequence[Optional[jnp.ndarray]],
              row_mask: jnp.ndarray,
              max_groups: int) -> GroupIds:
    """Assign dense group ids to rows by their key tuple.

    Grouping is by 64-bit row hash: with splitmix64-quality mixing the
    collision probability at 1M distinct keys is ~2^-44 per pair; the BVT
    harness cross-checks results against the numpy oracle. Padding rows
    (row_mask False) sort last and take no group id.
    """
    h = mohash.hash_columns(key_columns, key_validities)
    h = jnp.where(row_mask, h, _NULL_GROUP_SENTINEL)
    order = jnp.argsort(h).astype(jnp.int32)     # padding rows last
    sorted_h = h[order]
    sorted_mask = row_mask[order]
    first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                             sorted_h[1:] != sorted_h[:-1]])
    first = first & sorted_mask
    gid_sorted = jnp.cumsum(first.astype(jnp.int32)) - 1
    num_groups = jnp.where(jnp.any(sorted_mask), jnp.max(
        jnp.where(sorted_mask, gid_sorted, -1)) + 1, 0)
    # scatter group ids back to row order
    n = h.shape[0]
    gids = jnp.zeros((n,), jnp.int32).at[order].set(gid_sorted)
    # representative row for each group = first row (in sorted order)
    rep_target = jnp.where(first, gid_sorted, max_groups)
    rep_rows = jnp.zeros((max_groups + 1,), jnp.int32).at[rep_target].set(order)[:max_groups]
    return GroupIds(gids=gids, num_groups=num_groups.astype(jnp.int32),
                    rep_rows=rep_rows)


def _masked(values: jnp.ndarray, mask: jnp.ndarray, fill) -> jnp.ndarray:
    return jnp.where(mask, values, jnp.asarray(fill, values.dtype))


@partial(jax.jit, static_argnames=("max_groups",))
def seg_sum(values, gids, mask, max_groups: int):
    """Masked segment sum."""
    return jax.ops.segment_sum(_masked(values, mask, 0), gids,
                               num_segments=max_groups)


@partial(jax.jit, static_argnames=("max_groups",))
def seg_count(gids, mask, max_groups: int):
    return jax.ops.segment_sum(mask.astype(jnp.int64), gids,
                               num_segments=max_groups)


def _reduce_fill(dtype, for_min: bool):
    """Identity element for min/max over dtype (BOOL included)."""
    if jnp.issubdtype(dtype, jnp.bool_):
        return True if for_min else False
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if for_min else -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max if for_min else info.min


@partial(jax.jit, static_argnames=("max_groups",))
def seg_min(values, gids, mask, max_groups: int):
    is_bool = jnp.issubdtype(values.dtype, jnp.bool_)
    v = _masked(values.astype(jnp.int32) if is_bool else values, mask,
                _reduce_fill(values.dtype, True))
    out = jax.ops.segment_min(v, gids, num_segments=max_groups)
    return out.astype(jnp.bool_) if is_bool else out


@partial(jax.jit, static_argnames=("max_groups",))
def seg_max(values, gids, mask, max_groups: int):
    is_bool = jnp.issubdtype(values.dtype, jnp.bool_)
    v = _masked(values.astype(jnp.int32) if is_bool else values, mask,
                _reduce_fill(values.dtype, False))
    out = jax.ops.segment_max(v, gids, num_segments=max_groups)
    return out.astype(jnp.bool_) if is_bool else out


def dense_slot_strides(sizes: Sequence[int], null_slots: bool = True
                       ) -> Tuple[Tuple[int, ...], int]:
    """Row-major strides over the dense key space. With null_slots each
    key contributes (size + 1) slots — the extra slot is its NULL group;
    without, exactly `size` slots (a chunk proven all-valid)."""
    strides = []
    acc = 1
    for s in reversed(sizes):
        strides.append(acc)
        acc *= s + (1 if null_slots else 0)
    return tuple(reversed(strides)), acc


@partial(jax.jit, static_argnames=("sizes", "with_null"))
def dense_lane_partials(codes, valids, row_mask, int_vals, int_masks,
                        float_vals, float_masks, *, sizes, with_null):
    """Small-key grouped partials without hash or sort.

    When every group key is a dictionary code (or bool) the group id is
    a mixed-radix digit expansion over the key space — no 64-bit hash,
    no argsort over the batch. Each (deduplicated) partial lane then
    reduces per group as a masked sum; XLA's multi-output fusion turns
    the G x L reduction family over shared inputs into a handful of
    passes, which profiles ~4x faster than a segment_sum scatter per
    field on CPU and avoids the scatter path on TPU entirely.

    Lanes: parallel (value, mask) tuples per dtype class. value None
    means "count the mask"; mask None means "row_mask only". Returns
    (int64 lanes [Li, G], float64 lanes [Lf, G], rows [G]) with G the
    compact (with_null=False) or NULL-slotted key space.
    """
    strides, G = dense_slot_strides(sizes, null_slots=with_null)
    n = row_mask.shape[0]
    gid = jnp.zeros((n,), jnp.int32)
    for c, v, s, st in zip(codes, valids, sizes, strides):
        slot = jnp.clip(c.astype(jnp.int32), 0, s - 1)
        if with_null:
            slot = jnp.where(v, slot, jnp.asarray(s, jnp.int32))
        gid = gid + slot * jnp.asarray(st, jnp.int32)

    def lane_sums(vals, masks, dtype):
        outs = []
        for g in range(G):
            sel = (gid == g) & row_mask
            row = []
            for v, m in zip(vals, masks):
                sm = sel if m is None else sel & m
                row.append(jnp.sum(sm) if v is None
                           else jnp.sum(jnp.where(sm, v.astype(dtype),
                                                  jnp.asarray(0, dtype))))
            outs.append(row)
        return jnp.asarray(outs, dtype).T           # (L, G)

    ints = lane_sums(int_vals, int_masks, jnp.int64)
    floats = lane_sums(float_vals, float_masks, jnp.float64)
    rows = jnp.asarray([jnp.sum((gid == g) & row_mask)
                        for g in range(G)], jnp.int64)
    return ints, floats, rows


def gather_keys(key_columns: Sequence[jnp.ndarray],
                key_validities: Sequence[Optional[jnp.ndarray]],
                rep_rows: jnp.ndarray) -> Tuple[list, list]:
    """Materialize one key value per group from representative rows."""
    out_vals, out_vals_valid = [], []
    for data, valid in zip(key_columns, key_validities):
        out_vals.append(data[rep_rows])
        if valid is None:
            out_vals_valid.append(jnp.ones(rep_rows.shape, jnp.bool_))
        else:
            out_vals_valid.append(valid[rep_rows])
    return out_vals, out_vals_valid


# scalar (no GROUP BY) aggregates ------------------------------------------

def scalar_sum(values, mask):
    return jnp.sum(_masked(values, mask, 0))


def scalar_count(mask):
    return jnp.sum(mask.astype(jnp.int64))


def scalar_min(values, mask):
    is_bool = jnp.issubdtype(values.dtype, jnp.bool_)
    v = values.astype(jnp.int32) if is_bool else values
    out = jnp.min(_masked(v, mask, _reduce_fill(values.dtype, True)))
    return out.astype(jnp.bool_) if is_bool else out


def scalar_max(values, mask):
    is_bool = jnp.issubdtype(values.dtype, jnp.bool_)
    v = values.astype(jnp.int32) if is_bool else values
    out = jnp.max(_masked(v, mask, _reduce_fill(values.dtype, False)))
    return out.astype(jnp.bool_) if is_bool else out
