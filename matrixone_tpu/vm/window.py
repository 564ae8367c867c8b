"""Window function operator (reference: pkg/sql/colexec/window).

TPU formulation: materialize, assign partition ids (ops.agg.group_ids),
sort rows by (partition, order keys), then every window function is a
segmented scan over the sorted order:

  row_number  = position since partition start
  rank        = position of the first peer + 1
  dense_rank  = per-partition count of peer-group starts
  agg + ORDER = cumulative aggregate up to the LAST PEER of the row
                (SQL default frame RANGE UNBOUNDED PRECEDING..CURRENT ROW)
  agg alone   = whole-partition aggregate broadcast

Everything is argsort + (value, segment) associative scans + gathers —
native XLA; the reference walks per-partition accumulators in Go.
"""

from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container import dtypes as dt
from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
from matrixone_tpu.ops import agg as A, hash as H, sort as msort
from matrixone_tpu.sql import plan as P
from matrixone_tpu.vm.exprs import EvalError, ExecBatch, eval_expr
from matrixone_tpu.vm.operators import (Operator, _broadcast_full,
                                        _concat_batches, _sort_key_col)

_BIG = np.int64(1) << 62


def _seg_scan(vals: jnp.ndarray, seg: jnp.ndarray, combine):
    """Inclusive scan of `combine` over vals, restarting at each new value
    of the (nondecreasing) segment id."""

    def fn(a, b):
        va, sa = a
        vb, sb = b
        take_b = sb > sa
        return jnp.where(take_b, vb, combine(va, vb)), jnp.maximum(sa, sb)

    out, _ = jax.lax.associative_scan(fn, (vals, seg))
    return out


def _suffix_min(vals: jnp.ndarray) -> jnp.ndarray:
    """suffix_min[i] = min(vals[i:])."""
    return jnp.flip(jax.lax.associative_scan(jnp.minimum, jnp.flip(vals)))


class WindowOp(Operator):
    step_span = "op.window"

    def __init__(self, node: P.Window, child: Operator,
                 max_partitions: int = 65536):
        self.node = node
        self.child = child
        self.schema = node.schema
        self.max_partitions = max_partitions

    def execute(self) -> Iterator[ExecBatch]:
        batches = list(self.child.execute())
        if not batches:
            return
        ex = _concat_batches(batches, self.node.child.schema)
        out_cols, out_dicts = self.compute_columns(ex)
        db = DeviceBatch(columns=out_cols, n_rows=ex.batch.n_rows)
        yield ExecBatch(batch=db, dicts=out_dicts, mask=ex.mask)

    def compute_columns(self, ex: ExecBatch):
        """Evaluate every window entry over one materialized batch ->
        (output columns, output dicts).  Pure device math (argsort +
        segmented scans + gathers): the fused window fragment
        (vm/fusion_window.py) traces this very method, so the fused and
        per-operator paths share one kernel body."""
        from matrixone_tpu.vm.operators import _expr_dict
        out_cols = dict(ex.batch.columns)
        out_dicts = dict(ex.dicts)
        # entries sharing one OVER spec share the sort/segment machinery
        spec_cache = {}
        for entry in self.node.entries:
            (fn, arg, part, okeys, odescs, out_name) = entry[:6]
            extra = entry[6] if len(entry) > 6 else {}
            from matrixone_tpu.sql.serde import expr_to_json
            key = (tuple(repr(expr_to_json(p)) for p in part),
                   tuple(repr(expr_to_json(k)) for k in okeys),
                   tuple(odescs))
            if key not in spec_cache:
                spec_cache[key] = self._spec(part, okeys, odescs, ex)
            out_cols[out_name] = self._compute(fn, arg, spec_cache[key],
                                               ex, extra)
            # value functions over varchar carry their source dictionary
            if arg is not None and arg.dtype.is_varlen:
                d = _expr_dict(arg, ex)
                if d is not None:
                    out_dicts[out_name] = d
        return out_cols, out_dicts

    # ------------------------------------------------------------ kernels
    def _spec(self, part, okeys, odescs, ex):
        """Sort + segment machinery shared by every fn over one OVER spec."""
        n = ex.padded_len
        mask = ex.mask
        if part:
            cols = [_broadcast_full(eval_expr(p, ex), n) for p in part]
            gi = A.group_ids([c.data for c in cols],
                             [c.validity for c in cols], mask,
                             self.max_partitions)
            pid = gi.gids
        else:
            pid = jnp.zeros((n,), jnp.int32)

        ocols = [_sort_key_col(k, ex) for k in okeys]
        order = msort.sort_indices(
            [pid] + [c.data for c in ocols],
            [None] + [c.validity for c in ocols],
            [False] + list(odescs), mask)
        pid_s = pid[order]
        mask_s = mask[order]
        idx = jnp.arange(n, dtype=jnp.int64)
        first = jnp.concatenate([jnp.ones((1,), jnp.bool_),
                                 (pid_s[1:] != pid_s[:-1])
                                 | (mask_s[1:] != mask_s[:-1])])
        seg = jnp.cumsum(first.astype(jnp.int64))          # partition seq no

        # position within partition (0-based): idx - partition start
        start_idx = _seg_scan(jnp.where(first, idx, 0), seg, jnp.maximum)
        pos = idx - start_idx

        if ocols:
            okey_hash = H.hash_columns(
                [c.data[order] for c in ocols],
                [None if c.validity is None else c.validity[order]
                 for c in ocols])
            new_peer = jnp.concatenate(
                [jnp.ones((1,), jnp.bool_),
                 (okey_hash[1:] != okey_hash[:-1])]) | first
        else:
            new_peer = first

        # last row index of each peer group: next peer start - 1 (or the
        # partition/array end)
        nb = jnp.concatenate([jnp.where(new_peer, idx, _BIG)[1:],
                              jnp.asarray([_BIG])])
        next_peer_start = _suffix_min(nb)
        part_nb = jnp.concatenate([jnp.where(first, idx, _BIG)[1:],
                                   jnp.asarray([_BIG])])
        next_part_start = _suffix_min(part_nb)
        peer_end = jnp.minimum(jnp.where(next_peer_start == _BIG,
                                         n - 1, next_peer_start - 1),
                               jnp.where(next_part_start == _BIG,
                                         n - 1, next_part_start - 1))
        part_end = jnp.where(next_part_start == _BIG, n - 1,
                             next_part_start - 1)
        return {"order": order, "seg": seg, "first": first, "pos": pos,
                "new_peer": new_peer, "peer_end": peer_end,
                "part_end": part_end, "mask_s": mask_s,
                "start_idx": start_idx, "has_order": bool(ocols)}

    def _compute(self, fn, arg, spec, ex, extra=None) -> DeviceColumn:
        extra = extra or {}
        n = ex.padded_len
        order = spec["order"]
        seg = spec["seg"]
        pos = spec["pos"]
        new_peer = spec["new_peer"]
        mask_s = spec["mask_s"]

        if fn == "row_number":
            vals_s, out_t = pos + 1, dt.INT64
        elif fn == "rank":
            vals_s = _seg_scan(jnp.where(new_peer, pos + 1, 0), seg,
                               jnp.maximum)
            out_t = dt.INT64
        elif fn == "dense_rank":
            vals_s = _seg_scan(new_peer.astype(jnp.int64), seg, jnp.add)
            out_t = dt.INT64
        elif fn == "ntile":
            vals_s = self._ntile(extra["n"], spec)
            out_t = dt.INT64
        elif fn in ("lag", "lead", "first_value", "last_value",
                    "nth_value"):
            vals_s, valid_out, out_t = self._value_window(
                fn, arg, ex, spec, extra)
            out = jnp.zeros((n,), vals_s.dtype).at[order].set(vals_s)
            valid = jnp.zeros((n,), jnp.bool_).at[order].set(
                mask_s & valid_out)
            return DeviceColumn(out, valid, out_t)
        else:
            frame = extra.get("frame")
            if frame is not None:
                vals_s, frame_valid, out_t = self._framed_agg(
                    fn, arg, ex, spec, frame)
            else:
                take_at = spec["peer_end"] if spec["has_order"] \
                    else spec["part_end"]
                vals_s, frame_valid, out_t = self._agg_window(
                    fn, arg, ex, order, seg, mask_s, take_at)
            out = jnp.zeros((n,), vals_s.dtype).at[order].set(vals_s)
            valid = jnp.zeros((n,), jnp.bool_).at[order].set(
                mask_s & frame_valid)
            return DeviceColumn(out, valid, out_t)

        out = jnp.zeros((n,), vals_s.dtype).at[order].set(vals_s)
        valid = jnp.zeros((n,), jnp.bool_).at[order].set(mask_s)
        return DeviceColumn(out, valid, out_t)

    def _ntile(self, nt: int, spec):
        """MySQL ntile: first (count % nt) buckets get one extra row;
        when count < nt every row is its own bucket."""
        pos = spec["pos"]
        count = spec["part_end"] - spec["start_idx"] + 1
        size = count // nt
        rem = count % nt
        big_span = rem * (size + 1)
        in_big = pos < big_span
        bucket_small = jnp.where(size > 0,
                                 rem + (pos - big_span)
                                 // jnp.maximum(size, 1),
                                 pos)
        bucket = jnp.where(in_big, pos // jnp.maximum(size + 1, 1),
                           bucket_small)
        return bucket + 1

    # ---------------------------------------------------- value functions
    def _value_window(self, fn, arg, ex, spec, extra):
        n = ex.padded_len
        order = spec["order"]
        idx = jnp.arange(n, dtype=jnp.int64)
        start = spec["start_idx"]
        pend = spec["part_end"]
        col = _broadcast_full(eval_expr(arg, ex), n)
        v_s = col.data[order]
        cval_s = col.validity[order]

        if fn in ("lag", "lead"):
            off = extra.get("offset", 1)
            src = idx - off if fn == "lag" else idx + off
            in_part = (src >= start) & (src <= pend)
            srcc = jnp.clip(src, 0, n - 1)
            vals = jnp.take(v_s, srcc, axis=0)
            valid = in_part & jnp.take(cval_s, srcc)
            dflt = extra.get("default")
            if dflt is not None:
                dv = jnp.asarray(dflt.value).astype(v_s.dtype)
                vals = jnp.where(in_part, vals, dv)
                valid = valid | ~in_part
            return vals, valid, arg.dtype
        if fn == "first_value":
            src = self._frame_lo(spec, extra.get("frame"))
        elif fn == "last_value":
            src = self._frame_hi(spec, extra.get("frame"))
        else:                                  # nth_value
            src = self._frame_lo(spec, extra.get("frame")) \
                + extra["n"] - 1
        hi = self._frame_hi(spec, extra.get("frame"))
        lo = self._frame_lo(spec, extra.get("frame"))
        in_frame = (src >= lo) & (src <= hi) & (lo <= hi)
        srcc = jnp.clip(src, 0, n - 1)
        vals = jnp.take(v_s, srcc, axis=0)
        valid = in_frame & jnp.take(cval_s, srcc)
        return vals, valid, arg.dtype

    # ------------------------------------------------------------- frames
    def _frame_lo(self, spec, frame):
        idx = jnp.arange(len(spec["pos"]), dtype=jnp.int64)
        start = spec["start_idx"]
        if frame is None:
            return start                        # default: RANGE UNB..CUR
        kind, k = frame[1]
        if kind == "unbounded_preceding":
            raw = start
        elif kind == "current":
            raw = idx
        elif kind == "preceding":
            raw = idx - k
        else:                                   # following
            raw = idx + k
        return jnp.maximum(raw, start)

    def _frame_hi(self, spec, frame):
        idx = jnp.arange(len(spec["pos"]), dtype=jnp.int64)
        pend = spec["part_end"]
        if frame is None:
            return spec["peer_end"] if spec["has_order"] else pend
        kind, k = frame[2]
        if kind == "unbounded_following":
            raw = pend
        elif kind == "current":
            raw = idx
        elif kind == "following":
            raw = idx + k
        else:                                   # preceding
            raw = idx - k
        return jnp.minimum(raw, pend)

    def _framed_agg(self, fn, arg, ex, spec, frame):
        """ROWS-frame aggregate: sum/count/avg by inclusive-prefix
        difference; min/max by a sparse table (log-levels of shifted
        combines) queried per row — O(n log n), fully vectorized, no
        per-partition host loop."""
        n = ex.padded_len
        order = spec["order"]
        seg = spec["seg"]
        mask_s = spec["mask_s"]
        start = spec["start_idx"]
        lo = self._frame_lo(spec, frame)
        hi = self._frame_hi(spec, frame)
        nonempty = lo <= hi
        loc = jnp.clip(lo, 0, n - 1)
        hic = jnp.clip(hi, 0, n - 1)

        if arg is not None:
            col = _broadcast_full(eval_expr(arg, ex), n)
            v_s = col.data[order]
            valid_s = col.validity[order] & mask_s
        else:
            v_s = jnp.ones((n,), jnp.int64)
            valid_s = mask_s

        cnt_pre = _seg_scan(valid_s.astype(jnp.int64), seg, jnp.add)
        cnt = jnp.where(nonempty,
                        jnp.take(cnt_pre, hic)
                        - jnp.where(lo > start,
                                    jnp.take(cnt_pre,
                                             jnp.clip(lo - 1, 0, n - 1)),
                                    0),
                        0)
        if fn == "count":
            return cnt, jnp.ones_like(cnt, jnp.bool_), dt.INT64
        frame_valid = (cnt > 0) & nonempty
        if fn in ("sum", "avg"):
            x = jnp.where(valid_s, v_s, 0)
            csum = _seg_scan(x, seg, jnp.add)
            s = jnp.where(nonempty,
                          jnp.take(csum, hic)
                          - jnp.where(lo > start,
                                      jnp.take(csum,
                                               jnp.clip(lo - 1, 0,
                                                        n - 1)),
                                      0),
                          0)
            if fn == "avg":
                cs = s.astype(jnp.float64)
                if arg is not None and \
                        arg.dtype.oid == dt.TypeOid.DECIMAL64:
                    cs = cs / (10.0 ** arg.dtype.scale)
                return cs / jnp.maximum(cnt, 1), frame_valid, dt.FLOAT64
            out_t = (arg.dtype if arg.dtype.oid == dt.TypeOid.DECIMAL64
                     else dt.INT64 if arg.dtype.is_integer
                     else dt.FLOAT64)
            return s.astype(out_t.jnp_dtype), frame_valid, out_t
        # min / max over arbitrary in-partition ranges: sparse table
        fill = jnp.asarray(A._reduce_fill(v_s.dtype, fn == "min"),
                           v_s.dtype)
        comb = jnp.minimum if fn == "min" else jnp.maximum
        x = jnp.where(valid_s, v_s, fill)
        levels = [x]
        span = 1
        while span * 2 <= n:
            prev = levels[-1]
            shifted = jnp.concatenate(
                [prev[span:], jnp.full((span,), fill, x.dtype)])
            levels.append(comb(prev, shifted))
            span *= 2
        st = jnp.stack(levels)                  # [L, n]
        length = jnp.maximum(hi - lo + 1, 1)
        # k = floor(log2(length)), exact via comparisons
        k = jnp.zeros_like(length)
        for j in range(1, len(levels)):
            k = k + (length >= (1 << j)).astype(length.dtype)
        right = jnp.clip(hi - ((jnp.int64(1) << k) - 1), 0, n - 1)
        vals = comb(st[k, loc], st[k, right])
        return jnp.where(frame_valid, vals, fill), frame_valid, \
            (arg.dtype if arg is not None else dt.INT64)

    def _agg_window(self, fn, arg, ex, order, seg, mask_s, take_at):
        n = ex.padded_len
        if arg is not None:
            col = _broadcast_full(eval_expr(arg, ex), n)
            v_s = col.data[order]
            valid_s = col.validity[order] & mask_s
        else:                         # count(*)
            v_s = jnp.ones((n,), jnp.int64)
            valid_s = mask_s

        if fn in ("sum", "avg", "count"):
            x = valid_s.astype(jnp.int64) if fn == "count" \
                else jnp.where(valid_s, v_s, 0)
            csum = _seg_scan(x, seg, jnp.add)[take_at]
            cnt = _seg_scan(valid_s.astype(jnp.int64), seg, jnp.add)[take_at]
            if fn == "count":
                return cnt, jnp.ones_like(cnt, jnp.bool_), dt.INT64
            # an all-NULL frame yields SQL NULL, not the identity element
            frame_valid = cnt > 0
            if fn == "avg":
                cs = csum.astype(jnp.float64)
                if arg is not None and arg.dtype.oid == dt.TypeOid.DECIMAL64:
                    cs = cs / (10.0 ** arg.dtype.scale)
                return cs / jnp.maximum(cnt, 1), frame_valid, dt.FLOAT64
            out_t = (arg.dtype if arg.dtype.oid == dt.TypeOid.DECIMAL64
                     else dt.INT64 if arg.dtype.is_integer else dt.FLOAT64)
            return csum.astype(out_t.jnp_dtype), frame_valid, out_t
        if fn in ("min", "max"):
            fill = jnp.asarray(A._reduce_fill(v_s.dtype, fn == "min"),
                               v_s.dtype)
            x = jnp.where(valid_s, v_s, fill)
            comb = jnp.minimum if fn == "min" else jnp.maximum
            vals = _seg_scan(x, seg, comb)[take_at]
            cnt = _seg_scan(valid_s.astype(jnp.int64), seg, jnp.add)[take_at]
            return vals, cnt > 0, (arg.dtype if arg is not None
                                   else dt.INT64)
        raise EvalError(f"unsupported window function {fn}")
