"""The result path: a statement's batches leave the device, and the last
Sort or TopK over a small result orders it on the host (ROADMAP A2).

`to_host` fetches a batch with one `jax.device_get`: a small batch whole,
compacted by its mask in numpy; a larger one compacted on the device first.
`last_order` finds, when the operator tree is compiled, the statement's last
Sort or TopK: the operator whose output the session pulls, under at most
Projects of plain columns and Limits.  `Tail.execute` runs that operator's
input, and when it is small fetches it in one wait and orders it in numpy
(`SortOp.host_rows` / `TopKOp.host_rows`, the device's order), applying the
Projects above as a column map and the Limits as a row window, so the rows
of the result cost no device program.  A larger input is ordered on the
device, and the operators above run over that order as they are.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Union

import jax
import numpy as np

from matrixone_tpu.container.batch import Batch, from_device, host_batch
from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
from matrixone_tpu.ops import filter as F
from matrixone_tpu.sql.expr import BoundCol
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.utils import motrace
from matrixone_tpu.vm import operators as O
from matrixone_tpu.vm.exprs import ExecBatch

#: A batch of at most this many lanes crosses to the host uncompacted, in one
#: `device_get`, and a statement's last Sort or TopK over it orders it in
#: numpy.  On the chip's host an eager program costs the statement's thread
#: about 0.6 ms: Q1's ORDER BY over 12 lanes and its fetch were 305 of them
#: and 22 waits, 187 ms (ISSUE 36), where 4,096 lanes x 10 columns x 9 B is
#: a 369 KB fetch.  4,096 is the wide grouped aggregate's state, so every
#: dense or wide-fold grouped result is ordered on the host, and a general
#: group table at its `max_groups` (Q3's) stays on the device.
HOST_LANES = O.AggOp.WIDE_STATE_LANES


def take_rows(hb: DeviceBatch, rows: np.ndarray) -> DeviceBatch:
    """Rows of a batch of numpy arrays, in the order of `rows`; a const
    column stays const (`host_batch` broadcasts it)."""
    cols = {name: col if col.is_const else
            DeviceColumn(col.data[rows], col.validity[rows], col.dtype)
            for name, col in hb.columns.items()}
    return DeviceBatch(columns=cols, n_rows=np.int32(len(rows)))


def to_host(ex: ExecBatch, schema) -> Batch:
    """An ExecBatch's live rows as a host Batch, with one wait."""
    M.device_wait.inc(site="result")
    if ex.padded_len <= HOST_LANES:
        hb, mask = jax.device_get((ex.batch, ex.mask))
        return host_batch(take_rows(hb, np.flatnonzero(mask)), ex.dicts,
                          dict(schema))
    return from_device(F.compact(ex.batch, ex.mask, ex.padded_len),
                       ex.dicts, dict(schema))


def _plain(op) -> Optional[List[O.Operator]]:
    """The Projects of plain columns and Limits `op` stands for, top
    down (a fused stream fragment stands for its stages), or None.  A
    join probe or a window fragment is a FusedFragmentOp too, but its
    `child` is one input of a join or a window: it stands for more."""
    from matrixone_tpu.vm.fusion import FusedFragmentOp
    if type(op) is FusedFragmentOp and op._terminal == "stream":
        ops = [st.op for st in reversed(op.stages)]
    else:
        ops = [op]
    for o in ops:
        if not (isinstance(o, O.LimitOp)
                or (isinstance(o, O.ProjectOp)
                    and all(isinstance(e, BoundCol) for e in o.node.exprs))):
            return None
    return ops


def last_order(root: O.Operator) -> Optional["Tail"]:
    """The statement's last Sort or TopK under `root`, with what lies
    above it, or None."""
    above: List[O.Operator] = []
    parent, op = None, root
    while not isinstance(op, (O.SortOp, O.TopKOp)):
        ops = _plain(op)
        if ops is None:
            return None
        above.extend(ops)
        parent, op = op, op.child
    if not all(isinstance(k, BoundCol) for k in op.node.keys):
        return None
    return Tail(root, parent, op, above)


class _Given(O.Operator):
    """One ordered batch in the place of the Sort or TopK that made it."""

    def __init__(self, ex: ExecBatch, schema):
        self._batches = [ex]
        self.schema = schema

    def execute(self) -> Iterator[ExecBatch]:
        while self._batches:            # once: a stale link holds nothing
            yield self._batches.pop()


class Tail:
    """A statement's last Sort or TopK (`order_op`, the `child` of
    `parent`, under `root`) and the Projects and Limits above it.  For
    the host path these reduce to `cols` (output column -> `order_op`'s
    column) and the row window [lo, hi) of `order_op`'s output."""

    def __init__(self, root, parent, order_op, above: List[O.Operator]):
        self.root, self.parent, self.order_op = root, parent, order_op
        self.kind = "sort" if isinstance(order_op, O.SortOp) else "topk"
        self.cols: Dict[str, str] = {n: n for n, _ in order_op.schema}
        self.lo, self.hi = 0, None
        for o in reversed(above):               # bottom up
            if isinstance(o, O.LimitOp):
                self.lo += o.node.offset or 0
                if o.node.n is not None:
                    end = self.lo + o.node.n
                    self.hi = end if self.hi is None else min(self.hi, end)
            else:
                self.cols = {out: self.cols[e.name] for (out, _), e
                             in zip(o.node.schema, o.node.exprs)}

    def execute(self, schema) -> Iterator[Union[Batch, ExecBatch]]:
        """The result: a host Batch where the input was small, else the
        root's own ExecBatches over the device's order."""
        batches = list(self.order_op.child.execute())
        if not batches:
            return
        if sum(ex.padded_len for ex in batches) <= HOST_LANES:
            yield self._host(batches, schema)
            return
        yield from self._device(batches)

    def _device(self, batches: List[ExecBatch]) -> List[ExecBatch]:
        """The Sort or TopK on the device, and the operators above it run
        over its output as they are."""
        with motrace.span("result.sort", where="device"):
            ex = self.order_op.order(
                O._concat_batches(batches, self.order_op.schema))
            M.result_finish.inc(op=self.kind, where="device")
        if self.parent is None:
            return [ex]
        self.parent.child = _Given(ex, self.order_op.schema)
        try:
            return list(self.root.execute())
        finally:
            self.parent.child = self.order_op

    def _host(self, batches: List[ExecBatch], schema) -> Batch:
        with motrace.span("result.fetch"):
            got = jax.device_get([(ex.batch, ex.mask) for ex in batches])
            M.device_wait.inc(site="result")
            M.result_finish.inc(op="fetch", where="host")
            hex_ = _host_concat(got, batches, self.order_op.schema)
            with motrace.span("result.sort", where="host"):
                rows = self.order_op.host_rows(hex_)[self.lo:self.hi]
                M.result_finish.inc(op=self.kind, where="host")
            cols = {out: hex_.batch.columns[src]
                    for out, src in self.cols.items()}
            dicts = {out: hex_.dicts[src] for out, src in self.cols.items()
                     if src in hex_.dicts}
            hb = take_rows(DeviceBatch(columns=cols, n_rows=hex_.batch.n_rows),
                           rows)
            return host_batch(hb, dicts, dict(schema))


def _host_concat(got, batches: List[ExecBatch], schema) -> ExecBatch:
    """`operators._concat_batches` over fetched batches, in numpy."""
    if len(got) == 1:
        (hb, mask), = got
        return ExecBatch(batch=hb, dicts=batches[0].dicts, mask=mask)
    cols = {}
    for name, _ in schema:
        parts = [(hb.columns[name], mask.shape[0]) for hb, mask in got]
        cols[name] = DeviceColumn(
            np.concatenate([np.broadcast_to(c.data, (n,) + c.data.shape[1:])
                            for c, n in parts]),
            np.concatenate([np.broadcast_to(c.validity, (n,))
                            for c, n in parts]),
            parts[0][0].dtype)
    dicts: Dict[str, list] = {}
    for ex in batches:
        dicts.update(ex.dicts)
    mask = np.concatenate([mask for _, mask in got])
    n_rows = np.int32(sum(int(hb.n_rows) for hb, _ in got))
    return ExecBatch(batch=DeviceBatch(columns=cols, n_rows=n_rows),
                     dicts=dicts, mask=mask)
