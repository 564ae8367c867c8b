"""Device-resident columnar containers (JAX pytrees).

The TPU-native redesign of the reference's `container.Vector` / `container.Batch`
(`pkg/container/vector/vector.go:43`, `pkg/container/batch/types.go:45`):

  reference (Go, CPU)                      this module (JAX, TPU)
  ------------------------------           -----------------------------------
  data []byte (fixed-width values)    ->   DeviceColumn.data  jnp array
  nulls *nulls.Nulls (bitmap)         ->   DeviceColumn.validity bool array
  area []byte (varlena heap)          ->   dictionary codes in .data (int32),
                                           dictionary strings stay host-side
  batch.Batch{Vecs, rowCount}         ->   DeviceBatch{columns, n_rows}

Key deviations, all deliberate for XLA:
  * arrays are padded to bucketed lengths so jitted kernels hit the compile
    cache instead of recompiling per batch size (see `bucket_length`); padding
    rows are masked out by `DeviceBatch.row_mask()`;
  * validity is a bool array, not a bitmap — XLA fuses mask math into
    neighbouring elementwise ops for free; host<->device serialization packs
    to bits (container/host Vector does that);
  * a "const" (scalar) column is a length-1 array broadcast by kernels,
    mirroring the reference's const-vector class.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.container.dtypes import DType
from matrixone_tpu.utils import metrics as M, qa

#: batch length buckets — powers of two from 1Ki to 1Mi. A batch of 13_000
#: rows is padded to 16_384 so every operator's jit cache has at most
#: len(_BUCKETS) entries per dtype signature (the reference has no analogue:
#: Go code doesn't recompile; XLA does, so shapes must be quantized).
_BUCKETS = [1 << k for k in range(10, 21)]


def bucket_length(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    # beyond 1Mi rows, round up to the next multiple of 1Mi
    m = _BUCKETS[-1]
    return ((n + m - 1) // m) * m


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceColumn:
    """One typed column on device: values + validity mask.

    ``data``: shape [n] (scalar types) or [n, dim] (VECF32 embeddings).
    ``validity``: bool [n]; True = value present (Arrow convention).
    ``dtype``: the SQL type (static / aux data, not traced).
    """

    data: jnp.ndarray
    validity: jnp.ndarray
    dtype: DType

    def tree_flatten(self):
        return (self.data, self.validity), self.dtype

    @classmethod
    def tree_unflatten(cls, aux, children):
        data, validity = children
        return cls(data=data, validity=validity, dtype=aux)

    @property
    def padded_len(self) -> int:
        return self.data.shape[0]

    @classmethod
    def const(cls, value, dtype: DType) -> "DeviceColumn":
        """Length-1 'const' column (reference: const-class vectors)."""
        data = jnp.asarray([value], dtype=dtype.jnp_dtype)
        return cls(data=data, validity=jnp.ones((1,), jnp.bool_), dtype=dtype)

    @classmethod
    def const_null(cls, dtype: DType) -> "DeviceColumn":
        data = jnp.zeros((1,), dtype=dtype.jnp_dtype)
        return cls(data=data, validity=jnp.zeros((1,), jnp.bool_), dtype=dtype)

    @property
    def is_const(self) -> bool:
        return self.data.shape[0] == 1


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceBatch:
    """A batch of named columns + dynamic row count.

    ``n_rows`` is a traced int32 scalar: batches padded to the same bucket
    share one compiled executable regardless of their true length.
    """

    columns: Dict[str, DeviceColumn]
    n_rows: jnp.ndarray  # int32 scalar

    def tree_flatten(self):
        names = tuple(self.columns.keys())
        return (tuple(self.columns.values()), self.n_rows), names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols, n_rows = children
        return cls(columns=dict(zip(names, cols)), n_rows=n_rows)

    @property
    def padded_len(self) -> int:
        for c in self.columns.values():
            if not c.is_const:
                return c.padded_len
        return 1

    def row_mask(self) -> jnp.ndarray:
        """bool [padded_len]: True for real (non-padding) rows."""
        return jnp.arange(self.padded_len, dtype=jnp.int32) < self.n_rows

    def column(self, name: str) -> DeviceColumn:
        return self.columns[name]

    def with_column(self, name: str, col: DeviceColumn) -> "DeviceBatch":
        cols = dict(self.columns)
        cols[name] = col
        return DeviceBatch(columns=cols, n_rows=self.n_rows)

    def select(self, names) -> "DeviceBatch":
        return DeviceBatch(columns={n: self.columns[n] for n in names},
                           n_rows=self.n_rows)


def _dtype_ok(have, want: np.dtype) -> bool:
    """Accept the declared dtype OR a narrower signed int (narrow dict
    codes from ops/encodings: int8/int16 codes under a declared int32
    column must survive staging, not silently widen back)."""
    have = np.dtype(have)
    if have == want:
        return True
    return (have.kind == "i" and want.kind == "i"
            and have.itemsize < want.itemsize)


@functools.partial(jax.jit, static_argnames=("padded", "poison"))
def _pad_on_device(datas, vals, *, padded: int, poison: bool):
    """Pad a chunk's device-resident columns to their bucket in one
    program: each data array gets a tail of `qa.pad_fill`'s value (zeros,
    or the dtype's canary when moqa is armed: `poison`), each validity a
    tail of False (`None` = every real row present)."""
    out_d, out_v = [], []
    for d, v in zip(datas, vals):
        n = d.shape[0]
        fill = qa.canary_value(d.dtype) if poison else None
        edges = [(0, padded - n, 0)] + [(0, 0, 0)] * (d.ndim - 1)
        out_d.append(jax.lax.pad(
            d, jnp.asarray(0 if fill is None else fill, d.dtype), edges))
        out_v.append(jnp.arange(padded, dtype=jnp.int32) < n if v is None
                     else jax.lax.pad(v.astype(jnp.bool_), jnp.bool_(False),
                                      [(0, padded - n, 0)]))
    return out_d, out_v


def from_numpy(arrays: Dict[str, np.ndarray],
               dtypes: Dict[str, DType],
               validity: Optional[Dict[str, np.ndarray]] = None,
               n_rows: Optional[int] = None,
               pad_to: Optional[int] = None) -> DeviceBatch:
    """Build a padded DeviceBatch from host numpy arrays or device-resident
    jax arrays (zero rows allowed).  A device array of an acceptable dtype
    never passes through the host: at bucket length it is taken as it is,
    under it the chunk's device columns are padded there by one program."""
    if n_rows is None:
        n_rows = len(next(iter(arrays.values()))) if arrays else 0
    padded = pad_to if pad_to is not None else bucket_length(max(n_rows, 1))
    cols = {}
    ragged = []          # device-resident columns under the bucket length
    paths = collections.Counter()
    for name, arr in arrays.items():
        dt = dtypes[name]
        val = None if validity is None else validity.get(name)
        on_device = isinstance(arr, jax.Array)
        if on_device and _dtype_ok(arr.dtype, np.dtype(dt.np_dtype)):
            if not isinstance(val, jax.Array) and val is not None:
                val = jnp.asarray(np.asarray(val, np.bool_))
            if padded == n_rows:
                # already device-resident at the right dtype and length
                # (the blockcache hands out ready-to-batch device arrays):
                # skip the host round-trip entirely — the warm-scan path
                paths["device"] += 1
                jval = jnp.ones(n_rows, jnp.bool_) if val is None else val
                cols[name] = DeviceColumn(data=arr, validity=jval, dtype=dt)
            else:
                # a segment's ragged last chunk, or one thinned by
                # tombstones: padded on the device below, all columns of
                # the chunk in one dispatch (the slot keeps the order)
                paths["device_pad"] += 1
                cols[name] = None
                ragged.append((name, arr, val))
            continue
        paths["roundtrip" if on_device else "host"] += 1
        arr = np.asarray(arr)
        if not _dtype_ok(arr.dtype, np.dtype(dt.np_dtype)):
            arr = np.asarray(arr, dtype=dt.np_dtype)
        if val is None:
            val = np.ones(n_rows, dtype=np.bool_)
        else:
            val = np.asarray(val, np.bool_)
        pad_n = padded - n_rows
        if pad_n:
            pad_shape = (pad_n,) + arr.shape[1:]
            # padded-tail fill: zeros, or canary-poisoned under the moqa
            # audit (utils/qa.py) — the tail is dead by contract, so the
            # fill value must never be observable
            arr = np.concatenate([arr, qa.pad_fill(arr.dtype, pad_shape)])
            val = np.concatenate([val, np.zeros(pad_n, dtype=np.bool_)])
        cols[name] = DeviceColumn(data=jnp.asarray(arr),
                                  validity=jnp.asarray(val),
                                  dtype=dt)
    if ragged:
        datas, vals = _pad_on_device(
            tuple(a for _, a, _ in ragged), tuple(v for _, _, v in ragged),
            padded=padded, poison=qa.armed())
        for (name, _, _), d, v in zip(ragged, datas, vals):
            cols[name] = DeviceColumn(data=d, validity=v, dtype=dtypes[name])
    for path, k in paths.items():
        M.from_numpy_columns.inc(k, path=path)
    return DeviceBatch(columns=cols, n_rows=jnp.asarray(n_rows, jnp.int32))
