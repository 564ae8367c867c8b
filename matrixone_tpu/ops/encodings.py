"""Narrow column encodings: per-column storage/compute dtype selection.

The decoded-column working set is the scan path's bandwidth bill, and
most of it is wider than the data: dictionary codes for join/group keys
ship as int32 even when the dictionary holds 20 strings, and float
aggregate lanes ride f32/f64 through the fused program even though the
accumulator (not the element) carries the precision.  This module is
the one policy point for narrowing both, the way PR 3 measured
bf16-vs-f32 per backend for IVF — generalized to per-column choice:

  * **dict codes** (lossless, bit-identical): int8 when the dictionary
    fits 128 entries, int16 under 32768, int32 otherwise.  Codes hash,
    compare and gather identically at any width (ops/hash widens to
    int64 before mixing; jnp comparisons promote), so this is purely a
    memory/bandwidth choice.  Applied at the host->device boundary
    (vm/operators.chunk_to_execbatch); a dictionary that grows past a
    width boundary flips the code dtype, which the fragment compile key
    carries (vm/fusion._runtime_key includes the array dtype), so a
    widened dict re-traces instead of colliding.
  * **bf16 float-agg lanes** (lossy, documented tolerance): FLOAT32
    aggregate *input* lanes in the fused dense-agg terminal round to
    bfloat16 before the (always-f64) accumulation — elements lose
    mantissa, sums do not lose order.  The documented tolerance is
    bf16's 8 mantissa bits: ~2-3 significant decimal digits per
    element, so relative error of a sum of same-signed elements stays
    under ~0.4%.  FLOAT64 lanes are never narrowed (the SQL `double`
    contract), and the exact-decimal discipline is untouched: decimals
    and counts stay scaled int64 everywhere.  Predicates, group keys,
    join keys and projections always evaluate at full width — flipping
    a row across a filter is a wrong answer, not a tolerance.

The policy is chosen per backend: `MO_NARROW_ENCODINGS` is `auto` by
default (on for TPU, off on CPU, where narrow loads
de-vectorize instead of saving bandwidth), `1` forces it on (the moqa
`narrow-encodings` lockstep pair runs this against the f32/int64
baseline), `0` kills it.
"""

from __future__ import annotations

import os

import numpy as np


def _flag() -> str:
    return os.environ.get("MO_NARROW_ENCODINGS", "auto").lower()


def enabled() -> bool:
    """Resolve the policy for this process/backend.  Read on the host
    at batch-staging and trace time only — every consumer records the
    resolved value in its compile key (directly or via the narrowed
    array dtypes), so a flip re-traces instead of colliding."""
    v = _flag()
    if v in ("1", "on", "true"):
        return True
    if v in ("0", "off", "false", ""):
        return False
    from matrixone_tpu.ops.kernels import platform
    return platform() == "tpu"


def signature() -> tuple:
    """Compile-key component: the resolved policy.  The narrowed input
    dtypes already distinguish most flips, but the bf16 lane choice is
    applied inside the trace (not visible in the input signature), so
    the key must carry it explicitly."""
    return ("narrow", enabled())


# ------------------------------------------------------------ dict codes

def code_np_dtype(dict_len: int) -> np.dtype:
    """Narrowest signed int dtype holding codes 0..dict_len-1."""
    if dict_len <= (1 << 7):
        return np.dtype(np.int8)
    if dict_len <= (1 << 15):
        return np.dtype(np.int16)
    return np.dtype(np.int32)


def narrow_codes(arr, dict_len: int):
    """Cast a code array (numpy or jax) to its narrowest width.  A
    no-op when the policy is off or the array is already narrow."""
    if not enabled():
        return arr
    cdt = code_np_dtype(dict_len)
    if arr.dtype == cdt:
        return arr
    if np.dtype(arr.dtype).itemsize < cdt.itemsize:
        return arr                      # never widen here
    return arr.astype(cdt)


# --------------------------------------------------------- bf16 agg lanes

def narrow_lane(val):
    """Round one float aggregate-input lane to bf16 (FLOAT32 only;
    f64 and non-floats pass through).  Called inside the fused trace —
    the accumulation downstream stays f64, so only element precision
    narrows, never reduction order."""
    import jax.numpy as jnp
    if enabled() and val is not None and val.dtype == jnp.float32:
        return val.astype(jnp.bfloat16)
    return val
