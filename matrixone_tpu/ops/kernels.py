"""Hand-kernel dispatch seam: the ONE routing point between XLA's
fused programs and the hand-written Pallas kernels for the two inner
loops the profile says XLA loses on TPU — the hash-join probe's sorted
search and the grouped-agg group-scatter.

Paper L4 analogue: `cgo/xcall.c` — hand SIMD/CUDA kernels live NEXT TO
the codegen'd operators behind one dispatch table, so "use the hand
loop" is a routing decision, not a code fork.  Here likewise: callers
(vm/join, ops/agg) call through this module and never name Pallas
directly; the choice is

  * `MO_HAND_KERNELS=0` — kill switch: always the XLA path (the
    rollback story when a kernel misbehaves on new hardware);
  * `MO_HAND_KERNELS=1` — force on (tier-1 runs the Pallas kernels in
    interpret mode on cpu this way; the bit-identity drills and the
    moqa padding canary ride it);
  * unset / `auto` — on where the devices are TPUs, off on cpu
    (XLA:CPU's native scatter/searchsorted beat interpreted Pallas by
    orders of magnitude).

The platform is read ONCE from `jax.devices()` (`platform()`), and every
production caller of a Pallas kernel passes `interpret=interpret()`:
compiled on a TPU, interpreted only where a force switch
(`MO_HAND_KERNELS=1`, `SET use_pallas = 1`, `MO_USE_PALLAS=1`) turned a
kernel on for a platform that has no kernel compiler.  The auto route
is on only on TPU, so it can never select interpret mode.

Identity contract: `sorted_lookup` is bit-identical to the XLA path on
EVERY backend by construction (integer count, no rounding, no order
sensitivity — tools/precheck --kernel-smoke enforces it).
`grouped_scatter_add` routes only float32 sums to the MXU one-hot
kernel (same rule the session `SET use_pallas` path always had);
exact int64/decimal/f64 sums stay on the XLA scatter unconditionally.
The resolved routing is baked into traced executables, so every fused
compile key carries `signature()` (vm/fusion, vm/fusion_join).
"""

from __future__ import annotations

import functools
import os


@functools.lru_cache(maxsize=None)
def platform() -> str:
    """Platform of the devices this process computes on, resolved once
    ("tpu" | "cpu" | ...)."""
    import jax
    return jax.devices()[0].platform


def interpret() -> bool:
    """The `interpret=` every production Pallas call passes (see the
    module docstring): False on TPU, True only under a force switch
    elsewhere."""
    return platform() != "tpu"


def _flag() -> str:
    return os.environ.get("MO_HAND_KERNELS", "auto").lower()


def enabled() -> bool:
    """Resolve the hand-kernel routing for this process/backend.  Read
    host-side at trace/compile time only; consumers record it in their
    compile keys so a flip re-traces instead of colliding."""
    v = _flag()
    if v in ("1", "on", "true"):
        return True
    if v in ("0", "off", "false"):
        return False
    return platform() == "tpu"


def signature() -> tuple:
    """Compile-key component: the resolved routing (the kernels are
    trace-time choices, invisible in input dtypes/shapes)."""
    return ("hand_kernels", enabled())


def sorted_lookup(sorted_vals, queries):
    """searchsorted-left over the sorted build-side hashes (uint64):
    the probe's per-row entry point into the hash run.  Pallas
    count-less-than kernel when enabled, jnp.searchsorted otherwise —
    bit-identical either way."""
    import jax.numpy as jnp
    if enabled():
        from matrixone_tpu.ops import pallas_kernels as PK
        return PK.sorted_search_pallas(sorted_vals, queries,
                                       interpret=interpret())
    return jnp.searchsorted(sorted_vals, queries).astype(jnp.int32)


def grouped_scatter_add(values, gids, mask, max_groups: int,
                        use_pallas: bool = False):
    """Masked segment sum — the grouped-agg group-scatter.  float32
    values ride the one-hot-matmul Pallas kernel when routing says so;
    every exact dtype (int64 counts/decimals, f64) stays on the XLA
    scatter.  `use_pallas` must be resolved OUTSIDE any jit (it picks
    the traced program): vm/compile ORs the session `SET use_pallas`
    with `enabled()` and threads it as a static jit arg, so the routing
    is part of the jit cache key — this function never reads the env."""
    import jax.numpy as jnp
    if (use_pallas and values.dtype == jnp.float32
            and max_groups <= 4096 and values.shape[0] > 0):
        from matrixone_tpu.ops import pallas_kernels as PK
        n = values.shape[0]
        tile = 512
        padded = ((n + tile - 1) // tile) * tile
        if padded != n:
            values = jnp.pad(values, (0, padded - n))
            gids = jnp.pad(gids, (0, padded - n))
            mask = jnp.pad(mask, (0, padded - n))   # pads False
        return PK.segment_sum_pallas(values, gids, mask,
                                     num_segments=max_groups,
                                     tile_n=tile, interpret=interpret())
    import jax
    v = jnp.where(mask, values, jnp.asarray(0, values.dtype))
    return jax.ops.segment_sum(v, gids, num_segments=max_groups)
