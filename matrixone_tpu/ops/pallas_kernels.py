"""Hand-tiled Pallas TPU kernels for the hottest inner loops.

Reference analogue: the hand-written SIMD/CUDA kernels (`cgo/arith.c`,
`cgo/cuda/mocl.cu`, `cgo/cuvs/ivf_pq_c.cpp` ADC scoring) — here Pallas
grid kernels that keep the MXU fed from VMEM explicitly instead of
relying on XLA's default tiling.

Kernels:
  * `l2_distance_sq_pallas`     — tiled pairwise L2 with the norm
    epilogue fused (never round-trips through HBM);
  * `l2_distance_sq_masked_pallas` — same with a fused validity mask
    (masked rows score +inf), the filtered-search shape
    (`cgo/cuvs/filter.hpp` bitset prefilter analogue);
  * `segment_sum_pallas`        — one-hot-matmul GROUP BY segment sum:
    the hash-table-free TPU formulation of `colexec/group` partial
    aggregation, riding the MXU instead of scatter units;
  * `adc_score_pallas`          — IVF-PQ asymmetric-distance scoring
    sum_m LUT[g, m, code] as a one-hot matmul per candidate tile
    (`cgo/cuvs` ivf_pq ADC kernel analogue);
  * `sorted_search_pallas`      — the hash-join probe's searchsorted
    over the sorted build hashes as a count-less-than reduction
    (gather-free, VPU compares + integer sum), bit-identical to
    `jnp.searchsorted(side='left')` by construction.

Every kernel compiles for the device unless its caller passes
`interpret=True`; nothing here looks for a chip.  Production callers take
the flag from the dispatch seam (`ops/kernels.py` `interpret()`), the
CPU tests pass it themselves.  The first four are opt-in: sessions
enable them with `SET use_pallas = 1` (reference:
`pkg/util/gpumode/gpu_mode.go:37 EffectiveGpuMode` — session value wins,
else the MO_USE_PALLAS env default); the sorted search is routed by the
seam.  Every kernel compiles for a described v5e at the widths of
chip_smoke.py (tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


# --------------------------------------------------------------- gating
def use_pallas() -> bool:
    """Process default (env). Kept for back-compat; prefer
    effective_use_pallas(session_value)."""
    return os.environ.get("MO_USE_PALLAS") == "1"


def effective_use_pallas(session_value=None) -> bool:
    """gpu_mode.go:37 EffectiveGpuMode analogue: an explicit session
    `SET use_pallas = 0|1` wins; otherwise the MO_USE_PALLAS env var
    (the build-tag default of the reference)."""
    if session_value is not None:
        try:
            return bool(int(session_value))
        except (TypeError, ValueError):
            return False
    return use_pallas()


def _note_trace(kernel: str, interpret: bool) -> None:
    """Count one trace of `kernel` (runs while the jitted wrapper is
    traced): how chip_smoke.py learns which kernels the served path
    really put into its programs, and in which mode."""
    from matrixone_tpu.utils import metrics as M
    M.pallas_traces.inc(kernel=kernel, interpret=str(bool(interpret)))


# jax_enable_x64 is on package-wide, so a literal 0 in an index map is
# an int64 the TPU kernel compiler refuses: block indices are int32
_Z = np.int32(0)


# ------------------------------------------------- pairwise L2 (fused)
def _l2_kernel(x_ref, q_ref, q2_ref, out_ref):
    x = x_ref[:]                                   # [TM, D] f32
    q = q_ref[:]                                   # [B, D]  f32
    xq = jax.lax.dot_general(
        x, q, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # [TM, B] on the MXU
    x2 = jnp.sum(x * x, axis=1, keepdims=True)     # fused row norms (VPU)
    out_ref[:] = jnp.maximum(x2 + q2_ref[:] - 2.0 * xq, 0.0)


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def l2_distance_sq_pallas(x: jnp.ndarray, q: jnp.ndarray,
                          tile_m: int = 1024,
                          interpret: bool = False) -> jnp.ndarray:
    """Pairwise squared L2 [n, b]; n must be a multiple of tile_m."""
    n, d = x.shape
    b = q.shape[0]
    assert n % tile_m == 0, f"n={n} must be a multiple of tile_m={tile_m}"
    _note_trace("l2_distance_sq_pallas", interpret)
    xf = x.astype(jnp.float32)
    qf = q.astype(jnp.float32)
    q2 = jnp.sum(qf * qf, axis=1)[None, :]          # [1, b]
    grid = (n // tile_m,)
    kernel = pl.pallas_call(
        _l2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_m, d), lambda i: (i, _Z)),
            pl.BlockSpec((b, d), lambda i: (_Z, _Z)),
            pl.BlockSpec((1, b), lambda i: (_Z, _Z)),
        ],
        out_specs=pl.BlockSpec((tile_m, b), lambda i: (i, _Z)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        interpret=interpret,
    )
    with jax.named_scope("l2_distance_sq_pallas"):
        return kernel(xf, qf, q2)


# -------------------------------------- pairwise L2 with fused prefilter
def _l2_masked_kernel(x_ref, q_ref, q2_ref, m_ref, out_ref):
    x = x_ref[:]                                   # [TM, D] f32
    q = q_ref[:]                                   # [B, D]  f32
    xq = jax.lax.dot_general(
        x, q, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    dist = jnp.maximum(x2 + q2_ref[:] - 2.0 * xq, 0.0)
    # fused doc-filter: excluded rows never leave the kernel as
    # candidates (top-k downstream sorts them last)
    keep = m_ref[:] > 0                            # [TM, 1] int32
    out_ref[:] = jnp.where(keep, dist, jnp.inf)


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def l2_distance_sq_masked_pallas(x: jnp.ndarray, q: jnp.ndarray,
                                 mask: jnp.ndarray,
                                 tile_m: int = 1024,
                                 interpret: bool = False
                                 ) -> jnp.ndarray:
    """Filtered pairwise squared L2 [n, b]: rows with mask=False score
    +inf. The mask rides into the same VMEM tile as the vectors, so the
    filter costs no extra HBM pass (the reference pre-filters with a
    bitset handed to cuVS — cgo/cuvs/filter.hpp)."""
    n, d = x.shape
    b = q.shape[0]
    assert n % tile_m == 0, f"n={n} must be a multiple of tile_m={tile_m}"
    _note_trace("l2_distance_sq_masked_pallas", interpret)
    xf = x.astype(jnp.float32)
    qf = q.astype(jnp.float32)
    q2 = jnp.sum(qf * qf, axis=1)[None, :]
    m2 = mask.astype(jnp.int32)[:, None]            # [n, 1]
    kernel = pl.pallas_call(
        _l2_masked_kernel,
        grid=(n // tile_m,),
        in_specs=[
            pl.BlockSpec((tile_m, d), lambda i: (i, _Z)),
            pl.BlockSpec((b, d), lambda i: (_Z, _Z)),
            pl.BlockSpec((1, b), lambda i: (_Z, _Z)),
            pl.BlockSpec((tile_m, 1), lambda i: (i, _Z)),
        ],
        out_specs=pl.BlockSpec((tile_m, b), lambda i: (i, _Z)),
        out_shape=jax.ShapeDtypeStruct((n, b), jnp.float32),
        interpret=interpret,
    )
    with jax.named_scope("l2_distance_sq_masked_pallas"):
        return kernel(xf, qf, q2, m2)


# ------------------------------------------------ GROUP BY segment sum
def _segsum_kernel(v_ref, g_ref, out_ref):
    i = pl.program_id(0)
    v = v_ref[:]                                    # [1, TN] f32
    g = g_ref[:]                                    # [1, TN] int32
    num_segments = out_ref.shape[1]
    # one-hot [TN, G] on the fly in VMEM; the segment reduction becomes
    # a [1, TN] @ [TN, G] matmul on the MXU — no scatter, no hash table
    onehot = (g[0][:, None] ==
              jax.lax.broadcasted_iota(jnp.int32, (1, num_segments), 1)
              ).astype(jnp.float32)
    partial = jax.lax.dot_general(
        v, onehot, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # [1, G]

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += partial                           # grid is sequential


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "tile_n", "interpret"))
def segment_sum_pallas(values: jnp.ndarray, gids: jnp.ndarray,
                       mask: jnp.ndarray, num_segments: int,
                       tile_n: int = 2048,
                       interpret: bool = False) -> jnp.ndarray:
    """Masked float32 segment sum over [n] values into [num_segments].

    TPU formulation of `colexec/group` partial aggregation: instead of a
    hash-table scatter, each row tile builds its one-hot group matrix in
    VMEM and reduces with a single MXU matmul; the sequential TPU grid
    accumulates partials in the output block, which stays resident.
    n must be a multiple of tile_n (callers pad with mask=False);
    num_segments bounded by VMEM (tile_n * num_segments * 4B ≲ 8 MB).

    NOTE float32 only: exact int64/decimal sums must stay on the XLA
    `segment_sum` scatter path (MXU accumulation is float).
    """
    n = values.shape[0]
    assert n % tile_n == 0, f"n={n} not a multiple of tile_n={tile_n}"
    _note_trace("segment_sum_pallas", interpret)
    v = jnp.where(mask, values.astype(jnp.float32), 0.0)[None, :]  # [1, n]
    # masked rows also get an out-of-range id so a gid collision with a
    # real group cannot resurrect them (id G sums into nothing: the iota
    # comparison never matches because iota < G)
    g = jnp.where(mask, gids.astype(jnp.int32), num_segments)[None, :]
    kernel = pl.pallas_call(
        _segsum_kernel,
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((1, tile_n), lambda i: (_Z, i)),
            pl.BlockSpec((1, tile_n), lambda i: (_Z, i)),
        ],
        out_specs=pl.BlockSpec((1, num_segments), lambda i: (_Z, _Z)),
        out_shape=jax.ShapeDtypeStruct((1, num_segments), jnp.float32),
        interpret=interpret,
    )
    with jax.named_scope("segment_sum_pallas"):
        return kernel(v, g)[0]


# --------------------------------------- hash-join probe sorted search
def _sorted_search_kernel(shi_ref, slo_ref, qhi_ref, qlo_ref, out_ref):
    j = pl.program_id(1)                            # sorted-tile index
    shi = shi_ref[:][0][:, None]                    # [TN, 1] int32
    slo = slo_ref[:][0][:, None]
    qhi = qhi_ref[:][0][None, :]                    # [1, TQ] int32
    qlo = qlo_ref[:][0][None, :]
    # lexicographic (hi, lo) compare == the uint64 compare: both halves
    # were pre-mapped to sign-flipped int32 so signed order == unsigned
    less = (shi < qhi) | ((shi == qhi) & (slo < qlo))   # [TN, TQ]

    @pl.when(j == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    # count-less-than accumulates across sorted tiles (the TPU grid is
    # sequential in its last dimension); the sum is order-free integer
    # arithmetic, so the result is exactly searchsorted-left
    out_ref[:] += jnp.sum(less.astype(jnp.int32), axis=0,
                          dtype=jnp.int32)[None, :]


def _sign_flip_halves(x64: jnp.ndarray):
    """uint64 [n] -> (hi, lo) sign-flipped int32 pairs whose signed
    lexicographic order equals the unsigned 64-bit order (TPU Pallas
    has no 64-bit integers in VMEM)."""
    hi = (x64 >> jnp.uint64(32)).astype(jnp.uint32)
    lo = x64.astype(jnp.uint32)                     # truncating mod 2^32
    flip = jnp.uint32(0x80000000)
    return ((hi ^ flip).astype(jnp.int32),
            (lo ^ flip).astype(jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("tile_q", "tile_n", "interpret"))
def sorted_search_pallas(sorted_vals: jnp.ndarray, queries: jnp.ndarray,
                         tile_q: int = 1024, tile_n: int = 1024,
                         interpret: bool = False) -> jnp.ndarray:
    """`jnp.searchsorted(sorted_vals, queries, side='left')` for uint64
    hashes, as a Pallas kernel: insertion-point-left(q) == #{s : s < q},
    so each (query-tile, sorted-tile) step is a dense VPU compare plus
    an integer reduction — no per-lane gather, no binary-search control
    flow, and bit-identical to the XLA path because an integer count has
    no rounding and no order sensitivity.

    Pads both inputs internally: sorted pads with UINT64_MAX (counted
    only for queries > MAX — impossible), queries pad with don't-cares
    sliced off the result.
    """
    (n,), (m,) = sorted_vals.shape, queries.shape
    _note_trace("sorted_search_pallas", interpret)
    s64 = sorted_vals.astype(jnp.uint64)
    q64 = queries.astype(jnp.uint64)
    pad_n = (-n) % tile_n
    pad_m = (-m) % tile_q
    if pad_n:
        s64 = jnp.pad(s64, (0, pad_n),
                      constant_values=jnp.uint64(0xFFFFFFFFFFFFFFFF))
    if pad_m:
        q64 = jnp.pad(q64, (0, pad_m))
    shi, slo = _sign_flip_halves(s64)
    qhi, qlo = _sign_flip_halves(q64)
    kernel = pl.pallas_call(
        _sorted_search_kernel,
        grid=(q64.shape[0] // tile_q, s64.shape[0] // tile_n),
        in_specs=[
            pl.BlockSpec((1, tile_n), lambda qi, ni: (_Z, ni)),
            pl.BlockSpec((1, tile_n), lambda qi, ni: (_Z, ni)),
            pl.BlockSpec((1, tile_q), lambda qi, ni: (_Z, qi)),
            pl.BlockSpec((1, tile_q), lambda qi, ni: (_Z, qi)),
        ],
        out_specs=pl.BlockSpec((1, tile_q), lambda qi, ni: (_Z, qi)),
        out_shape=jax.ShapeDtypeStruct((1, q64.shape[0]), jnp.int32),
        interpret=interpret,
    )
    with jax.named_scope("sorted_search_pallas"):
        out = kernel(shi[None, :], slo[None, :], qhi[None, :],
                     qlo[None, :])
    return out[0][:m]


# ------------------------------------------------- IVF-PQ ADC scoring
def _adc_kernel(codes_ref, lut_ref, out_ref):
    codes = codes_ref[0]                            # [TC, M] int32
    lut = lut_ref[0]                                # [M, 256] f32
    tc, m = codes.shape
    # scores[c] = sum_m lut[m, codes[c, m]] — one one-hot [TC, 256]
    # per subspace contracted with that subspace's LUT row on the MXU
    # (the reference's cuVS ADC kernel does warp-local LUT gathers;
    # TPUs have no per-lane gather, but the one-hot contraction is
    # exactly what the systolic array is good at).  The contraction is
    # [1, 256] x [TC, 256]^T so the scores come out lane-major, the
    # layout of the output block, with no in-kernel reshape.
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, 256), 1)  # [1, 256]
    acc = jnp.zeros((1, tc), jnp.float32)
    for j in range(m):
        onehot = (codes[:, j:j + 1] == iota).astype(jnp.float32)
        acc = acc + jax.lax.dot_general(
            lut[j:j + 1, :], onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    out_ref[0] = acc


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def adc_score_pallas(codes: jnp.ndarray, lut: jnp.ndarray,
                     tile_c: int = 256,
                     interpret: bool = False) -> jnp.ndarray:
    """Batched ADC scoring: codes [G, P, M] uint8/int32 (G query-probe
    groups, P candidates each), lut [G, M, 256] f32 -> scores [G, P]
    with scores[g, p] = sum_m lut[g, m, codes[g, p, m]].

    P must be a multiple of tile_c. VMEM per step: the one-hot tile
    (tile_c * M * 256 * 4B — 4 MB at tile_c=256, M=16) plus one LUT.
    """
    g, p, m = codes.shape
    assert p % tile_c == 0, f"P={p} not a multiple of tile_c={tile_c}"
    assert lut.shape == (g, m, 256), lut.shape
    _note_trace("adc_score_pallas", interpret)
    c32 = codes.astype(jnp.int32)
    kernel = pl.pallas_call(
        _adc_kernel,
        grid=(g, p // tile_c),
        in_specs=[
            pl.BlockSpec((1, tile_c, m), lambda gi, ci: (gi, ci, _Z)),
            pl.BlockSpec((1, m, 256), lambda gi, ci: (gi, _Z, _Z)),
        ],
        # [g, 1, p]: a (1, tile_c) block of a [g, p] array breaks the
        # chip's (8, 128) block rule; with the unit axis second-to-last
        # the block equals the array there
        out_specs=pl.BlockSpec((1, 1, tile_c),
                               lambda gi, ci: (gi, _Z, ci)),
        out_shape=jax.ShapeDtypeStruct((g, 1, p), jnp.float32),
        interpret=interpret,
    )
    with jax.named_scope("adc_score_pallas"):
        out = kernel(c32, lut.astype(jnp.float32))
    return out[:, 0, :]
