"""The hand-tiled Pallas TPU kernel, imported by `ops/kernels.py` alone.

Reference analogue: the hand-written SIMD/CUDA kernels (`cgo/arith.c`,
`cgo/cuda/mocl.cu`, `cgo/cuvs/ivf_pq_c.cpp` ADC scoring).

  * `adc_score_pallas` — IVF-PQ asymmetric-distance scoring
    sum_m LUT[g, m, code] as a one-hot matmul per candidate tile
    (`cgo/cuvs` ivf_pq ADC kernel analogue): a gather a code byte is
    what the chip does worst, a matmul what it does best.

It is here because it beat the XLA gather beside it on the chip at equal
precision (PERF.md, PR 31); `ops/kernels.py` says when it is chosen.  It
compiles for the device unless its caller passes `interpret=True`;
nothing here looks for a chip.  `ops/kernels.py` passes its
`interpret()`, the CPU tests pass the flag themselves.  It compiles for a
described v5e at deployment widths (tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _note_trace(kernel: str, interpret: bool) -> None:
    """Count one trace of `kernel` (runs while the jitted wrapper is
    traced): how chip_smoke.py learns which kernels the served path
    really put into its programs, and in which mode."""
    from matrixone_tpu.utils import metrics as M
    M.pallas_traces.inc(kernel=kernel, interpret=str(bool(interpret)))


# jax_enable_x64 is on package-wide, so a literal 0 in an index map is
# an int64 the TPU kernel compiler refuses: block indices are int32
_Z = np.int32(0)


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
        # HIGHEST: at the default the MXU rounds the table's entries to
        # bfloat16 and the scores are off by 1e-3 on the chip; so they
        # are off by 5e-7, as the XLA gather's are
        acc = acc + jax.lax.dot_general(
            lut[j:j + 1, :], onehot,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
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
