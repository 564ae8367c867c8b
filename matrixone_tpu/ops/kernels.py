"""The one module that knows a hand-written kernel can exist.

A caller (`vectorindex/ivf_pq._search`) calls a function here and gets
an answer; whether XLA or the Pallas kernel of `ops/pallas_kernels.py`
computed it is chosen here, from what this module can observe: the
platform of the process's devices, read once, and the shape of the
arguments.  No session variable, environment variable or caller's
argument names a kernel.  The choice is a pure function of things a
compile key already holds (shapes, one platform a process), so no key
carries it.

A kernel is here because it beat the XLA code beside it on the chip, at
the shapes its caller passes and at the precision of that code, by more
than the spread of five alternating repeats (`tools/profile_pallas.py`;
the table is in PERF.md, PR 31), and is chosen under the condition it was
timed under.  The kernels that lost, or that nothing called, were
deleted there: the join probe's sorted search, the pairwise L2 and its
masked sibling, the one-hot segment sum.

On a platform with no kernel compiler the kernel runs only where a test
substitutes the choice (`adc_kernel_chosen`), and then in interpret mode
(`interpret()`).
"""

from __future__ import annotations

import functools

_ADC_TILE = 128      # the tile the kernel was timed with on the chip


@functools.lru_cache(maxsize=None)
def platform() -> str:
    """Platform of the devices this process computes on, resolved once
    ("tpu" | "cpu" | ...)."""
    import jax
    return jax.devices()[0].platform


def interpret() -> bool:
    """The `interpret=` every Pallas call here passes: compiled on a TPU,
    interpreted elsewhere (reached only through a test's substitute)."""
    return platform() != "tpu"


#: widest key range a direct-address join table is made for: 2^22 int32
#: slots are 16 MB of device memory.  A bound on memory, not a crossover:
#: on the chip the table beat the one-lane sorted search at every size
#: tried (SSB at SF1, five alternating pairs on the same constants: a
#: statement 4.4-6.4x slower sorted, builds of 2,000 to 200,000 rows in
#: tables of 2^12 to 2^18 slots; 18.7x, 5.70 s against 0.305 s, for a
#: 1,500,000-row build in 2^21 slots probed by 6,000,000 rows;
#: PERF.md section 6, PR 33)
JOIN_DENSE_SPAN_MAX = 1 << 22


def join_lookup(unique: bool, int_keys: int, span: int) -> str:
    """How the fused join finds a probe key's build row: "dense", a
    direct-address table indexed by key - min(key) (one gather a probe
    row, no hash, no sort, nothing to verify), where the build was seen to
    hold one row a key at most, on one integer key whose values span at
    most `JOIN_DENSE_SPAN_MAX`: a dimension keyed 1..N, a date key; else
    "sorted", the binary search into the sorted hashes.  All three are
    what the build step observes or the plan declares; nothing names a
    lookup from outside."""
    if unique and int_keys == 1 and 0 < span <= JOIN_DENSE_SPAN_MAX:
        return "dense"
    return "sorted"


def adc_kernel_chosen(candidates: int) -> bool:
    """IVF-PQ's ADC scores ride the one-hot matmul on a TPU where the
    candidate lists are padded to whole 128-lane tiles (`ivf_pq.build`
    always pads so)."""
    return platform() == "tpu" and candidates % _ADC_TILE == 0


def adc_scores(codes, lut):
    """scores[g, p] = sum_m lut[g, m, codes[g, p, m]]: codes [G, P, M]
    uint8/int32 (G query-probe groups of P candidates), lut [G, M, 256]
    float32."""
    import jax.numpy as jnp
    if adc_kernel_chosen(codes.shape[1]):
        from matrixone_tpu.ops import pallas_kernels as PK
        return PK.adc_score_pallas(codes, lut, tile_c=_ADC_TILE,
                                   interpret=interpret())
    gathered = jnp.take_along_axis(
        lut[:, None, :, :],                          # [G, 1, M, 256]
        codes[..., None].astype(jnp.int32),          # [G, P, M, 1]
        axis=3)[..., 0]                              # [G, P, M]
    return jnp.sum(gathered, axis=-1)
