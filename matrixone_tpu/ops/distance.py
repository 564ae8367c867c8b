"""Vector distance kernels on the MXU.

TPU-native replacement for the reference's distance stack:
`pkg/vectorize/moarray/external.go:181 L2Distance / :201 CosineDistance`
(gonum CPU), `cgo/xcall.h:81 xcall_l2distance_f32/64` (SIMD C),
`cgo/cuda/mocl.cu` (CUDA), and cuVS brute-force (`cgo/cuvs/distance_c.cpp`).

Design: every pairwise distance is expressed as a matmul so the 128x128
systolic array does the FLOPs:

    ||x - q||^2 = ||x||^2 + ||q||^2 - 2 x.q      (one X @ Q^T)
    cosine(x,q) = 1 - x.q / (||x|| ||q||)        (one matmul on normalized)

Inputs may be bf16 (2x HBM bandwidth, 2x+ MXU rate) with f32 accumulation
via `preferred_element_type` — the same precision split cuVS uses for its
fp16 path (`cgo/cuvs/quantize.hpp`). Exact f32 paths exist for the
bit-identical oracle comparison required by BASELINE.json.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _matmul_xqT(x: jnp.ndarray, q: jnp.ndarray, compute_dtype) -> jnp.ndarray:
    """x [n,d] @ q[b,d]^T -> [n,b] with f32 accumulation.

    When no compute_dtype override is given, request HIGHEST precision:
    TPU matmuls otherwise run f32 inputs through bf16 passes (~1e-3 rel
    error — measured on v5e), which silently reorders near-tie top-k
    results. The fast path passes compute_dtype=bfloat16 explicitly.
    """
    precision = jax.lax.Precision.HIGHEST
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        q = q.astype(compute_dtype)
        precision = jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(
        x, q, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)


@partial(jax.jit, static_argnames=("compute_dtype",))
def l2_distance_sq(x: jnp.ndarray, q: jnp.ndarray,
                   compute_dtype=None) -> jnp.ndarray:
    """Squared L2 distances [n, b] between rows of x [n,d] and q [b,d]."""
    xq = _matmul_xqT(x, q, compute_dtype)
    x2 = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    q2 = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=-1)
    return jnp.maximum(x2 + q2[None, :] - 2.0 * xq, 0.0)


@partial(jax.jit, static_argnames=("compute_dtype",))
def l2_distance(x: jnp.ndarray, q: jnp.ndarray, compute_dtype=None) -> jnp.ndarray:
    return jnp.sqrt(l2_distance_sq(x, q, compute_dtype=compute_dtype))


def _seq_sum_lastdim(x: jnp.ndarray) -> jnp.ndarray:
    """Sequential (left-fold) sum over the last dim — a *defined* reduction
    order, so results are bit-identical to a sequential CPU oracle. XLA's
    default reduce reassociates; the north star requires reproducible float
    reductions (SURVEY.md §7 'bit-identical float reductions')."""
    xt = jnp.moveaxis(x, -1, 0)
    return jax.lax.scan(lambda acc, v: (acc + v, None),
                        jnp.zeros(xt.shape[1:], x.dtype), xt)[0]


@jax.jit
def l2_distance_rowwise(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Row-to-row l2_distance(a[i], b[i]) — the SQL scalar function shape
    (`SELECT l2_distance(col, const)`), f64 accumulation in defined
    sequential order (reference CPU path: moarray/external.go:181)."""
    d = a.astype(jnp.float64) - b.astype(jnp.float64)
    return jnp.sqrt(_seq_sum_lastdim(d * d))


@jax.jit
def inner_product_rowwise(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return _seq_sum_lastdim(a.astype(jnp.float64) * b.astype(jnp.float64))


@jax.jit
def cosine_distance_rowwise(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    a64, b64 = a.astype(jnp.float64), b.astype(jnp.float64)
    num = _seq_sum_lastdim(a64 * b64)
    den = jnp.sqrt(_seq_sum_lastdim(a64 * a64) * _seq_sum_lastdim(b64 * b64))
    return 1.0 - num / den


@partial(jax.jit, static_argnames=("compute_dtype",))
def inner_product(x: jnp.ndarray, q: jnp.ndarray, compute_dtype=None) -> jnp.ndarray:
    """Pairwise inner products [n, b]."""
    return _matmul_xqT(x, q, compute_dtype)


def normalize(x: jnp.ndarray, eps: float = 1e-30) -> jnp.ndarray:
    """L2-normalize rows (host-side prep for cosine -> inner product)."""
    n = jnp.linalg.norm(x.astype(jnp.float32), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) / jnp.maximum(n, eps)).astype(x.dtype)


@partial(jax.jit, static_argnames=("compute_dtype",))
def cosine_distance(x: jnp.ndarray, q: jnp.ndarray,
                    compute_dtype=None) -> jnp.ndarray:
    """Pairwise cosine distance [n, b] = 1 - cos_similarity."""
    xq = _matmul_xqT(x, q, compute_dtype)
    xn = jnp.linalg.norm(x.astype(jnp.float32), axis=-1, keepdims=True)
    qn = jnp.linalg.norm(q.astype(jnp.float32), axis=-1)
    den = jnp.maximum(xn * qn[None, :], 1e-30)
    return 1.0 - xq / den
