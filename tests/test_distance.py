"""Distance kernels vs numpy oracle (reference: moarray/external_test.go)."""

import jax.numpy as jnp
import numpy as np
import pytest

from matrixone_tpu.ops import distance as D


def test_l2_pairwise_matches_numpy(rng):
    x = rng.standard_normal((256, 64)).astype(np.float32)
    q = rng.standard_normal((8, 64)).astype(np.float32)
    got = np.asarray(D.l2_distance(jnp.asarray(x), jnp.asarray(q)))
    expect = np.linalg.norm(x[:, None, :] - q[None, :, :], axis=-1)
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-4)


def test_l2_rowwise_bit_exactness(rng):
    # the SQL scalar path accumulates in f64 in *sequential* order; the
    # oracle is the same left-fold on the host -> bit-identical
    a = rng.standard_normal((100, 32)).astype(np.float32)
    b = rng.standard_normal((100, 32)).astype(np.float32)
    got = np.asarray(D.l2_distance_rowwise(jnp.asarray(a), jnp.asarray(b)))
    sq = (a.astype(np.float64) - b.astype(np.float64)) ** 2
    acc = np.zeros(100, np.float64)
    for j in range(sq.shape[1]):   # defined left-fold order
        acc = acc + sq[:, j]
    expect = np.sqrt(acc)
    np.testing.assert_array_equal(got, expect)


def test_cosine_pairwise(rng):
    x = rng.standard_normal((128, 48)).astype(np.float32)
    q = rng.standard_normal((4, 48)).astype(np.float32)
    got = np.asarray(D.cosine_distance(jnp.asarray(x), jnp.asarray(q)))
    xn = x / np.linalg.norm(x, axis=-1, keepdims=True)
    qn = q / np.linalg.norm(q, axis=-1, keepdims=True)
    expect = 1.0 - xn @ qn.T
    np.testing.assert_allclose(got, expect, rtol=1e-4, atol=1e-4)


def test_inner_product(rng):
    x = rng.standard_normal((64, 16)).astype(np.float32)
    q = rng.standard_normal((4, 16)).astype(np.float32)
    got = np.asarray(D.inner_product(jnp.asarray(x), jnp.asarray(q)))
    np.testing.assert_allclose(got, x @ q.T, rtol=1e-4, atol=1e-6)


def test_bf16_compute_close(rng):
    x = rng.standard_normal((256, 128)).astype(np.float32)
    q = rng.standard_normal((8, 128)).astype(np.float32)
    exact = np.asarray(D.l2_distance_sq(jnp.asarray(x), jnp.asarray(q)))
    fast = np.asarray(D.l2_distance_sq(jnp.asarray(x), jnp.asarray(q),
                                       compute_dtype=jnp.bfloat16))
    # bf16 matmul with f32 accumulation: relative error ~1e-2
    np.testing.assert_allclose(fast, exact, rtol=0.1, atol=0.5)


def test_hash_determinism_and_spread(rng):
    from matrixone_tpu.ops import hash as H
    x = jnp.asarray(np.arange(10000, dtype=np.int64))
    h1 = np.asarray(H.hash_column(x))
    h2 = np.asarray(H.hash_column(x))
    np.testing.assert_array_equal(h1, h2)
    assert len(np.unique(h1)) == 10000  # no collisions on consecutive ints
    # low bits well distributed
    low = h1 % 16
    counts = np.bincount(low.astype(np.int64), minlength=16)
    assert counts.min() > 400


@pytest.mark.parametrize("n,d,b", [(2048, 64, 16), (2048, 128, 16),
                                   (1024, 16, 4)])
def test_l2_distance_sq_matches_float64_numpy(n, d, b):
    """The shapes the deleted Pallas l2 kernel was held to, for the XLA
    formulation that stays: float64 numpy is the reference, and
    self-pairs clamp at zero."""
    rng = np.random.default_rng(n + d + b)
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = np.concatenate([rng.standard_normal((b - 1, d)).astype(np.float32),
                        x[:1]])
    got = np.asarray(D.l2_distance_sq(jnp.asarray(x), jnp.asarray(q)))
    want = ((x.astype(np.float64)[:, None, :]
             - q.astype(np.float64)[None, :, :]) ** 2).sum(-1)
    assert got.dtype == np.float32 and got.shape == (n, b)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert (got >= 0).all() and got[0, b - 1] <= 1e-4
