"""Group-by / sort / top-k kernels vs numpy oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from matrixone_tpu.ops import agg, filter as F, sort as msort
from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
from matrixone_tpu.container import dtypes as dt


def _pad(a, n, fill=0):
    a = np.asarray(a)
    out = np.full((n,) + a.shape[1:], fill, dtype=a.dtype)
    out[:len(a)] = a
    return out


def test_group_ids_and_seg_aggs(rng):
    n, padded, max_groups = 5000, 8192, 1024
    keys = rng.integers(0, 37, n).astype(np.int64)
    vals = rng.integers(-100, 100, n).astype(np.int64)
    row_mask = jnp.asarray(_pad(np.ones(n, bool), padded, False))
    gk = jnp.asarray(_pad(keys, padded))
    gv = jnp.asarray(_pad(vals, padded))

    gi = agg.group_ids([gk], [None], row_mask, max_groups)
    assert int(gi.num_groups) == len(np.unique(keys))

    sums = agg.seg_sum(gv, gi.gids, row_mask, max_groups)
    counts = agg.seg_count(gi.gids, row_mask, max_groups)
    mins = agg.seg_min(gv, gi.gids, row_mask, max_groups)
    maxs = agg.seg_max(gv, gi.gids, row_mask, max_groups)
    rep_keys = np.asarray(gk[gi.rep_rows])

    # oracle
    for g in range(int(gi.num_groups)):
        k = rep_keys[g]
        sel = keys == k
        assert int(sums[g]) == vals[sel].sum()
        assert int(counts[g]) == sel.sum()
        assert int(mins[g]) == vals[sel].min()
        assert int(maxs[g]) == vals[sel].max()
    # each key appears exactly once as a representative
    assert sorted(rep_keys[:int(gi.num_groups)].tolist()) == sorted(np.unique(keys).tolist())


def test_group_by_multi_key_with_nulls(rng):
    n, padded, max_groups = 1000, 1024, 256
    k1 = rng.integers(0, 4, n).astype(np.int32)
    k2 = rng.integers(0, 3, n).astype(np.int64)
    k1_valid = rng.random(n) > 0.1
    row_mask = jnp.asarray(_pad(np.ones(n, bool), padded, False))
    gi = agg.group_ids(
        [jnp.asarray(_pad(k1, padded)), jnp.asarray(_pad(k2, padded))],
        [jnp.asarray(_pad(k1_valid, padded, False)), None],
        row_mask, max_groups)
    # oracle: distinct (k1-or-null, k2) pairs
    key_tuples = {(int(a) if v else None, int(b))
                  for a, b, v in zip(k1, k2, k1_valid)}
    assert int(gi.num_groups) == len(key_tuples)


def test_scalar_aggs(rng):
    n, padded = 777, 1024
    vals = rng.standard_normal(n)
    mask = jnp.asarray(_pad(np.ones(n, bool), padded, False))
    v = jnp.asarray(_pad(vals, padded))
    assert np.isclose(float(agg.scalar_sum(v, mask)), vals.sum())
    assert int(agg.scalar_count(mask)) == n
    assert float(agg.scalar_min(v, mask)) == vals.min()
    assert float(agg.scalar_max(v, mask)) == vals.max()


def test_sort_indices_multi_key(rng):
    n, padded = 500, 1024
    a = rng.integers(0, 5, n).astype(np.int64)
    b = rng.standard_normal(n)
    row_mask = jnp.asarray(_pad(np.ones(n, bool), padded, False))
    order = msort.sort_indices(
        [jnp.asarray(_pad(a, padded)), jnp.asarray(_pad(b, padded))],
        [None, None], [False, True], row_mask)
    got = np.asarray(order)[:n]
    expect = np.lexsort((-b, a))  # a asc, b desc
    np.testing.assert_array_equal(np.asarray(a)[got], a[expect])
    np.testing.assert_array_equal(np.asarray(b)[got], b[expect])


def test_top_k(rng):
    n, padded, k = 300, 1024, 10
    key = rng.standard_normal(n)
    row_mask = jnp.asarray(_pad(np.ones(n, bool), padded, False))
    idx, cnt = msort.top_k_indices(jnp.asarray(_pad(key, padded)), None,
                                   descending=False, row_mask=row_mask, k=k)
    assert int(cnt) == k
    got = np.sort(key[np.asarray(idx)])
    np.testing.assert_allclose(got, np.sort(key)[:k], rtol=1e-6)


def test_compact_and_gather(rng):
    n, padded = 100, 1024
    vals = np.arange(n, dtype=np.int64)
    db = DeviceBatch(
        columns={"x": DeviceColumn(jnp.asarray(_pad(vals, padded)),
                                   jnp.asarray(_pad(np.ones(n, bool), padded, False)),
                                   dt.INT64)},
        n_rows=jnp.asarray(n, jnp.int32))
    mask = db.columns["x"].data % 3 == 0
    mask = mask & db.row_mask()
    out = F.compact(db, mask, capacity=64)
    n_out = int(out.n_rows)
    assert n_out == len([v for v in vals if v % 3 == 0])
    np.testing.assert_array_equal(
        np.asarray(out.columns["x"].data)[:n_out], vals[vals % 3 == 0])


def test_top_k_bigint_precision():
    # int keys >= 2^53 must not collapse (regression: f32/f64 cast bug)
    import jax.numpy as jnp
    base = 2 ** 60
    vals = np.array([base, base + 1, base + 2, base - 1], dtype=np.int64)
    padded = 1024
    row_mask = jnp.asarray(_pad(np.ones(4, bool), padded, False))
    key = jnp.asarray(_pad(vals, padded))
    idx, cnt = msort.top_k_indices(key, None, descending=True,
                                   row_mask=row_mask, k=2)
    assert np.asarray(idx).tolist() == [2, 1]
    idx, _ = msort.top_k_indices(key, None, descending=False,
                                 row_mask=row_mask, k=2)
    assert np.asarray(idx).tolist() == [3, 0]


def test_sort_bigint_precision():
    import jax.numpy as jnp
    base = 2 ** 60
    vals = np.array([base + 2, base, base + 1], dtype=np.int64)
    padded = 1024
    row_mask = jnp.asarray(_pad(np.ones(3, bool), padded, False))
    order = msort.sort_indices([jnp.asarray(_pad(vals, padded))], [None],
                               [False], row_mask)
    assert np.asarray(order)[:3].tolist() == [1, 2, 0]


def test_minmax_bool():
    import jax.numpy as jnp
    vals = np.array([True, False, True, False])
    keys = np.array([0, 0, 1, 1], dtype=np.int64)
    padded = 1024
    mask = jnp.asarray(_pad(np.ones(4, bool), padded, False))
    gi = agg.group_ids([jnp.asarray(_pad(keys, padded))], [None], mask, 16)
    mn = agg.seg_min(jnp.asarray(_pad(vals, padded)), gi.gids, mask, 16)
    mx = agg.seg_max(jnp.asarray(_pad(vals, padded)), gi.gids, mask, 16)
    rep_keys = np.asarray(jnp.asarray(_pad(keys, padded))[gi.rep_rows])[:2]
    for g, k in enumerate(rep_keys):
        assert bool(mn[g]) == False  # both groups contain a False
        assert bool(mx[g]) == True
    assert bool(agg.scalar_min(jnp.asarray(_pad(vals, padded)), mask)) == False
    assert bool(agg.scalar_max(jnp.asarray(_pad(vals, padded)), mask)) == True


def test_sort_nulls_ordering():
    import jax.numpy as jnp
    vals = np.array([5, 3, 9, 7], dtype=np.int64)
    valid = np.array([True, False, True, True])
    padded = 1024
    row_mask = jnp.asarray(_pad(np.ones(4, bool), padded, False))
    v = jnp.asarray(_pad(vals, padded))
    va = jnp.asarray(_pad(valid, padded, False))
    # ASC: nulls first
    order = msort.sort_indices([v], [va], [False], row_mask)
    assert np.asarray(order)[:4].tolist() == [1, 0, 3, 2]
    # DESC: nulls last
    order = msort.sort_indices([v], [va], [True], row_mask)
    assert np.asarray(order)[:4].tolist() == [2, 3, 0, 1]


def _seg_sum_case(name):
    """-> (values, gids, mask, max_groups): the inputs the deleted Pallas
    segment-sum tests held their kernel to, for the XLA scatter."""
    rng = np.random.default_rng(21)

    def rows(n, groups, dtype=np.float32, live=0.8):
        return (rng.standard_normal(n).astype(dtype),
                rng.integers(0, groups, n).astype(np.int32),
                rng.random(n) < live, groups)
    if name == "f32_4096_rows_17_groups":
        return rows(4096, 17)
    if name == "f32_2048_rows_1_group":
        return rows(2048, 1)
    if name == "f32_8192_rows_512_groups":
        return rows(8192, 512)
    if name == "f32_1_group":
        return rows(5000, 1)
    if name == "f32_4000_groups":
        return rows(5000, 4000)
    if name == "f32_4096_groups":
        return rows(5000, 4096)
    if name == "zero_rows":
        return (np.zeros(0, np.float32), np.zeros(0, np.int32),
                np.zeros(0, bool), 8)
    if name == "masked_rows_never_leak":
        # every masked row carries an in-range gid and a large value
        return (np.full(2048, 100.0, np.float32), np.zeros(2048, np.int32),
                np.arange(2048) < 3, 4)
    if name == "int64_exact":
        return (np.array([1 << 40, 3, -7, 1 << 40], np.int64),
                np.array([0, 0, 1, 1], np.int32),
                np.array([True, True, True, False]), 2)
    assert name == "float64_exact"
    return (np.array([1e-17, 1.0, 1e-17], np.float64),
            np.zeros(3, np.int32), np.ones(3, bool), 1)


@pytest.mark.parametrize("name", [
    "f32_4096_rows_17_groups", "f32_2048_rows_1_group",
    "f32_8192_rows_512_groups", "f32_1_group", "f32_4000_groups",
    "f32_4096_groups", "zero_rows", "masked_rows_never_leak", "int64_exact",
    "float64_exact"])
def test_seg_sum_matches_numpy(name):
    v, g, m, groups = _seg_sum_case(name)
    got = np.asarray(agg.seg_sum(jnp.asarray(v), jnp.asarray(g),
                                 jnp.asarray(m), groups))
    assert got.dtype == v.dtype and got.shape == (groups,)
    if name == "float64_exact":      # one group: a left fold, as numpy's
        assert got[0] == np.float64(1e-17) + 1.0 + 1e-17
        return
    want = np.zeros(groups, np.int64 if v.dtype == np.int64 else np.float64)
    np.add.at(want, g[m], v[m])
    if v.dtype == np.int64:
        assert got.tolist() == want.tolist()
    else:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
