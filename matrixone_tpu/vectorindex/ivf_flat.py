"""IVF-Flat index: build + batched search, all on TPU.

TPU-native replacement for the reference's IVF-Flat stack:
`pkg/vectorindex/ivfflat/{build,search}.go` (CPU, SQL re-entry per query),
`cgo/cuvs/ivf_flat_c.cpp` (GPU worker). Design differences, all deliberate:

 * build = k-means on the MXU (kmeans.py) + one argsort: vectors are stored
   *cluster-major* (sorted by label) with CSR offsets — the "inverted lists"
   are contiguous slices, so probing a cluster is a dense dynamic-slice
   gather, never pointer chasing;
 * storage is *residual-encoded* (r = x - centroid, the IVF-PQ trick,
   cgo/cuvs residual quantization analogue): ||x-q||^2 = ||c-q||^2 +
   ||r||^2 + 2 r.c - 2 r.q, where ||c-q||^2 comes free from the probe
   stage and ||r||^2, r.c are f32 scalars precomputed at build — the only
   low-precision term is the r.q matmul over SMALL-magnitude residuals,
   so bf16 storage/compute loses ~0.2% of the score range instead of
   drowning neighbor gaps in quantization noise (measured: recall 0.42 ->
   1.0 on tight clusters);
 * search is batched: queries are processed in fixed-size chunks; each chunk
   top-nprobes the centroid table (one matmul), gathers its probed clusters
   into a padded [chunk, nprobe, pad, d] tensor, and scores candidates
   PER QUERY — a batched [pad, d] @ [d] contraction (einsum), NOT the
   seed's [qc, m] x [qc, d] -> [qc, m, qc] matmul that computed every
   query's score against every OTHER query's candidates and kept only the
   diagonal: a query_chunk-fold (32x) flops waste that kept the MXU busy
   doing nothing (r05 roofline: 0.0045 TFLOPS achieved). Top-k is
   two-stage: per-probe partial top-k (over pad lanes) then a global merge
   over nprobe*k — the full nprobe*pad sort never happens;
 * optional exact re-rank of the final k in f64 sequential order makes
   results bit-identical to the CPU scalar path (BASELINE.json requirement).

The index is a pytree of device arrays — it lives in HBM between queries,
exactly like the cuvs_worker_t's persistent device-resident indexes
(`cgo/cuvs/README.md`). For multi-chip serving see vectorindex/sharded.py
(cluster-sharded inverted lists over the parallel/mesh.py mesh).

Batch contract: `search` pads any batch size internally to the next
power of two and strips pad rows before returning — callers no longer
carry host-side padding code, and dynamic batch sizes reuse a small set
of compiled shapes (the cuvs compile-cache role).
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.ops import distance as D
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.vectorindex import kmeans

METRIC_L2 = "l2"
METRIC_COSINE = "cosine"
METRIC_IP = "ip"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IvfFlatIndex:
    centroids: jnp.ndarray   # [nlist, d] f32
    vectors: jnp.ndarray     # [n, d] RESIDUALS x - c, cluster-major (storage dtype)
    r_norm2: jnp.ndarray     # [n] f32 ||r||^2
    r_dot_c: jnp.ndarray     # [n] f32 r . centroid (l2 metric)
    ids: jnp.ndarray         # [n] int32 original row position
    offsets: jnp.ndarray     # [nlist+1] int32 CSR into vectors
    # static:
    metric: str = METRIC_L2
    max_cluster_size: int = 0
    n: int = 0

    def tree_flatten(self):
        return ((self.centroids, self.vectors, self.r_norm2, self.r_dot_c,
                 self.ids, self.offsets),
                (self.metric, self.max_cluster_size, self.n))

    @classmethod
    def tree_unflatten(cls, aux, children):
        metric, mcs, n = aux
        c, v, rn, rc, i, o = children
        return cls(centroids=c, vectors=v, r_norm2=rn, r_dot_c=rc, ids=i,
                   offsets=o, metric=metric, max_cluster_size=mcs, n=n)

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]


def build(dataset: jnp.ndarray, nlist: int, metric: str = METRIC_L2,
          n_iter: int = 10, seed: int = 0, storage_dtype=None,
          balance_weight: float = 0.3, kmeans_sample: Optional[int] = 262144,
          compute_dtype=jnp.bfloat16,
          max_list_factor: Optional[float] = 4.0,
          kmeans_minibatch: Optional[int] = None,
          balance_mode: str = "cap",
          target_list_size: int = 224,
          mesh=None) -> IvfFlatIndex:
    """Build an IVF-Flat index on device.

    cosine metric stores normalized vectors (cosine -> inner product), the
    same trick the reference applies in vectorindex/metric.

    max_list_factor HARD-caps every inverted list at factor * ceil(n/nlist)
    rows (overflow points go to their next-nearest centroid). The cap is
    what bounds search memory: the probe gather is [chunk, nprobe * cap, d],
    so one runaway cluster would otherwise set the budget for every query
    (observed: a 42k-row cluster at mean 977 = 15.7 GB gather on v5e).

    kmeans_minibatch rotates Lloyd iterations through fixed-size blocks of
    the training sample (see kmeans.fit) — the big build_seconds lever.
    mesh (parallel/mesh.py) parallelizes the full-dataset assignment pass
    across devices. Build stages are metered in mo_vector_build_seconds.

    balance_mode picks how oversized lists are bounded:
      "cap"   — capped_labels relocation to the next-nearest centroid
                (seed behavior; bounded memory, costs recall on strongly
                clustered data);
      "split" — kmeans.split_oversized: big clusters become local child
                clusters capped at target_list_size (recall goes UP and
                the padded gather budget shrinks ~3x; nlist grows by the
                number of extra children). The serving-bench default.
    """
    n, d = dataset.shape
    data = jnp.asarray(dataset)
    if metric == METRIC_COSINE:
        data = D.normalize(data)
    t0 = time.perf_counter()
    km = kmeans.fit(data, nlist, n_iter=n_iter, seed=seed,
                    balance_weight=balance_weight, sample=kmeans_sample,
                    compute_dtype=compute_dtype,
                    minibatch=kmeans_minibatch,
                    final_assign=(max_list_factor is None
                                  or balance_mode == "split"))
    jax.block_until_ready(km.centroids)
    M.vector_build_seconds.inc(time.perf_counter() - t0, stage="kmeans")
    t0 = time.perf_counter()
    centroids = km.centroids
    if balance_mode == "split":
        cents2, labels2, _cap = kmeans.split_oversized(
            np.asarray(data), np.asarray(centroids), np.asarray(km.labels),
            target=target_list_size, seed=seed)
        centroids = jnp.asarray(cents2)
        labels = jnp.asarray(labels2)
        counts = jnp.asarray(np.bincount(
            labels2, minlength=len(cents2)).astype(np.int32))
        nlist = len(cents2)
    elif max_list_factor is not None:
        labels, counts, _ = kmeans.capped_labels(
            data, centroids, nlist, max_list_factor,
            compute_dtype=compute_dtype, mesh=mesh)
    else:
        labels = km.labels
        counts = km.cluster_sizes
    jax.block_until_ready(counts)
    M.vector_build_seconds.inc(time.perf_counter() - t0, stage="assign")
    t0 = time.perf_counter()
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    order, residuals, r_norm2, r_dot_c = _pack(data, centroids, labels,
                                               storage_dtype)
    max_cs = int(jnp.max(counts))
    max_cs = ((max_cs + 127) // 128) * 128  # lane-align the gather budget
    index = IvfFlatIndex(centroids=centroids, vectors=residuals,
                         r_norm2=r_norm2, r_dot_c=r_dot_c, ids=order,
                         offsets=offsets, metric=metric,
                         max_cluster_size=max_cs, n=n)
    jax.block_until_ready(index.vectors)
    M.vector_build_seconds.inc(time.perf_counter() - t0, stage="pack")
    return index


@partial(jax.jit, static_argnames=("storage_dtype",))
def _pack(data, centroids, labels, storage_dtype):
    """Cluster-major residual encoding as ONE program.  Op by op, the
    sorted copy, the gathered centroids, the residuals and the square
    each hold an [n, d] f32 array beside the data: five of them at
    1M x 768 peaked at 15.6 of the v5e's 16 GB (chip_smoke, PR 25); the
    fused program needs the data, one temporary and the result."""
    order = jnp.argsort(labels).astype(jnp.int32)
    sorted_vecs = data[order].astype(jnp.float32)
    sorted_centroids = centroids[labels[order]]
    residuals = sorted_vecs - sorted_centroids          # small magnitude
    r_norm2 = jnp.sum(jnp.square(residuals), axis=-1)
    r_dot_c = jnp.sum(residuals * sorted_centroids, axis=-1)
    if storage_dtype is not None:
        residuals = residuals.astype(storage_dtype)
    return order, residuals, r_norm2, r_dot_c


def _bucket_batch(b: int, query_chunk: int) -> Tuple[int, int]:
    """(padded batch, effective chunk): batches pad up to the next power
    of two so dynamic sizes reuse a small set of compiled shapes, and the
    chunk never exceeds the padded batch (a 1-query SQL lookup compiles a
    1-row kernel, not a 32-row one). The effective chunk is rounded DOWN
    to a power of two so it always divides the padded batch — a caller's
    query_chunk=48 must not crash the chunk reshape."""
    target = max(1, 1 << (max(b, 1) - 1).bit_length())
    qc = max(1, min(query_chunk, target))
    return target, 1 << (qc.bit_length() - 1)


def _probe(index: IvfFlatIndex, q: jnp.ndarray, nprobe: int):
    """Stage 1: centroid scores + top-nprobe clusters per query.
    Full f32 precision: these scores re-enter the candidate distances."""
    if index.metric == METRIC_L2:
        cdist = D.l2_distance_sq(index.centroids, q).T      # [b, nlist]
    else:
        cdist = -D.inner_product(q, index.centroids)
    cprobe_scores, probes = jax.lax.top_k(-cdist, nprobe)  # [b, nprobe]
    return -cprobe_scores, probes                      # ||c-q||^2 / -c.q


def _score_chunk(index: IvfFlatIndex, qc, pc, cs, pmask, k: int,
                 compute_dtype, exact: bool = False, clusters=None):
    """Score one query chunk's probed clusters and return its top-k.

    qc [qc, d] queries, pc [qc, nprobe] probed cluster ids, cs [qc, nprobe]
    probe-stage scores, pmask [qc, nprobe] live-probe mask (False lanes are
    ignored entirely — the sharded path masks probes owned by other
    devices). Per-query scoring + two-stage top-k (see module docstring).

    With `exact` the k survivors are re-scored from the index's own
    vectors (centroid + f32 residual) in elementwise float32 and re-sorted
    in the same program, so the distances returned are those of the stored
    rows to float32 rounding and no caller has to fetch the rows to
    re-rank them.  `clusters` [qc, nprobe] are the probes' ids in
    `index.centroids` where `pc` indexes another table (the sharded view's
    local slots); it defaults to `pc`.
    """
    query_chunk, nprobe = pc.shape
    pad = index.max_cluster_size
    starts = index.offsets[pc]                         # [qc, nprobe]
    ends = index.offsets[pc + 1]
    lane = jnp.arange(pad, dtype=jnp.int32)
    cand = starts[:, :, None] + lane[None, None, :]    # [qc, nprobe, pad]
    valid = (cand < ends[:, :, None]) & pmask[:, :, None]
    cand = jnp.where(valid, cand, 0)
    vecs = index.vectors[cand]                         # [qc, nprobe, pad, d]
    # per-query candidate scoring: contract d for each query's own
    # candidates only ([pad, d] @ [d] batched over (query, probe))
    own = jnp.einsum("qpld,qd->qpl",
                     vecs.astype(compute_dtype), qc.astype(compute_dtype),
                     preferred_element_type=jnp.float32)
    # residual decomposition: ||x-q||^2 = ||c-q||^2 + ||r||^2
    #                                    + 2 r.c - 2 r.q
    #          (ip/cosine):      x.q    = c.q + r.q
    if index.metric == METRIC_L2:
        rn = index.r_norm2[cand]
        rc = index.r_dot_c[cand]
        dist = jnp.maximum(cs[:, :, None] + rn + 2.0 * rc - 2.0 * own, 0.0)
    else:
        dist = 1.0 - (-cs[:, :, None] + own)           # cs = -c.q
    dist = jnp.where(valid, dist, jnp.inf)
    # two-stage top-k: per-probe partial top-k, then merge nprobe*kk
    kk = min(k, pad)
    s1, p1 = jax.lax.top_k(-dist, kk)                  # [qc, nprobe, kk]
    c1 = jnp.take_along_axis(cand, p1, axis=2)
    s1f = s1.reshape(query_chunk, nprobe * kk)
    c1f = c1.reshape(query_chunk, nprobe * kk)
    top_s, top_pos = jax.lax.top_k(s1f, min(k, nprobe * kk))
    top_cand = jnp.take_along_axis(c1f, top_pos, axis=1)
    top_ids = index.ids[top_cand].astype(jnp.int32)
    if not exact:
        return -top_s, top_ids
    with jax.named_scope("ivf_rerank_exact"):
        own_cluster = jnp.take_along_axis(
            pc if clusters is None else clusters, top_pos // kk, axis=1)
        x = index.centroids[own_cluster] \
            + index.vectors[top_cand].astype(jnp.float32)   # [qc, k, d]
        q32 = qc.astype(jnp.float32)[:, None, :]
        if index.metric == METRIC_L2:
            diff = x - q32
            exact_d = jnp.sum(diff * diff, axis=-1)
        else:
            exact_d = 1.0 - jnp.sum(x * q32, axis=-1)
        exact_d = jnp.where(jnp.isfinite(top_s), exact_d, jnp.inf)
        order = jnp.argsort(exact_d, axis=1)
        return (jnp.take_along_axis(exact_d, order, axis=1),
                jnp.take_along_axis(top_ids, order, axis=1))


@partial(jax.jit, static_argnames=("k", "nprobe", "query_chunk",
                                   "compute_dtype", "exact"))
def _search(index: IvfFlatIndex, queries: jnp.ndarray, k: int, nprobe: int,
            query_chunk: int, compute_dtype, exact: bool = False):
    b, d = queries.shape
    q = queries.astype(jnp.float32)
    if index.metric == METRIC_COSINE:
        q = D.normalize(q)
    cprobe_scores, probes = _probe(index, q, nprobe)
    n_chunks = b // query_chunk
    q_chunks = q.reshape(n_chunks, query_chunk, d)
    probe_chunks = probes.reshape(n_chunks, query_chunk, nprobe)
    cscore_chunks = cprobe_scores.reshape(n_chunks, query_chunk, nprobe)
    pmask = jnp.ones((query_chunk, nprobe), jnp.bool_)

    def step(_, inp):
        qc, pc, cs = inp
        return None, _score_chunk(index, qc, pc, cs, pmask, k,
                                  compute_dtype, exact)

    _, (dists, ids) = jax.lax.scan(
        step, None, (q_chunks, probe_chunks, cscore_chunks))
    return dists.reshape(b, -1), ids.reshape(b, -1)


def search(index: IvfFlatIndex, queries: jnp.ndarray, k: int, nprobe: int,
           query_chunk: int = 32, compute_dtype=jnp.bfloat16,
           exact: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched IVF search -> (distances [b,k], row_positions [b,k] int32).

    With `exact` the k results carry float32 distances recomputed from
    the stored vectors and are ordered by them (`_score_chunk`): the
    search and its exact re-rank are one device program.

    Distances are squared l2 (metric=l2) or 1-ip (cosine/ip). Any batch
    size b works: queries are padded internally to the next power of two
    (pad rows are zero queries whose results are stripped before return),
    so callers never carry padding code and compiled-shape reuse is
    bounded at log2(max batch) entries.
    """
    b, d = queries.shape
    target, qc_eff = _bucket_batch(b, query_chunk)
    q = jnp.asarray(queries)
    if target != b:
        q = jnp.concatenate([q, jnp.zeros((target - b, d), q.dtype)])
        M.vector_search_pad_rows.inc(target - b)
    M.vector_search_queries.inc(b)
    dists, ids = _search(index, q, k, nprobe, qc_eff, compute_dtype,
                         exact)
    if target != b:
        dists, ids = dists[:b], ids[:b]
    return dists, ids


_probe_jit = jax.jit(_probe, static_argnames=("nprobe",))
_score_jit = jax.jit(_score_chunk, static_argnames=("k", "compute_dtype"))


def search_profiled(index: IvfFlatIndex, queries: jnp.ndarray, k: int,
                    nprobe: int, query_chunk: int = 32,
                    compute_dtype=jnp.bfloat16) -> dict:
    """Diagnostic re-execution of the search pipeline with a device sync
    between stages, attributing wall time to probe / score / merge.
    NOT the serving path (the fused `search` kernel is) — bench.py runs
    this once per round to fill the mo_vector_search_seconds stage
    counters and the per-stage JSON breakdown."""
    b, d = queries.shape
    target, qc_eff = _bucket_batch(b, query_chunk)
    q = jnp.asarray(queries, jnp.float32)
    if target != b:
        q = jnp.concatenate([q, jnp.zeros((target - b, d), q.dtype)])
    if index.metric == METRIC_COSINE:
        q = D.normalize(q)
    probe_fn = _probe_jit
    score_fn = _score_jit
    pmask = jnp.ones((qc_eff, nprobe), jnp.bool_)
    # warm the compile caches so stage times measure execution, not XLA
    jax.block_until_ready(probe_fn(index, q, nprobe=nprobe))
    t0 = time.perf_counter()
    cs, probes = probe_fn(index, q, nprobe=nprobe)
    jax.block_until_ready(probes)
    t_probe = time.perf_counter() - t0
    jax.block_until_ready(score_fn(index, q[:qc_eff], probes[:qc_eff],
                                   cs[:qc_eff], pmask, k=k,
                                   compute_dtype=compute_dtype))
    t_score = 0.0
    parts = []
    t0 = time.perf_counter()
    for i in range(0, target, qc_eff):
        out = score_fn(index, q[i:i + qc_eff], probes[i:i + qc_eff],
                       cs[i:i + qc_eff], pmask, k=k,
                       compute_dtype=compute_dtype)
        parts.append(out)
    jax.block_until_ready(parts[-1])
    t_score = time.perf_counter() - t0
    t0 = time.perf_counter()
    dists = np.concatenate([np.asarray(p[0]) for p in parts])[:b]
    ids = np.concatenate([np.asarray(p[1]) for p in parts])[:b]
    t_merge = time.perf_counter() - t0
    M.vector_search_seconds.inc(t_probe, stage="probe")
    M.vector_search_seconds.inc(t_score, stage="score")
    M.vector_search_seconds.inc(t_merge, stage="merge")
    return {"probe_seconds": t_probe, "score_seconds": t_score,
            "merge_seconds": t_merge, "dists": dists, "ids": ids}


def rerank_exact(dataset: jnp.ndarray, queries: jnp.ndarray,
                 ids: jnp.ndarray, metric: str = METRIC_L2,
                 valid: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Re-score candidate ids with the f64 sequential-order rowwise kernel
    and re-sort — final (distances, ids) are bit-identical to the CPU scalar
    path (`l2_distance` SQL function) applied to the same candidates.
    `valid` masks padded candidate lanes (their ids are CLAMPED
    duplicates): invalid lanes keep inf distance and sort last."""
    b, k = ids.shape
    cand = dataset[ids.reshape(-1)].reshape(b, k, -1)
    qe = jnp.repeat(queries[:, None, :], k, axis=1)
    if metric == METRIC_L2:
        dist = D.l2_distance_rowwise(cand.reshape(b * k, -1),
                                     qe.reshape(b * k, -1)).reshape(b, k)
    elif metric == METRIC_COSINE:
        dist = D.cosine_distance_rowwise(cand.reshape(b * k, -1),
                                         qe.reshape(b * k, -1)).reshape(b, k)
    else:
        dist = -D.inner_product_rowwise(cand.reshape(b * k, -1),
                                        qe.reshape(b * k, -1)).reshape(b, k)
    if valid is not None:
        dist = jnp.where(valid, dist, jnp.inf)
    order = jnp.argsort(dist, axis=1)
    return (jnp.take_along_axis(dist, order, axis=1),
            jnp.take_along_axis(ids, order, axis=1))
