"""IVF-PQ index: product-quantized residuals + ADC scoring on TPU.

Reference analogue: `cgo/cuvs/ivf_pq_c.cpp` (the reference's headline GPU
index — 759 QPS @ 88M on 8xL40S, blog.md:155) + `pkg/cuvs/ivf_pq.go`.
TPU redesign:

 * build: coarse k-means (kmeans.py) -> residuals -> per-subspace k-means
   (all on the MXU) -> uint8 codes, cluster-major CSR like ivf_flat;
   memory = M bytes/vector (768d M=96: 16x smaller than bf16 flat);
 * search: asymmetric distance computation — per (query, probed cluster)
   a [M, 256] lookup table of sub-distances (one small matmul), then
   candidate scores are gather-sums of LUT entries over the code bytes:
   ||x-q||^2 ~= sum_m ||q_m - c_m - codebook[m, code_m]||^2.

Recall loss vs IVF-Flat is the PQ quantization error (same tradeoff the
reference ships); exact re-rank of the final k recovers ordering when the
caller holds the raw vectors (the SQL layer's Project recompute does).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.ops import distance as D
from matrixone_tpu.ops import kernels as HK
from matrixone_tpu.vectorindex import kmeans

METRIC_L2 = "l2"
METRIC_COSINE = "cosine"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class IvfPqIndex:
    centroids: jnp.ndarray    # [nlist, d] f32 coarse centroids
    codebooks: jnp.ndarray    # [M, 256, ds] f32 per-subspace codebooks
    codes: jnp.ndarray        # [n, M] uint8, cluster-major
    ids: jnp.ndarray          # [n] int32 original row position
    offsets: jnp.ndarray      # [nlist+1] int32 CSR
    metric: str = METRIC_L2
    max_cluster_size: int = 0
    n: int = 0

    def tree_flatten(self):
        return ((self.centroids, self.codebooks, self.codes, self.ids,
                 self.offsets),
                (self.metric, self.max_cluster_size, self.n))

    @classmethod
    def tree_unflatten(cls, aux, children):
        metric, mcs, n = aux
        c, cb, co, i, o = children
        return cls(centroids=c, codebooks=cb, codes=co, ids=i, offsets=o,
                   metric=metric, max_cluster_size=mcs, n=n)

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]

    @property
    def dim(self) -> int:
        return self.centroids.shape[1]

    @property
    def n_subspaces(self) -> int:
        return self.codebooks.shape[0]


def build(dataset: jnp.ndarray, nlist: int, n_subspaces: int = 16,
          metric: str = METRIC_L2, n_iter: int = 10, pq_iter: int = 8,
          seed: int = 0, balance_weight: float = 0.3,
          kmeans_sample: Optional[int] = 262144,
          compute_dtype=jnp.bfloat16,
          max_list_factor: Optional[float] = 4.0) -> IvfPqIndex:
    if metric not in (METRIC_L2, METRIC_COSINE):
        raise ValueError(
            f"ivf_pq supports l2/cosine metrics only (got {metric!r}); "
            f"inner-product ADC needs a dedicated formulation")
    n, d = dataset.shape
    if d % n_subspaces != 0:
        raise ValueError(
            f"dim {d} must divide into n_subspaces={n_subspaces}")
    ds = d // n_subspaces
    data = jnp.asarray(dataset, jnp.float32)
    if metric == METRIC_COSINE:
        data = D.normalize(data)
    km = kmeans.fit(data, nlist, n_iter=n_iter, seed=seed,
                    balance_weight=balance_weight, sample=kmeans_sample,
                    compute_dtype=compute_dtype,
                    final_assign=max_list_factor is None)
    if max_list_factor is not None:
        labels, counts, _ = kmeans.capped_labels(
            data, km.centroids, nlist, max_list_factor,
            compute_dtype=compute_dtype)
    else:
        labels = km.labels
        counts = km.cluster_sizes
    order = jnp.argsort(labels).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    sorted_vecs = data[order]
    residuals = sorted_vecs - km.centroids[labels[order]]   # [n, d]

    # per-subspace k-means over residual slices (256 codes = 8 bits)
    k_pq = min(256, max(2, n))
    codebooks, codes = [], []
    for m in range(n_subspaces):
        sub = residuals[:, m * ds:(m + 1) * ds]
        skm = kmeans.fit(sub, k_pq, n_iter=pq_iter,
                         seed=seed + 1000 + m, sample=kmeans_sample,
                         compute_dtype=None)
        cb = skm.centroids
        if k_pq < 256:   # pad codebook so codes stay uint8-addressable
            cb = jnp.concatenate(
                [cb, jnp.full((256 - k_pq, ds), 1e10, jnp.float32)])
        codebooks.append(cb)
        codes.append(skm.labels.astype(jnp.uint8))
    codebooks = jnp.stack(codebooks)               # [M, 256, ds]
    codes = jnp.stack(codes, axis=1)               # [n, M]

    max_cs = int(jnp.max(counts))
    max_cs = ((max_cs + 127) // 128) * 128
    return IvfPqIndex(centroids=km.centroids, codebooks=codebooks,
                      codes=codes, ids=order, offsets=offsets,
                      metric=metric, max_cluster_size=max_cs, n=n)


@partial(jax.jit, static_argnames=("k", "nprobe", "query_chunk",
                                   "compute_dtype"))
def _search(index: IvfPqIndex, queries: jnp.ndarray, k: int, nprobe: int,
            query_chunk: int = 32,
            compute_dtype=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, d = queries.shape
    M = index.n_subspaces
    ds = d // M
    q = queries.astype(jnp.float32)
    if index.metric == METRIC_COSINE:
        q = D.normalize(q)
    cdist = D.l2_distance_sq(q, index.centroids)
    _, probes = jax.lax.top_k(-cdist, nprobe)      # [b, nprobe]

    pad = index.max_cluster_size
    n_chunks = b // query_chunk
    q_chunks = q.reshape(n_chunks, query_chunk, d)
    probe_chunks = probes.reshape(n_chunks, query_chunk, nprobe)

    def step(_, inp):
        qc, pc = inp                                # [qc,d], [qc,nprobe]
        # residual queries per probed cluster: [qc, nprobe, d]
        qr = qc[:, None, :] - index.centroids[pc]
        qr_sub = qr.reshape(query_chunk, nprobe, M, ds)
        # LUT[q,p,m,j] = ||qr_sub - codebook[m,j]||^2  via the matmul trick
        cb = index.codebooks                         # [M, 256, ds]
        if compute_dtype is not None:
            dots = jnp.einsum("qpmd,mjd->qpmj",
                              qr_sub.astype(compute_dtype),
                              cb.astype(compute_dtype),
                              preferred_element_type=jnp.float32)
        else:
            dots = jnp.einsum("qpmd,mjd->qpmj", qr_sub, cb,
                              preferred_element_type=jnp.float32)
        cb2 = jnp.sum(cb * cb, axis=-1)              # [M, 256]
        qr2 = jnp.sum(qr_sub * qr_sub, axis=-1)      # [qc, nprobe, M]
        lut = qr2[..., None] + cb2[None, None] - 2.0 * dots
        # candidates
        starts = index.offsets[pc]
        ends = index.offsets[pc + 1]
        lane = jnp.arange(pad, dtype=jnp.int32)
        cand = starts[:, :, None] + lane[None, None, :]
        valid = cand < ends[:, :, None]
        cand = jnp.where(valid, cand, 0)             # [qc, nprobe, pad]
        cand_codes = index.codes[cand]               # [qc, nprobe, pad, M]
        # dist = sum_m LUT[..., m, code_m]
        g = query_chunk * nprobe
        dist = HK.adc_scores(
            cand_codes.reshape(g, pad, M), lut.reshape(g, M, 256)
        ).reshape(query_chunk, nprobe, pad)
        dist = jnp.where(valid, dist, jnp.inf)
        # two-stage top-k (same shape argument as ivf_flat: the top-k of
        # the probe union is contained in the union of per-probe top-ks)
        kk = min(k, pad)
        s1, p1 = jax.lax.top_k(-dist, kk)              # [qc, nprobe, kk]
        c1 = jnp.take_along_axis(cand, p1, axis=2)
        s1f = s1.reshape(query_chunk, nprobe * kk)
        c1f = c1.reshape(query_chunk, nprobe * kk)
        top_s, top_pos = jax.lax.top_k(s1f, min(k, nprobe * kk))
        top_cand = jnp.take_along_axis(c1f, top_pos, axis=1)
        return None, (-top_s, index.ids[top_cand].astype(jnp.int32))

    _, (dists, ids) = jax.lax.scan(step, None, (q_chunks, probe_chunks))
    return dists.reshape(b, -1), ids.reshape(b, -1)


def search(index: IvfPqIndex, queries: jnp.ndarray, k: int, nprobe: int,
           query_chunk: int = 32,
           compute_dtype=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched ADC search -> (approx distances [b,k], row positions [b,k]).

    Same batch contract as ivf_flat.search: any b works, padded
    internally to the next power of two."""
    from matrixone_tpu.utils import metrics as Mx
    from matrixone_tpu.vectorindex.ivf_flat import _bucket_batch
    b, d = queries.shape
    target, qc_eff = _bucket_batch(b, query_chunk)
    q = jnp.asarray(queries)
    if target != b:
        q = jnp.concatenate([q, jnp.zeros((target - b, d), q.dtype)])
        Mx.vector_search_pad_rows.inc(target - b)
    Mx.vector_search_queries.inc(b)
    dists, ids = _search(index, q, k, nprobe, query_chunk=qc_eff,
                         compute_dtype=compute_dtype)
    if target != b:
        dists, ids = dists[:b], ids[:b]
    return dists, ids
