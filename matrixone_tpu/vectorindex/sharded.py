"""Cluster-sharded IVF serving across the device mesh.

The reference's cuVS worker scales one index across GPUs two ways
(`cgo/cuvs/README.md`): replicate (throughput) or shard (capacity). This
module is the shard mode done TPU-natively: the inverted lists of ONE
IvfFlatIndex are partitioned cluster-wise across the `parallel/mesh.py`
mesh (greedy size-balanced, so every chip carries ~1/S of the rows),
centroids are replicated, and `search_sharded` runs a `shard_map` program
where each device probes/scores/top-ks ONLY the clusters it owns, followed
by one small all-gather of [b, k] candidates and an on-device merge.

Correctness contract: every device computes the SAME global top-nprobe
probe list (replicated centroids + replicated queries), then keeps the
probes it owns. The union of per-device candidate sets is therefore
exactly the single-device candidate set, and a per-device top-k + global
merge of S*k candidates selects exactly the global top-k of that union —
sharded results are bit-identical to `ivf_flat.search` on the unsharded
index (modulo float near-ties; `rerank_exact` collapses even those).
`probe_capacity` < nprobe trades that guarantee for a 1/S per-device
probe budget (each device then scores at most `probe_capacity` of its
owned probes — the fast mode for latency-critical serving).

HBM math is the point: a sharded index stores n/S rows per chip, so an
index S times larger than one chip's HBM still serves from device memory
— the cuvs_worker_t capacity story, without the host round-trip.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from matrixone_tpu.ops import distance as D
from matrixone_tpu.utils import metrics as M
from matrixone_tpu.vectorindex.ivf_flat import (IvfFlatIndex, METRIC_COSINE,
                                                METRIC_L2, _bucket_batch,
                                                _score_chunk)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShardedIvfIndex:
    centroids: jnp.ndarray       # [nlist, d] f32, replicated
    owner: jnp.ndarray           # [nlist] i32, replicated: owning shard
    local_slot: jnp.ndarray      # [nlist] i32, replicated: slot in shard
    vectors: jnp.ndarray         # [S, rows_pad, d] sharded (residuals)
    r_norm2: jnp.ndarray         # [S, rows_pad] f32 sharded
    r_dot_c: jnp.ndarray         # [S, rows_pad] f32 sharded
    ids: jnp.ndarray             # [S, rows_pad] i32 sharded (global rows)
    local_offsets: jnp.ndarray   # [S, L+1] i32 sharded per-shard CSR
    # static:
    metric: str = METRIC_L2
    max_cluster_size: int = 0
    n: int = 0
    n_shards: int = 1
    mesh: object = None          # jax Mesh (hashable -> jit-static)

    def tree_flatten(self):
        return ((self.centroids, self.owner, self.local_slot, self.vectors,
                 self.r_norm2, self.r_dot_c, self.ids, self.local_offsets),
                (self.metric, self.max_cluster_size, self.n, self.n_shards,
                 self.mesh))

    @classmethod
    def tree_unflatten(cls, aux, children):
        metric, mcs, n, s, mesh = aux
        (c, ow, ls, v, rn, rc, i, lo) = children
        return cls(centroids=c, owner=ow, local_slot=ls, vectors=v,
                   r_norm2=rn, r_dot_c=rc, ids=i, local_offsets=lo,
                   metric=metric, max_cluster_size=mcs, n=n, n_shards=s,
                   mesh=mesh)

    @property
    def nlist(self) -> int:
        return self.centroids.shape[0]


def shard_ivf(index: IvfFlatIndex, mesh) -> ShardedIvfIndex:
    """Repack an IvfFlatIndex cluster-sharded over `mesh` ("shard" axis).

    Clusters are assigned greedily (largest first, to the lightest shard)
    so row counts balance regardless of the k-means outcome; the achieved
    max/mean row ratio is exported as mo_vector_shard_imbalance."""
    S = int(np.prod(mesh.devices.shape))
    offs = np.asarray(index.offsets)
    counts = np.diff(offs)
    nlist = index.nlist
    # greedy balance: biggest cluster to the currently lightest shard
    order = np.argsort(-counts, kind="stable")
    loads = np.zeros(S, np.int64)
    owner = np.zeros(nlist, np.int32)
    for c in order:
        s = int(np.argmin(loads))
        owner[c] = s
        loads[s] += int(counts[c])
    shard_clusters = [np.flatnonzero(owner == s) for s in range(S)]
    L = max(1, max(len(cl) for cl in shard_clusters))
    rows_pad = max(128, int(-(-int(loads.max()) // 128) * 128))
    d = index.dim
    vec_np = np.asarray(index.vectors)
    rn_np = np.asarray(index.r_norm2)
    rc_np = np.asarray(index.r_dot_c)
    ids_np = np.asarray(index.ids)
    vecs = np.zeros((S, rows_pad, d), vec_np.dtype)
    rns = np.zeros((S, rows_pad), rn_np.dtype)
    rcs = np.zeros((S, rows_pad), rc_np.dtype)
    gids = np.zeros((S, rows_pad), np.int32)
    lofs = np.zeros((S, L + 1), np.int32)
    local_slot = np.zeros(nlist, np.int32)
    for s, clusters in enumerate(shard_clusters):
        pos = 0
        for j, c in enumerate(clusters):
            local_slot[c] = j
            lo, hi = int(offs[c]), int(offs[c + 1])
            m = hi - lo
            vecs[s, pos:pos + m] = vec_np[lo:hi]
            rns[s, pos:pos + m] = rn_np[lo:hi]
            rcs[s, pos:pos + m] = rc_np[lo:hi]
            gids[s, pos:pos + m] = ids_np[lo:hi]
            lofs[s, j] = pos
            pos += m
        lofs[s, len(clusters):] = pos       # trailing empty clusters
    mean_rows = max(1.0, float(loads.mean()))
    M.vector_shard_imbalance.set(float(loads.max()) / mean_rows)
    row = NamedSharding(mesh, P("shard"))
    rep = NamedSharding(mesh, P())
    # the packed arrays go from the HOST straight to their shards: a
    # jnp.asarray first would land the whole index on the default device
    # (device 0 then holds S times its share until the copy is freed)
    return ShardedIvfIndex(
        centroids=jax.device_put(np.asarray(index.centroids), rep),
        owner=jax.device_put(owner, rep),
        local_slot=jax.device_put(local_slot, rep),
        vectors=jax.device_put(vecs, row),
        r_norm2=jax.device_put(rns, row),
        r_dot_c=jax.device_put(rcs, row),
        ids=jax.device_put(gids, row),
        local_offsets=jax.device_put(lofs, row),
        metric=index.metric, max_cluster_size=index.max_cluster_size,
        n=index.n, n_shards=S, mesh=mesh)


@partial(jax.jit, static_argnames=("k", "nprobe", "query_chunk",
                                   "compute_dtype", "probe_capacity",
                                   "exact"))
def _search_sharded(sidx: ShardedIvfIndex, queries: jnp.ndarray, k: int,
                    nprobe: int, query_chunk: int, compute_dtype,
                    probe_capacity: Optional[int], exact: bool = False):
    mesh = sidx.mesh
    b, d = queries.shape
    L = sidx.local_offsets.shape[1] - 1
    lp = min(nprobe, L) if probe_capacity is None \
        else max(1, min(probe_capacity, nprobe, L))

    def local(q, centroids, owner, local_slot, vectors, rn, rc, gids,
              lofs):
        s = jax.lax.axis_index("shard")
        vectors, rn, rc = vectors[0], rn[0], rc[0]
        gids, lofs = gids[0], lofs[0]
        # probe against the REPLICATED centroid table: every device
        # derives the same global top-nprobe list, then keeps its own
        if sidx.metric == METRIC_L2:
            cdist = D.l2_distance_sq(centroids, q).T        # [b, nlist]
        else:
            cdist = -D.inner_product(q, centroids)
        cscores, probes = jax.lax.top_k(-cdist, nprobe)
        cscores = -cscores
        own = owner[probes] == s                            # [b, nprobe]
        if lp < nprobe:
            # compact owned probes to the front, keep the first lp
            order = jnp.argsort(~own, axis=1, stable=True)[:, :lp]
            probes = jnp.take_along_axis(probes, order, axis=1)
            cscores = jnp.take_along_axis(cscores, order, axis=1)
            own = jnp.take_along_axis(own, order, axis=1)
        pc_local = local_slot[probes]                       # [b, lp]
        # local scoring via the SAME chunked kernel as single-device
        # search — a local index view whose CSR is this shard's packing
        view = IvfFlatIndex(
            centroids=centroids, vectors=vectors, r_norm2=rn, r_dot_c=rc,
            ids=gids, offsets=lofs, metric=sidx.metric,
            max_cluster_size=sidx.max_cluster_size, n=sidx.n)
        n_chunks = b // query_chunk
        qs = q.reshape(n_chunks, query_chunk, d)
        pcs = pc_local.reshape(n_chunks, query_chunk, lp)
        css = cscores.reshape(n_chunks, query_chunk, lp)
        owns = own.reshape(n_chunks, query_chunk, lp)
        gcs = probes.reshape(n_chunks, query_chunk, lp)

        def step(_, inp):
            qc, pcc, csc, ownc, gcc = inp
            return None, _score_chunk(view, qc, pcc, csc, ownc, k,
                                      compute_dtype, exact, clusters=gcc)

        _, (dl, il) = jax.lax.scan(step, None, (qs, pcs, css, owns, gcs))
        dl = dl.reshape(b, -1)
        il = il.reshape(b, -1)
        # one small collective: every device merges the same S*k union
        alld = jax.lax.all_gather(dl, "shard")              # [S, b, k]
        alli = jax.lax.all_gather(il, "shard")
        kk = dl.shape[1]
        alld = jnp.moveaxis(alld, 0, 1).reshape(b, -1)      # [b, S*kk]
        alli = jnp.moveaxis(alli, 0, 1).reshape(b, -1)
        top_s, top_pos = jax.lax.top_k(-alld, min(k, alld.shape[1]))
        return -top_s, jnp.take_along_axis(alli, top_pos, axis=1)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P(), P(), P("shard"), P("shard"), P("shard"),
                  P("shard"), P("shard")),
        out_specs=(P(), P()), check_vma=False)
    return fn(queries, sidx.centroids, sidx.owner, sidx.local_slot,
              sidx.vectors, sidx.r_norm2, sidx.r_dot_c, sidx.ids,
              sidx.local_offsets)


def search_sharded(sidx: ShardedIvfIndex, queries: jnp.ndarray, k: int,
                   nprobe: int, query_chunk: int = 32,
                   compute_dtype=jnp.bfloat16,
                   probe_capacity: Optional[int] = None,
                   exact: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sharded IVF search -> (distances [b,k], row_positions [b,k]).

    Same batch contract as ivf_flat.search (internal power-of-two
    padding). probe_capacity=None preserves single-device-identical
    results; an integer < nprobe caps each device's probe budget for
    ~nprobe/S per-device work at a small recall cost.  `exact` as in
    ivf_flat.search: each shard re-scores its own survivors from its own
    vectors, and the merge orders the union by those distances."""
    b, d = queries.shape
    target, qc_eff = _bucket_batch(b, query_chunk)
    q = jnp.asarray(queries, jnp.float32)
    if sidx.metric == METRIC_COSINE:
        q = D.normalize(q)
    if target != b:
        q = jnp.concatenate([q, jnp.zeros((target - b, d), q.dtype)])
        M.vector_search_pad_rows.inc(target - b)
    M.vector_search_queries.inc(b)
    dists, ids = _search_sharded(sidx, q, k, nprobe, qc_eff, compute_dtype,
                                 probe_capacity, exact)
    if target != b:
        dists, ids = dists[:b], ids[:b]
    return dists, ids
