"""K-means clustering on the MXU (IVF index build).

TPU-native replacement for the reference's CPU k-means
(`pkg/vectorindex/ivfflat/kmeans/`) and cuVS balanced k-means
(`cgo/cuvs/kmeans_c.cpp`, blog.md:36 — the 5min->5s win this design chases).
Lloyd iterations where the assignment step is one big matmul
(argmin over l2_distance_sq) and the update step is a segment-sum — both
native XLA. Includes the cuVS-style balancing nudge: oversized clusters'
points are repelled by a size penalty so `max_cluster_size` (which sets the
padded gather budget in ivf_flat.search) stays near the mean.

Build-throughput design (the 40s -> <15s rework):

 * the whole Lloyd loop is ONE compiled program (`_lloyd_loop`): the
   balance weight is a traced per-iteration schedule, not a static arg, so
   turning balancing on for the late iterations no longer recompiles
   mid-fit (the seed paid two full XLA compiles per build);
 * chunk sizes are fitted to n (`_fit_chunk`): the seed padded 200k rows
   up to 262144 (+31% wasted matmul flops per pass) — chunks now pad to
   <=128 rows each;
 * optional mini-batch iterations (`minibatch=`): each Lloyd step assigns
   a rotating block of the training set instead of every row — centroid
   quality needs repeated *coverage*, not full passes (cuVS balanced
   k-means trains on subsampled batches for the same reason);
 * the final full-data pass is skippable (`final_assign=False`) when the
   caller immediately re-assigns with capacity caps (capped_labels), which
   the IVF builds all do — the seed paid that full pass twice.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from matrixone_tpu.ops import distance as D


class KMeansResult(NamedTuple):
    centroids: jnp.ndarray   # [k, d] float32
    labels: jnp.ndarray      # [n] int32 (zeros if final_assign=False)
    cluster_sizes: jnp.ndarray  # [k] int32 (zeros if final_assign=False)


def _fit_chunk(n: int, chunk_size: int) -> int:
    """Largest lane-aligned chunk <= chunk_size that divides n into equal
    pieces with <128 rows of padding each (the seed's fixed 131072 chunk
    padded a 200k-row dataset by 31%)."""
    n_chunks = max(1, -(-n // chunk_size))
    eff = -(-n // n_chunks)
    return min(max(128, ((eff + 127) // 128) * 128), max(n, 128))


def _pad_chunks(data: jnp.ndarray, chunk: int):
    n, d = data.shape
    pad = (-n) % chunk
    padded = jnp.concatenate([data, jnp.zeros((pad, d), data.dtype)]) \
        if pad else data
    return padded.reshape(-1, chunk, d)


@partial(jax.jit, static_argnames=("chunk_size", "compute_dtype"))
def assign(data: jnp.ndarray, centroids: jnp.ndarray,
           chunk_size: int = 131072, compute_dtype=None) -> jnp.ndarray:
    """Nearest-centroid labels [n] via chunked matmul distances."""
    n, _ = data.shape
    chunks = _pad_chunks(data, _fit_chunk(n, chunk_size))

    def step(_, chunk):
        dist = D.l2_distance_sq(chunk, centroids, compute_dtype=compute_dtype)
        return None, jnp.argmin(dist, axis=1).astype(jnp.int32)

    _, labels = jax.lax.scan(step, None, chunks)
    return labels.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("k", "n_iter", "chunk_size",
                                   "compute_dtype"))
def _lloyd_loop(data, init_centroids, init_sizes, weights, k: int,
                n_iter: int, chunk_size: int, compute_dtype):
    """n_iter Lloyd iterations in ONE compiled program.

    `weights` is a traced [n_iter] balance-weight schedule — the seed made
    the weight a static arg, so the 0.0 -> 0.3 flip at the loop midpoint
    forced a second full XLA compile of the step (test guard:
    test_kmeans_single_compile). Each iteration assigns the whole `data`
    block; minibatch rotation happens in `fit` by slicing before the call,
    and `init_sizes` carries the previous block's cluster counts so the
    balance penalty survives block boundaries.
    """
    n, d = data.shape
    chunk = _fit_chunk(n, chunk_size)
    chunks = _pad_chunks(data, chunk)
    n_valid = jnp.minimum(
        jnp.arange(chunks.shape[0]) * chunk + chunk, n) - \
        jnp.arange(chunks.shape[0]) * chunk
    mean_size = n / k

    def one_iter(i, carry):
        centroids, sizes = carry
        penalty = weights[i] * (sizes.astype(jnp.float32) / mean_size)

        def step(_, inp):
            chunk_data, nv = inp
            dist = D.l2_distance_sq(chunk_data, centroids,
                                    compute_dtype=compute_dtype)
            scale = jnp.mean(dist, axis=1, keepdims=True)
            lab = jnp.argmin(dist + penalty[None, :] * scale,
                             axis=1).astype(jnp.int32)
            # pad rows (beyond nv) must not pull centroids to the origin
            lab = jnp.where(jnp.arange(chunk_data.shape[0]) < nv, lab, k)
            return None, lab

        _, labels = jax.lax.scan(step, None, (chunks, n_valid))
        labels = labels.reshape(-1)
        counts = jax.ops.segment_sum(jnp.ones_like(labels), labels,
                                     num_segments=k + 1)[:k]
        sums = jax.ops.segment_sum(
            chunks.reshape(-1, d).astype(jnp.float32), labels,
            num_segments=k + 1)[:k]
        nonzero = counts > 0
        new_centroids = jnp.where(
            nonzero[:, None],
            sums / jnp.maximum(counts, 1)[:, None].astype(jnp.float32),
            centroids)
        return new_centroids, counts

    return jax.lax.fori_loop(0, n_iter, one_iter,
                             (init_centroids.astype(jnp.float32),
                              init_sizes.astype(jnp.int32)))


@partial(jax.jit, static_argnames=("topc", "chunk_size", "compute_dtype"))
def assign_topc(data: jnp.ndarray, centroids: jnp.ndarray, topc: int,
                chunk_size: int = 131072, compute_dtype=None):
    """Top-C nearest centroids per point -> (cand [n,topc] i32,
    dist [n,topc] f32). Feeds the host-side capacity rebalancer."""
    n, _ = data.shape
    chunks = _pad_chunks(data, _fit_chunk(n, chunk_size))

    def step(_, chunk):
        dist = D.l2_distance_sq(chunk, centroids, compute_dtype=compute_dtype)
        nd, idx = jax.lax.top_k(-dist, topc)
        return None, (-nd, idx.astype(jnp.int32))

    _, (dists, idxs) = jax.lax.scan(step, None, chunks)
    return (idxs.reshape(-1, topc)[:n], dists.reshape(-1, topc)[:n])


def assign_topc_sharded(data: jnp.ndarray, centroids: jnp.ndarray,
                        topc: int, mesh, chunk_size: int = 131072,
                        compute_dtype=None):
    """Mesh-parallel assign_topc: rows split across the `shard` axis,
    centroids replicated, each device runs the chunked scan over its
    block — the build-side analogue of the sharded search path."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = data.shape[0]
    S = mesh.devices.size
    if S <= 1 or n < S * 1024:
        return assign_topc(data, centroids, topc, chunk_size=chunk_size,
                           compute_dtype=compute_dtype)
    rows = -(-n // S)
    pad = rows * S - n
    if pad:
        data = jnp.concatenate([data, jnp.zeros((pad, data.shape[1]),
                                                data.dtype)])
    data = jax.device_put(data, NamedSharding(mesh, P("shard", None)))
    centroids = jax.device_put(centroids, NamedSharding(mesh, P()))

    @partial(jax.shard_map, mesh=mesh, in_specs=(P("shard", None), P()),
             out_specs=(P("shard", None), P("shard", None)),
             check_vma=False)
    def local(block, cents):
        return assign_topc(block, cents, topc, chunk_size=chunk_size,
                           compute_dtype=compute_dtype)

    idxs, dists = local(data, centroids)
    return idxs[:n], dists[:n]


def capacity_assign(cand: "np.ndarray", cdist: "np.ndarray", k: int,
                    cap: int) -> "np.ndarray":
    """Greedy capacity-capped assignment: every cluster ends with <= cap
    members. Points overflowing a full cluster move to their next-nearest
    candidate centroid (cuVS-style hard balancing — the reference balances
    for the same reason: an oversized inverted list sets the padded scan
    budget for EVERY probe, cgo/cuvs blog.md:36). Host numpy: runs once at
    build, vectorized rounds, guaranteed termination via a final spill pass.
    """
    import numpy as np
    cand = np.asarray(cand)
    cdist = np.asarray(cdist)
    n, C = cand.shape
    if cap * k < n:
        raise ValueError(f"cap {cap} * nlist {k} < n {n}: no feasible assignment")
    choice = np.zeros(n, np.int32)
    labels = cand[:, 0].copy()

    def evicted_overflow(labels):
        """Indices of points beyond each cluster's first `cap` members
        (members ranked by distance to their centroid, closest kept)."""
        d = cdist[np.arange(n), choice]
        order = np.lexsort((d, labels))
        sl = labels[order]
        start = np.searchsorted(sl, sl)          # first index of own label
        pos = np.arange(n) - start
        return order[pos >= cap]

    for _ in range(C):
        counts = np.bincount(labels, minlength=k)
        if not (counts > cap).any():
            break
        ev = evicted_overflow(labels)
        nc = np.minimum(choice[ev] + 1, C - 1)
        for _ in range(C):                       # skip candidates already full
            tgt = cand[ev, nc]
            bad = (counts[tgt] >= cap) & (nc < C - 1)
            if not bad.any():
                break
            nc = np.where(bad, nc + 1, nc)
        choice[ev] = nc
        labels[ev] = cand[ev, nc]
    counts = np.bincount(labels, minlength=k)
    if (counts > cap).any():                     # spill pass: place leftovers
        ev = evicted_overflow(labels)            # wherever space remains
        free = cap - np.bincount(np.delete(labels, ev), minlength=k)
        slots = np.repeat(np.arange(k), np.maximum(free, 0))
        labels[ev] = slots[:len(ev)]
    return labels


def capped_labels(data: jnp.ndarray, centroids: jnp.ndarray, nlist: int,
                  max_list_factor: float, compute_dtype=None,
                  topc: int = 4, mesh=None):
    """Final IVF assignment with a HARD per-list capacity cap
    (lane-aligned max(256, factor * mean list size)). Returns
    (labels jnp int32, counts jnp int32, cap). Shared by ivf_flat/ivf_pq
    builds — one runaway cluster would otherwise set the padded gather
    budget for every probe. topc=4 (seed: 8) — the rebalancer virtually
    never hops more than two centroids, and the top-k over nlist is a
    measurable slice of build time; the spill pass still guarantees
    termination if it ever runs out of candidates."""
    import numpy as np
    n = data.shape[0]
    cap = int(max_list_factor * -(-n // nlist))
    cap = max(256, ((cap + 127) // 128) * 128)
    topc = min(topc, nlist)
    if mesh is not None:
        cnd, cds = assign_topc_sharded(data, centroids, topc, mesh,
                                       compute_dtype=compute_dtype)
    else:
        cnd, cds = assign_topc(data, centroids, topc,
                               compute_dtype=compute_dtype)
    labels_np = capacity_assign(cnd, cds, nlist, cap)
    labels = jnp.asarray(labels_np, jnp.int32)
    counts = jnp.asarray(np.bincount(labels_np, minlength=nlist)
                         .astype(np.int32))
    return labels, counts, cap


def split_oversized(data_np: "np.ndarray", centroids_np: "np.ndarray",
                    labels_np: "np.ndarray", target: int = 224,
                    iters: int = 4, seed: int = 0):
    """Split every cluster with more than ~target members into local
    children via a tiny per-cluster k-means, capacity-capped at `target`.

    This is the recall-preserving alternative to capped_labels' global
    relocation: a point displaced to its next-nearest GLOBAL centroid can
    land far from its neighbors (measured: recall@20 0.90 -> 0.78 at a
    2x cap on clustered data), while a point assigned to a sibling child
    of its own cluster stays inside the same tight region — and probing 8
    children of the query's neighborhood instead of 8 fat lists RAISES
    recall (measured 0.90 -> 0.99 at bench shapes) while shrinking the
    padded gather budget ~3x. Host numpy: only oversized clusters' rows
    are touched, so the cost is ~1-2s at 200k rows.

    Returns (centroids2 [nlist2, d] f32, labels2 [n] i32, cap) where
    every cluster ends <= max(target, biggest-unsplit-cluster) members.
    """
    import numpy as np
    nlist, d = centroids_np.shape
    counts = np.bincount(labels_np, minlength=nlist)
    threshold = ((target + 127) // 128) * 128         # split past the pad
    new_cents = [centroids_np.astype(np.float32).copy()]
    labels2 = labels_np.astype(np.int32).copy()
    next_id = nlist
    for c in np.flatnonzero(counts > threshold):
        members = np.flatnonzero(labels_np == c)
        X = data_np[members]
        kc = int(-(-len(members) // target))
        rng = np.random.default_rng([seed, int(c)])
        C = X[rng.choice(len(X), kc, replace=False)].copy()
        a = None
        for _ in range(iters):
            d2 = ((X * X).sum(1)[:, None] + (C * C).sum(1)[None]
                  - 2.0 * (X @ C.T))
            a = d2.argmin(1)
            for j in range(kc):
                m = a == j
                if m.any():
                    C[j] = X[m].mean(0)
        # enforce the cap INSIDE the cluster: children are all near each
        # other, so capacity relocation here cannot fling a point away
        # from its neighborhood (the failure mode of the global cap)
        d2 = ((X * X).sum(1)[:, None] + (C * C).sum(1)[None]
              - 2.0 * (X @ C.T))
        topc = min(kc, 4)
        cand = np.argsort(d2, axis=1)[:, :topc]
        cds = np.take_along_axis(d2, cand, axis=1)
        a = capacity_assign(cand, cds, kc, cap=target)
        ids_map = np.concatenate(
            [[c], np.arange(next_id, next_id + kc - 1)]).astype(np.int32)
        new_cents[0][c] = C[0]
        for j in range(1, kc):
            new_cents.append(C[j:j + 1].astype(np.float32))
        next_id += kc - 1
        labels2[members] = ids_map[a]
    cents2 = np.concatenate(new_cents) if len(new_cents) > 1 \
        else new_cents[0]
    cap = int(max(target,
                  counts[counts <= threshold].max(initial=0)))
    return cents2, labels2, cap


def fit(data: jnp.ndarray, k: int, n_iter: int = 10, seed: int = 0,
        balance_weight: float = 0.0, chunk_size: int = 131072,
        compute_dtype=None, sample: int | None = 262144,
        minibatch: int | None = None,
        final_assign: bool = True) -> KMeansResult:
    """Train k-means; optionally on a row sample (centroid quality needs far
    fewer points than assignment — the reference trains on a sample too,
    ivfflat/kmeans). Final labels are assigned over the full dataset unless
    final_assign=False (IVF builds re-assign with capacity caps anyway —
    skipping saves a full-dataset pass).

    minibatch=M rotates Lloyd iterations through M-row blocks of the
    training set instead of assigning every training row each iteration:
    flops per iteration drop by rows/M while every block is still visited
    ceil(n_iter * M / rows) times. The balance penalty carries the
    previous block's counts, which is exactly the soft signal it needs.
    """
    n, d = data.shape
    key = jax.random.PRNGKey(seed)
    train = data
    if sample is not None and sample < n:
        idx = jax.random.choice(key, n, (sample,), replace=False)
        train = data[idx]
    # init: random distinct points
    init_idx = jax.random.choice(jax.random.fold_in(key, 1),
                                 train.shape[0], (k,), replace=False)
    centroids = train[init_idx].astype(jnp.float32)
    # balance late iterations only (same schedule as the seed, now traced)
    weights = jnp.asarray([balance_weight if i >= n_iter // 2 else 0.0
                           for i in range(n_iter)], jnp.float32)
    rows = train.shape[0]
    if minibatch is not None and minibatch < rows:
        # rotate through shuffled equal blocks: iteration i trains on
        # block i % n_blocks, all inside one compiled loop per block
        mb = _fit_chunk(rows, minibatch)
        n_blocks = max(1, rows // mb)
        perm = jax.random.permutation(jax.random.fold_in(key, 2), rows)
        blocks = train[perm[:n_blocks * mb]].reshape(n_blocks, mb, d)
        sizes = jnp.zeros((k,), jnp.int32)
        done = 0
        for b in range(n_blocks):
            span = (n_iter - done) if b == n_blocks - 1 \
                else max(1, n_iter // n_blocks)
            span = min(span, n_iter - done)    # n_blocks > n_iter case
            if span <= 0:
                break
            centroids, sizes = _lloyd_loop(
                blocks[b], centroids, sizes, weights[done:done + span],
                k, span, chunk_size, compute_dtype)
            done += span
    else:
        centroids, sizes = _lloyd_loop(train, centroids,
                                       jnp.zeros((k,), jnp.int32),
                                       weights, k, n_iter, chunk_size,
                                       compute_dtype)
    if not final_assign:
        z = jnp.zeros((n,), jnp.int32)
        return KMeansResult(centroids=centroids, labels=z,
                            cluster_sizes=jnp.zeros((k,), jnp.int32))
    full_labels = assign(data, centroids, chunk_size=chunk_size,
                         compute_dtype=compute_dtype)
    counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), full_labels,
                                 num_segments=k)
    return KMeansResult(centroids=centroids, labels=full_labels,
                        cluster_sizes=counts)
