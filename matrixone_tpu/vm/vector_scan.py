"""Vector-index scan operator (reference: colexec/table_function/
ivf_search.go + vectorindex/ivfflat/search.go — redesigned: the index is a
device-resident pytree and search is one jitted batched kernel; candidate
rows are fetched by row id and re-enter the normal pipeline).

What a top-k statement reads.  The index search returns the positions and
distances of `k x overfetch` candidates; an IVF-Flat index re-ranks them
exactly from its own vectors in the same device program (`dist_op`,
`P.VECTOR_DIST`) and returns them nearest first, so the statement costs
ONE device round trip for the search and its re-rank (a second one only
while a delta segment of not-yet-clustered rows exists), a host-side
visibility filter, the statement's own limit, and a `fetch_rows` of the k
survivors for the columns the statement's text names — k rows of `id`,
never the vector column unless the statement selects it.  The last round
trip of the statement is the result's fetch (`result.fetch`).  IVF-PQ and
HNSW yield no distance, and `inner_product` does not ascend with the
index's score: there the candidates' vectors are fetched (grouped by
segment, `MVCCTable.fetch_rows`) and the Project and TopK above re-rank
them by the statement's own key.

Txn-workspace caveat: the planner only applies the index rewrite outside
transactions that have written to the table (sql/optimize.apply_indices
skip_tables) — in-txn queries take the exact scan path, which merges the
workspace. Committed-but-post-snapshot rows and deletes ARE handled here
via MVCCTable.visible_mask.
"""

from __future__ import annotations

from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from matrixone_tpu.ops import distance as D
from matrixone_tpu.sql import plan as P
from matrixone_tpu.utils import metrics as M, motrace
from matrixone_tpu.vm.exprs import ExecBatch
from matrixone_tpu.vm.operators import Operator, chunk_to_execbatch


class VectorTopKOp(Operator):
    def __init__(self, node: P.VectorTopK, ctx):
        self.node = node
        self.ctx = ctx
        self.schema = node.schema

    def _sharded_view(self, ix, index):
        """Route the query onto the device mesh when `SET ivf_shards = N`
        (or the MO_IVF_SHARDS env default) asks for it and the mesh has
        the devices. The cluster-sharded repack of the current index_obj
        is cached on the IndexMeta, keyed by the source index object
        itself — a recluster/refresh swaps index_obj, which invalidates
        the cache automatically. Returns None for the single-device
        path."""
        import os

        import jax
        want = (self.ctx.variables or {}).get(
            "ivf_shards", os.environ.get("MO_IVF_SHARDS", 0))
        try:
            want = int(want)
        except (TypeError, ValueError):
            return None
        n_dev = len(jax.devices())
        shards = min(want, n_dev, index.nlist)
        if shards < 2:
            return None
        cached = ix.options.get("_sharded")
        # identity (not id()) comparison: holding the source index in the
        # cache entry both proves provenance and prevents id-reuse aliasing
        if cached is not None and cached[0] is index \
                and cached[1] == shards:
            return cached[2]
        from matrixone_tpu.parallel.mesh import make_mesh
        from matrixone_tpu.vectorindex import sharded as shmod
        sidx = shmod.shard_ivf(index, make_mesh(shards))
        ix.options["_sharded"] = (index, shards, sidx)
        return sidx

    def execute(self) -> Iterator[ExecBatch]:
        catalog = self.ctx.catalog
        ix = catalog.indexes[self.node.index_name]
        cache = getattr(catalog, "index_cache", None)
        with motrace.span("vector.index"):
            index, row_gids, delta_vecs, delta_gids = self._snapshot(
                catalog, ix)
        if cache is not None:
            cache.touch(ix)
        table = catalog.get_table(self.node.table)

        cols = [c for c in self.node.columns if c != P.VECTOR_DIST]
        if index is None:        # index over an empty table
            gids = np.zeros(0, np.int64)
            scores = np.zeros(0, np.float64)
        else:
            with motrace.span("vector.search"):
                gids, scores = self._search(ix, index, row_gids,
                                            delta_vecs, delta_gids)
                motrace.annotate(rows=len(gids))
            with motrace.span("vector.visible"):
                read_args = self.ctx.table_read_args(self.node.table)
                ok = table.visible_mask(
                    gids, snapshot_ts=self.ctx.snapshot_ts,
                    extra_deletes=read_args.get("extra_deletes"))
                gids, scores = gids[ok], scores[ok]
                if self.node.limit is not None:
                    rows = slice(self.node.offset,
                                 self.node.offset + self.node.limit)
                    gids, scores = gids[rows], scores[rows]
        with motrace.span("vector.fetch", rows=len(gids)):
            arrays, validity = table.fetch_rows(gids, cols)
            nbytes = sum(a.nbytes + validity[c].nbytes
                         for c, a in arrays.items())
            motrace.annotate(bytes=nbytes, cols=len(cols))
            M.vector_fetch_rows.inc(len(gids))
            M.vector_fetch_bytes.inc(nbytes)
        with motrace.span("vector.batch"):
            if P.VECTOR_DIST in self.node.columns:
                arrays[P.VECTOR_DIST] = _as_sql_distance(
                    scores, self.node.dist_op)
                validity[P.VECTOR_DIST] = np.ones(len(gids), np.bool_)
            out = chunk_to_execbatch(arrays, validity, table.dicts,
                                     len(gids), self.node.columns,
                                     self.node.schema)
        yield out

    @staticmethod
    def _snapshot(catalog, ix):
        """The index (rebuilt first where a commit left it dirty) and its
        delta segment, taken together under the commit lock: the recluster
        task mutates both atomically, and a concurrent cache eviction
        mid-read must retry the refresh instead of yielding an empty
        result.  -> (index, row_gids, delta_vecs, delta_gids)."""
        from matrixone_tpu import indexing
        for _ in range(8):
            indexing.refresh_if_dirty(catalog, ix)
            with catalog._commit_lock:
                if ix.dirty:
                    continue
                delta_vecs = ix.options.get("_delta_vecs")
                delta_gids = (np.asarray(ix.options["_delta_gids"])
                              if delta_vecs is not None and len(delta_vecs)
                              else None)
                return (ix.index_obj, np.asarray(ix.options["_row_gids"]),
                        delta_vecs, delta_gids)
        raise RuntimeError(
            f"index {ix.name} kept getting evicted/dirtied; raise the "
            f"index cache budget")

    def _search(self, ix, index, row_gids, delta_vecs, delta_gids):
        """The index search (with its exact re-rank where the node has a
        `dist_op`) and the exact scan of the delta segment.
        -> (gids, scores) of the candidates, nearest first; a score is
        what the index's search returns for the metric: squared l2,
        1 - cosine, 1 - inner product."""
        from matrixone_tpu.vectorindex import ivf_flat, ivf_pq
        q = np.asarray([self.node.query_vector], dtype=np.float32)
        exact = self.node.dist_op is not None
        if ix.algo == "hnsw":
            from matrixone_tpu.vectorindex import hnsw
            k = min(self.node.k, index.n) or 1
            ef = max(64, 2 * k)
            d2, pos2 = hnsw.search(index, q, k=k, ef=ef)
            keep = pos2[0] >= 0
            pos, main_d = pos2[0][keep], np.asarray(d2)[0][keep]
        else:
            nprobe = min(self.node.nprobe, index.nlist)
            pool = nprobe * index.max_cluster_size
            k = min(self.node.k, index.n, pool) or 1
            # no host-side padding: search buckets the batch internally
            sharded_ix = (self._sharded_view(ix, index)
                          if ix.algo == "ivfflat" else None)
            with motrace.span("vector.search.dispatch", nprobe=nprobe,
                              candidates=pool, k=k, algo=ix.algo):
                if sharded_ix is not None:
                    from matrixone_tpu.vectorindex import sharded as shmod
                    found = shmod.search_sharded(
                        sharded_ix, jnp.asarray(q), k=k, nprobe=nprobe,
                        exact=exact)
                elif ix.algo == "ivfpq":
                    found = ivf_pq.search(index, jnp.asarray(q), k=k,
                                          nprobe=nprobe)
                else:
                    found = ivf_flat.search(
                        index, jnp.asarray(q), k=k, nprobe=nprobe,
                        exact=exact)
            with motrace.span("vector.search.wait"):
                dists, pos = jax.device_get(found)
            M.device_wait.inc(site="vector_search")
            main_d, pos = dists[0], pos[0]
            keep = pos >= 0
            pos, main_d = pos[keep], main_d[keep]
        gids = row_gids[pos]
        # delta segment: rows inserted since the last full build are
        # scanned exactly and merged by distance (indexing._try_incremental).
        # Delta distances MUST be commensurate with what each algo's search
        # returns: ivfflat = sq-l2 | 1-cos | 1-ip; ivfpq cosine = sq-l2 of
        # NORMALIZED vectors (= 2*(1-cos)); hnsw per its own metric kernel
        if delta_gids is not None:
            metric = ix.options.get("_metric", "l2")
            if metric == "cosine" and ix.algo == "ivfpq":
                metric = "cosine_as_l2"
            with motrace.span("vector.search.delta", rows=len(delta_gids)):
                dd = np.asarray(_delta_scores(
                    jnp.asarray(np.asarray(delta_vecs, np.float32)),
                    jnp.asarray(q[0]), metric))
            M.device_wait.inc(site="vector_delta")
            all_d = np.concatenate([main_d, dd])
            all_g = np.concatenate([gids, delta_gids])
            order = np.argsort(all_d, kind="stable")[:self.node.k]
            gids, main_d = all_g[order], all_d[order]
        return gids, np.asarray(main_d, np.float64)


@partial(jax.jit, static_argnames=("metric",))
def _delta_scores(vecs, q, metric: str):
    """Scores of the delta segment's rows against one query, elementwise
    in float32 (no matmul: the default matmul precision on the TPU is
    bfloat16 passes, and these scores are merged with exact ones)."""
    if metric in ("cosine", "cosine_as_l2"):
        vecs, q = D.normalize(vecs), D.normalize(q)
    if metric in ("l2", "cosine_as_l2"):
        diff = vecs - q[None, :]
        return jnp.sum(diff * diff, axis=-1)
    return 1.0 - jnp.sum(vecs * q[None, :], axis=-1)


def _as_sql_distance(scores: np.ndarray, op: str) -> np.ndarray:
    """The index's scores as the values of the SQL function `op`, float64
    on the host (a float64 square root keeps distinct float32 scores
    distinct, so the order of the re-rank survives)."""
    if op == "l2_distance":
        return np.sqrt(scores)
    return scores                     # l2_distance_sq, cosine_distance
