"""Vector-index scan operator (reference: colexec/table_function/
ivf_search.go + vectorindex/ivfflat/search.go — redesigned: the index is a
device-resident pytree and search is one jitted batched kernel; candidate
rows are fetched by row id and re-enter the normal pipeline).

Txn-workspace caveat: the planner only applies the index rewrite outside
transactions that have written to the table (sql/optimize.apply_indices
skip_tables) — in-txn queries take the exact scan path, which merges the
workspace. Committed-but-post-snapshot rows and deletes ARE handled here
via MVCCTable.visible_gids.
"""

from __future__ import annotations

from typing import Iterator

import jax.numpy as jnp
import numpy as np

from matrixone_tpu.sql import plan as P
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
        from matrixone_tpu import indexing
        from matrixone_tpu.utils import motrace
        catalog = self.ctx.catalog
        ix = catalog.indexes[self.node.index_name]
        cache = getattr(catalog, "index_cache", None)
        # snapshot index + delta under the commit lock: the recluster task
        # mutates both atomically, and a concurrent cache eviction mid-read
        # must retry the refresh instead of yielding an empty result
        for _ in range(8):
            indexing.refresh_if_dirty(catalog, ix)
            with catalog._commit_lock:
                if ix.dirty:
                    continue
                index = ix.index_obj
                row_gids = np.asarray(ix.options["_row_gids"])
                delta_vecs = ix.options.get("_delta_vecs")
                delta_gids = (np.asarray(ix.options["_delta_gids"])
                              if delta_vecs is not None and len(delta_vecs)
                              else None)
                break
        else:
            raise RuntimeError(
                f"index {ix.name} kept getting evicted/dirtied; raise the "
                f"index cache budget")
        if cache is not None:
            cache.touch(ix)
        table = catalog.get_table(self.node.table)

        if index is None:        # index over an empty table
            arrays, validity = table.fetch_rows(
                np.zeros(0, np.int64), self.node.columns)
            yield chunk_to_execbatch(arrays, validity, table.dicts, 0,
                                     self.node.columns, self.node.schema)
            return

        with motrace.span("vector.search"):
            gids = self._search(ix, index, row_gids, delta_vecs,
                                delta_gids)
            motrace.annotate(rows=len(gids))
        with motrace.span("vector.fetch", rows=len(gids)):
            read_args = self.ctx.table_read_args(self.node.table)
            gids = table.visible_gids(
                gids, snapshot_ts=self.ctx.snapshot_ts,
                extra_deletes=read_args.get("extra_deletes"))
            arrays, validity = table.fetch_rows(gids, self.node.columns)
            out = chunk_to_execbatch(arrays, validity, table.dicts,
                                     len(gids), self.node.columns,
                                     self.node.schema)
        yield out

    def _search(self, ix, index, row_gids, delta_vecs, delta_gids):
        """The index search and the exact scan of the delta segment.
        -> gids of the candidates, nearest first."""
        from matrixone_tpu.vectorindex import ivf_flat, ivf_pq
        q = np.asarray([self.node.query_vector], dtype=np.float32)
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
            # session SET use_pallas = 1 routes the probe/ADC kernels
            # through the hand-tiled Pallas paths (gpu_mode analogue)
            from matrixone_tpu.ops import pallas_kernels as PK
            up = PK.effective_use_pallas(
                (self.ctx.variables or {}).get("use_pallas"))
            # no host-side padding: search buckets the batch internally
            sharded_ix = (self._sharded_view(ix, index)
                          if ix.algo == "ivfflat" else None)
            if sharded_ix is not None:
                from matrixone_tpu.vectorindex import sharded as shmod
                dists, pos = shmod.search_sharded(
                    sharded_ix, jnp.asarray(q), k=k, nprobe=nprobe)
            else:
                search_fn = (ivf_pq.search if ix.algo == "ivfpq"
                             else ivf_flat.search)
                dists, pos = search_fn(index, jnp.asarray(q), k=k,
                                       nprobe=nprobe, use_pallas=up)
            main_d = np.asarray(dists)[0]
            pos = np.asarray(pos)[0]
            keep = pos >= 0
            pos, main_d = pos[keep], main_d[keep]
        gids = row_gids[pos]
        # delta segment: rows inserted since the last full build are
        # scanned exactly and merged by distance (indexing._try_incremental).
        # Delta distances MUST be commensurate with what each algo's search
        # returns: ivfflat = sq-l2 | 1-cos | 1-ip; ivfpq cosine = sq-l2 of
        # NORMALIZED vectors (= 2*(1-cos)); hnsw per its own metric kernel
        if delta_gids is not None:
            from matrixone_tpu.ops import distance as D
            dv = jnp.asarray(np.asarray(delta_vecs, np.float32))
            qj = jnp.asarray(q)
            metric = ix.options.get("_metric", "l2")
            if metric == "l2":
                dd = np.asarray(D.l2_distance_sq(qj, dv))[0]
            elif metric == "cosine":
                if ix.algo == "ivfpq":
                    dd = np.asarray(D.l2_distance_sq(
                        D.normalize(qj), D.normalize(dv)))[0]
                else:
                    dd = 1.0 - np.asarray(D.inner_product(
                        D.normalize(qj), D.normalize(dv)))[0]
            else:                      # ip: search returns 1 - x.q
                dd = 1.0 - np.asarray(D.inner_product(qj, dv))[0]
            all_d = np.concatenate([main_d, dd])
            all_g = np.concatenate([gids, delta_gids])
            order = np.argsort(all_d)[:self.node.k]
            gids = all_g[order]
        return gids
