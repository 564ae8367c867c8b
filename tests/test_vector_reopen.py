"""A vector top-k statement on a re-opened engine: what it answers and what
it reads.

The state under test is the one every deployment is in after its first
restart: vectors loaded in several commits, checkpoint, close,
`Engine.open`, the IVF-Flat index built through SQL.  Every segment is then
object-backed (`LazyColumns`), and a top-k statement must move k rows of
the columns its text names, not whole columns through the device tier
(PERF.md section 7, open question 1 of PR 26: 43 s and 144.5 GB a query).
"""

import numpy as np
import pytest

from matrixone_tpu.frontend import Session
from matrixone_tpu.storage import blockcache
from matrixone_tpu.storage.engine import Engine
from matrixone_tpu.storage.fileservice import LocalFS
from matrixone_tpu.utils import metrics as M

N, DIM, LISTS, COMMITS, K = 4096, 32, 16, 4, 10


def _vectors(seed, n=N, dim=DIM, centres=LISTS, n_queries=6):
    rng = np.random.default_rng(seed)
    cent = rng.standard_normal((centres, dim), dtype=np.float32)
    x = (cent[rng.integers(0, centres, n)]
         + 2.0 * rng.standard_normal((n, dim), dtype=np.float32))
    q = (cent[rng.integers(0, centres, n_queries)]
         + 2.0 * rng.standard_normal((n_queries, dim), dtype=np.float32))
    return x, q


def _literal(v):
    return "[" + ",".join(repr(float(f)) for f in v) + "]"


def _load(path, x, commits=COMMITS):
    eng = Engine(LocalFS(str(path)))
    Session(catalog=eng).execute(
        f"create table docs (id bigint primary key, v vecf32({x.shape[1]}))")
    t = eng.get_table("docs")
    bounds = np.linspace(0, len(x), commits + 1).astype(np.int64)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        t.insert_numpy({"id": np.arange(lo, hi, dtype=np.int64),
                        "v": x[lo:hi]})
    return eng


def _reopen(eng, path):
    eng.checkpoint()
    eng.close()
    blockcache.CACHE.clear()
    return Engine.open(LocalFS(str(path)))


def _index(s, lists=LISTS):
    s.execute(f"create index docs_v using ivfflat on docs (v) "
              f"lists = {lists} op_type = 'vector_l2_ops'")


def _search(s, qv, k=K, cols="id"):
    return s.execute(f"select {cols} from docs order by "
                     f"l2_distance(v, '{_literal(qv)}') limit {k}").rows()


def _brute_force(rows, qv, k=K):
    """ids of the k nearest of `rows` {id: vector}, float64."""
    ids = np.fromiter(rows, np.int64)
    x = np.stack([rows[i] for i in ids.tolist()]).astype(np.float64)
    d = ((x - qv.astype(np.float64)) ** 2).sum(1)
    return ids[np.argsort(d, kind="stable")[:k]].tolist()


def _same_lists(index, x, qv, nprobe, k=K):
    """numpy search of the lists the index probes: the `nprobe` lists
    whose centroids are nearest, every member scored in float64."""
    cents = np.asarray(index.centroids, np.float64)
    offs, members = np.asarray(index.offsets), np.asarray(index.ids)
    cd = ((cents - qv.astype(np.float64)) ** 2).sum(1)
    cand = np.concatenate([members[offs[c]:offs[c + 1]]
                           for c in np.argsort(cd, kind="stable")[:nprobe]])
    d = ((x[cand].astype(np.float64) - qv.astype(np.float64)) ** 2).sum(1)
    return cand[np.argsort(d, kind="stable")[:k]].tolist()


# --------------------------------------------------------------- answers

@pytest.mark.parametrize("state", ["before_reopen", "after_reopen",
                                   "after_delete_and_insert",
                                   "older_snapshot"])
def test_ids_equal_numpy_id_for_id(tmp_path, state):
    """At `ivf_nprobe = lists` the statement's ids are the brute-force
    top-k; at nprobe 8 they are those of a numpy search of the same lists
    and centroids.  Before the re-open, after it, after a delete and an
    insert (the index's delta segment), and under a snapshot taken before
    that delete and insert: an open transaction declines the rewrite and
    scans exactly, and the operator's own visibility filter
    (`visible_mask`) gives the same view at that snapshot."""
    x, queries = _vectors(11)
    eng = _load(tmp_path, x)
    if state != "before_reopen":
        eng = _reopen(eng, tmp_path)
    s = Session(catalog=eng)
    _index(s)
    rows = {i: x[i] for i in range(len(x))}
    reader = s
    if state in ("after_delete_and_insert", "older_snapshot"):
        s.execute(f"set ivf_nprobe = {LISTS}")
        _search(s, queries[0])                    # the index is built now
        if state == "older_snapshot":
            reader = Session(catalog=eng)
            reader.execute("begin")               # snapshot: before the DML
            reader.execute("select count(*) from docs")
            old_ts = reader.txn.snapshot_ts
        writer = Session(catalog=eng)
        nearest = _brute_force(rows, queries[0], 3)
        writer.execute(f"delete from docs where id in "
                       f"({', '.join(map(str, nearest))})")
        fresh = {len(x) + j: (queries[j] + 0.01).astype(np.float32)
                 for j in range(3)}
        writer.execute("insert into docs values " + ", ".join(
            f"({i}, '{_literal(v)}')" for i, v in fresh.items()))
        if state == "after_delete_and_insert":
            for i in nearest:
                del rows[i]
            rows.update(fresh)
    plan = reader.execute("explain " + f"select id from docs order by "
                          f"l2_distance(v, '{_literal(queries[0])}') "
                          f"limit {K}").text
    if state == "older_snapshot":
        assert "VectorTopK" not in plan, plan     # the in-txn decline
    else:
        assert "VectorTopK" in plan and "docs_v" in plan, plan
    reader.execute(f"set ivf_nprobe = {LISTS}")
    for qv in queries:
        assert [r[0] for r in _search(reader, qv)] == _brute_force(rows, qv)
    if state in ("before_reopen", "after_reopen"):
        reader.execute("set ivf_nprobe = 8")
        index = eng.indexes["docs_v"].index_obj
        for qv in queries:
            assert [r[0] for r in _search(reader, qv)] == \
                _same_lists(index, x, qv, 8)
    if state == "after_delete_and_insert":
        assert len(eng.indexes["docs_v"].options["_delta_gids"]) == 3
    if state == "older_snapshot":
        t = eng.get_table("docs")
        gids = np.array(nearest + [len(x), len(x) + 1], np.int64)
        assert t.visible_mask(gids, snapshot_ts=old_ts).tolist() == \
            [True, True, True, False, False]
        assert t.visible_mask(gids).tolist() == \
            [False, False, False, True, True]
        reader.execute("commit")
        assert fresh.keys() & {r[0] for r in _search(reader, queries[0])}


def test_selected_vectors_and_distances_are_the_stored_ones(tmp_path):
    """A statement that does select `v`, or the distance itself, still
    works: the vectors are the stored rows and the distance is that of
    the SQL function to float32 rounding."""
    x, queries = _vectors(12)
    s = Session(catalog=_reopen(_load(tmp_path, x), tmp_path))
    _index(s)
    s.execute(f"set ivf_nprobe = {LISTS}")
    qv = queries[0]
    want = _brute_force({i: x[i] for i in range(len(x))}, qv, 5)
    got = _search(s, qv, 5, cols=f"id, v, l2_distance(v, '{_literal(qv)}')")
    assert [r[0] for r in got] == want
    for i, v, d in got:
        assert np.array_equal(np.asarray(v, np.float32), x[i])
        exact = np.sqrt(((x[i].astype(np.float64) - qv) ** 2).sum())
        assert abs(d - exact) <= 1e-5 * exact


_METRICS = {
    # SQL function: (op_type of the index, exact float64 key of a row)
    "l2_distance_sq": ("vector_l2_ops",
                       lambda x, q: ((x - q) ** 2).sum(1)),
    "cosine_distance": ("vector_cosine_ops",
                        lambda x, q: 1.0 - (x @ q) / (
                            np.linalg.norm(x, axis=1) * np.linalg.norm(q))),
    "inner_product": ("vector_ip_ops", lambda x, q: x @ q),
}


@pytest.mark.parametrize("fn", sorted(_METRICS))
def test_every_metric_answers_in_the_statements_own_order(tmp_path, fn):
    """`ORDER BY fn(v, q) LIMIT k` ascends in `fn`, whatever the index
    ranks by.  `l2_distance_sq` and `cosine_distance` ascend with the
    index's score, so the index's own order is the answer (no TopK in the
    plan, `v` not read) and at `ivf_nprobe = lists` the ids are numpy's
    brute-force top-k.  A `vector_ip_ops` index ranks by the LARGEST
    product, against the statement's text: there the TopK stays, over the
    index's 3k candidates, and the rows come out smallest product first."""
    op_type, key = _METRICS[fn]
    x, queries = _vectors(15)
    eng = _reopen(_load(tmp_path, x), tmp_path)
    s = Session(catalog=eng)
    s.execute(f"create index docs_v using ivfflat on docs (v) "
              f"lists = {LISTS} op_type = '{op_type}'")
    s.execute(f"set ivf_nprobe = {LISTS}")
    x64 = x.astype(np.float64)
    for qv in queries:
        sql = (f"select id, {fn}(v, '{_literal(qv)}') from docs "
               f"order by {fn}(v, '{_literal(qv)}') limit {K}")
        plan = s.execute("explain " + sql).text
        assert "VectorTopK" in plan and "docs_v" in plan, plan
        assert ("TopK " in plan.replace("VectorTopK", "")) == \
            (fn == "inner_product"), plan
        got = s.execute(sql).rows()
        exact = key(x64, qv.astype(np.float64))
        ids = [r[0] for r in got]
        assert len(set(ids)) == K
        for i, d in got:                  # the function's own value
            assert abs(d - exact[i]) <= 1e-5 * max(1.0, abs(exact[i]))
        assert np.all(np.diff(exact[ids]) >= 0), exact[ids]  # ascending
        if fn != "inner_product":
            assert ids == np.argsort(exact, kind="stable")[:K].tolist()
        else:
            # the k smallest products among the index's candidates, which
            # are (but for bfloat16 scoring at the pool's edge) the 3k
            # rows with the largest products
            largest = set(np.argsort(-exact, kind="stable")[:4 * K].tolist())
            assert set(ids) <= largest
            assert exact[ids].max() < np.sort(exact)[-2 * K]


# ----------------------------------------------------- the cost invariant

def _lookups(stats):
    return stats["hits"] + stats["misses"]


def test_a_warmed_search_moves_k_rows_not_columns(tmp_path, monkeypatch):
    """The cost invariant (it fails on the tree before PR 29).  With both
    cache tiers smaller than ONE segment's vector column, a warmed search
    after the re-open uploads nothing, decodes nothing, looks the cache up
    at most twice a segment (`id` and its validity) and waits for the
    device once (the search with its exact re-rank); the index build reads
    the vectors on the host, with no upload either."""
    dim, n = 128, 12000                     # 3000 x 128 x 4 B = 1.46 MB
    x, queries = _vectors(13, n=n, dim=dim)
    monkeypatch.setenv("MO_BLOCK_CACHE_MB", "1")
    monkeypatch.setenv("MO_DEVICE_CACHE_MB", "1")
    eng = _reopen(_load(tmp_path, x), tmp_path)
    assert all(seg.is_lazy and seg.n_rows * dim * 4 > (1 << 20)
               for seg in eng.get_table("docs").segments)
    s = Session(catalog=eng)
    before = blockcache.CACHE.stats()
    _index(s)
    s.execute("set ivf_nprobe = 8")
    _search(s, queries[0], 20)                       # builds, compiles
    built = blockcache.CACHE.stats()
    assert built["uploaded_bytes"] == before["uploaded_bytes"]
    assert built["device_tier"]["used_bytes"] == 0
    rows = {i: x[i] for i in range(n)}
    for qv in queries[1:]:
        st0 = blockcache.CACHE.stats()
        waits0 = sum(v["value"] for v in M.device_wait.snapshot()["values"])
        bytes0 = M.vector_fetch_bytes.get()
        got = _search(s, qv, 20)
        st1 = blockcache.CACHE.stats()
        waits1 = sum(v["value"] for v in M.device_wait.snapshot()["values"])
        assert st1["uploaded_bytes"] == st0["uploaded_bytes"]
        assert st1["misses"] == st0["misses"]        # nothing decoded
        assert _lookups(st1) - _lookups(st0) <= 2 * COMMITS
        assert waits1 - waits0 == 2            # the search, the result
        assert M.vector_fetch_bytes.get() - bytes0 == 20 * (8 + 1)
        assert len(got) == 20
    # a statement that selects `v` reads it through the grouped fetch:
    # one decode a segment at most, admitted nowhere, still no upload
    st0 = blockcache.CACHE.stats()
    got = _search(s, queries[0], 20, cols="id, v")
    st1 = blockcache.CACHE.stats()
    assert st1["uploaded_bytes"] == st0["uploaded_bytes"]
    assert st1["misses"] - st0["misses"] <= COMMITS
    assert all(np.array_equal(np.asarray(v, np.float32), rows[i])
               for i, v in got)


# ------------------------------------------------------------ fetch_rows

def _fetch_rows_row_by_row(table, gids, columns):
    """The semantics `fetch_rows` had before it grouped by segment: one
    lookup and one element a row a column."""
    arrays, validity = {}, {}
    schema = dict(table.meta.schema)
    for c in columns:
        parts_a, parts_v = [], []
        for g in gids:
            seg = next((sg for sg in table.segments
                        if sg.base_gid <= g < sg.base_gid + sg.n_rows), None)
            if seg is None:
                seg = table._gid_fence_segment(int(g))
            if seg is None:
                raise KeyError(int(g))
            off = int(g - seg.base_gid)
            parts_a.append(np.asarray(seg.arrays[c][off]))
            parts_v.append(bool(np.asarray(seg.validity[c][off])))
        if parts_a:
            arrays[c] = np.stack(parts_a)
        else:
            d = schema[c]
            arrays[c] = np.zeros((0, d.dim) if d.is_vector else (0,),
                                 np.int32 if d.is_varlen else d.np_dtype)
        validity[c] = np.asarray(parts_v, np.bool_)
    return arrays, validity


@pytest.fixture(scope="module")
def mixed_table(tmp_path_factory):
    """A table whose gids live in three kinds of segment: fenced (merged
    away, kept for a snapshot), object-backed (checkpointed, re-opened)
    and in-memory (committed after the re-open); with NULLs, a varchar
    (dictionary codes) and a vector column."""
    path = tmp_path_factory.mktemp("mixed")
    eng = Engine(LocalFS(str(path)))
    s = Session(catalog=eng)
    s.execute("create table m (id bigint primary key, n int, "
              "name varchar(12), v vecf32(4))")

    def insert(lo, hi):
        s.execute("insert into m values " + ", ".join(
            "({i}, {n}, {name}, '[{i}.5, 1, 2, {i}]')".format(
                i=i, n="null" if i % 7 == 0 else i * 3,
                name="null" if i % 5 == 0 else f"'w{i % 4}'")
            for i in range(lo, hi)))

    insert(0, 40)
    insert(40, 90)
    s.execute("create snapshot pin")            # keeps the fence alive
    s.execute("delete from m where id in (3, 41)")
    assert eng.merge_table("m") == 88           # fences segments 0 and 1
    insert(90, 130)
    eng.checkpoint()
    eng.close()
    blockcache.CACHE.clear()
    eng = Engine.open(LocalFS(str(path)))
    s = Session(catalog=eng)
    insert(130, 150)                            # in memory
    t = eng.get_table("m")
    assert t.fences and any(sg.is_lazy for sg in t.segments) \
        and not t.segments[-1].is_lazy
    return t


def _live_gids(t):
    return np.concatenate([np.arange(sg.base_gid, sg.base_gid + sg.n_rows)
                           for sg in t.segments])


@pytest.mark.parametrize("pick", [
    "empty", "one_row", "every_live_row_shuffled", "duplicates",
    "fenced_only", "fenced_lazy_and_memory_interleaved"])
@pytest.mark.parametrize("columns", [["id"], ["n", "name"],
                                     ["v", "id", "name", "n"]])
def test_fetch_rows_equals_the_row_by_row_gather(mixed_table, pick, columns):
    t = mixed_table
    live = _live_gids(t)
    fenced = np.arange(0, 90)                   # the merged-away ranges
    rng = np.random.default_rng(5)
    gids = {"empty": np.zeros(0, np.int64),
            "one_row": live[17:18],
            "every_live_row_shuffled": rng.permutation(live),
            "duplicates": np.array([live[3], live[-1], live[3], live[3],
                                    live[-1]]),
            "fenced_only": fenced[[5, 88, 0, 41]],
            "fenced_lazy_and_memory_interleaved": np.array(
                [live[-1], 7, live[0], 60, live[-2], live[1], 7])}[pick]
    want_a, want_v = _fetch_rows_row_by_row(t, gids, columns)
    got_a, got_v = t.fetch_rows(gids, columns)
    assert list(got_a) == columns
    for c in columns:
        assert isinstance(got_a[c], np.ndarray)
        assert got_a[c].dtype == want_a[c].dtype, c
        assert got_a[c].shape == want_a[c].shape, c
        assert np.array_equal(got_v[c], want_v[c]), c
        assert np.array_equal(got_a[c][got_v[c]], want_a[c][want_v[c]]), c


def test_fetch_rows_of_an_unknown_gid_raises(mixed_table):
    with pytest.raises(KeyError):
        mixed_table.fetch_rows(np.array([5, 10 ** 9]), ["id"])


def test_read_column_f32_skips_tombstones_and_reads_on_the_host(tmp_path):
    x, _ = _vectors(14, n=1024)
    eng = _reopen(_load(tmp_path, x), tmp_path)
    Session(catalog=eng).execute("delete from docs where id in (5, 700)")
    before = blockcache.CACHE.stats()["uploaded_bytes"]
    data, gids = eng.get_table("docs").read_column_f32("v")
    assert blockcache.CACHE.stats()["uploaded_bytes"] == before
    keep = np.setdiff1d(np.arange(1024), [5, 700])
    assert isinstance(data, np.ndarray) and data.dtype == np.float32
    assert np.array_equal(gids, keep) and np.array_equal(data, x[keep])
