"""A resident chunk costs the statement's thread one dispatch and no wait.

`MVCCTable._read_chunk` over an object-backed segment (columns served by
the block cache's device tier): every data and validity array of a chunk
is sliced by one program, and the chunk's zonemap numbers (valid rows,
their min and max) are computed once and kept with the immutable object,
so that a later statement checks host scalars.  Numpy segments take the
host path: no dispatch, no program, no kept summary.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matrixone_tpu.container import dtypes as dt
from matrixone_tpu.frontend import Session
from matrixone_tpu.sql.expr import BoundCol, BoundFunc, BoundLiteral
from matrixone_tpu.storage import blockcache, engine as engmod
from matrixone_tpu.storage.engine import Engine
from matrixone_tpu.storage.fileservice import LocalFS
from matrixone_tpu.utils import metrics as M

ROWS, COMMITS, BATCH_ROWS = 5000, 2, 1024   # a segment: 1024, 1024, 452
DIM = 4
#: scanned column -> what the scan carries for it
CARRIED = {
    "id": "int64", "qty": "DECIMAL's int64", "ship": "DATE's int32",
    "flag": "dictionary codes", "ok": "bool", "v": "[n, dim] vector",
}
OUTCOMES = ("scanned", "pruned_segment", "pruned_chunk", "all_dead")
SOURCES = ("memo", "device", "host")
HOWS = ("chunk", "column")


class _Counts:
    """What moved since it was made, of the counters this layer owns."""

    def __init__(self):
        self._0 = self._read()

    @staticmethod
    def _read():
        got = {("wait",): M.device_wait.get(site="zonemap")}
        got.update({("check", k): M.scan_zonemap_checks.get(source=k)
                    for k in SOURCES})
        got.update({("slice", k): M.scan_slice_dispatch.get(how=k)
                    for k in HOWS})
        got.update({("chunk", k): M.scan_chunks.get(outcome=k)
                    for k in OUTCOMES})
        return got

    def _moved(self, kind):
        now = self._read()
        return {k[1]: int(now[k] - self._0[k]) for k in now
                if k[0] == kind and now[k] != self._0[k]}

    waits = property(lambda self: int(
        M.device_wait.get(site="zonemap") - self._0[("wait",)]))
    checks = property(lambda self: self._moved("check"))
    slices = property(lambda self: self._moved("slice"))
    chunks = property(lambda self: self._moved("chunk"))


@contextlib.contextmanager
def _compiled():
    """-> the names of the programs compiled (or fetched from the compile
    cache) while it is open."""
    names = []

    def on_duration(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield names
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _load(path, lazy=True):
    """`li`: ROWS rows in COMMITS commits, NULLs in `qty` and `flag`;
    re-opened from its checkpoint (object-backed) where `lazy`."""
    eng = Engine(LocalFS(str(path)))
    s = Session(catalog=eng)
    s.execute("create table li (id bigint primary key, qty decimal(15,2),"
              " disc decimal(15,2), ship date, flag varchar(4), ok bool,"
              f" v vecf32({DIM}))")
    rng = np.random.default_rng(32)
    per = ROWS // COMMITS
    for lo in range(0, ROWS, per):
        rows = []
        for i in range(lo, lo + per):
            qty = "null" if i % 97 == 0 else f"{rng.integers(1, 51)}.00"
            flag = "null" if i % 89 == 0 else f"'{'ANR'[i % 3]}'"
            day = np.datetime64("1992-01-02") + int(rng.integers(0, 2500))
            vec = ",".join(f"{x:.3f}" for x in rng.standard_normal(DIM))
            rows.append(f"({i},{qty},0.{rng.integers(0, 11):02d},'{day}',"
                        f"{flag},{'true' if i % 5 else 'false'},'[{vec}]')")
        s.execute("insert into li values " + ",".join(rows))
    if lazy:
        eng.checkpoint()
        s.close()
        eng.close()
        blockcache.CACHE.clear()
        eng = Engine.open(LocalFS(str(path)))
        s = Session(catalog=eng)
    s.execute(f"set batch_rows = {BATCH_ROWS}")
    return eng, s


@pytest.fixture(scope="module")
def reopened(tmp_path_factory):
    eng, s = _load(tmp_path_factory.mktemp("li"))
    yield eng, s
    s.close()
    eng.close()
    blockcache.CACHE.clear()


def _pred(op, col, dtype, value, lit_dtype=None):
    return BoundFunc(op, [BoundCol(col, dtype),
                          BoundLiteral(value, lit_dtype or dtype)], dt.BOOL)


# ---------------------------------------------- (b) one program a chunk

@pytest.mark.parametrize("col", list(CARRIED), ids=list(CARRIED.values()))
def test_a_sliced_chunk_is_the_columns_own_rows(reopened, col):
    """Full and ragged chunks of an object-backed segment equal
    `np.asarray(column)[start:end]`, array for array and dtype for dtype,
    with no pad, after exactly one `how="chunk"` dispatch a chunk."""
    eng, _s = reopened
    table = eng.get_table("li")
    assert all(seg.is_lazy for seg in table.segments)
    counts = _Counts()
    chunks = list(map(engmod.live_rows,
                      table.iter_chunks([col, "id"], BATCH_ROWS)))
    cuts = [(seg, lo, min(lo + BATCH_ROWS, seg.n_rows))
            for seg in table.segments
            for lo in range(0, seg.n_rows, BATCH_ROWS)]
    assert [hi - lo for _, lo, hi in cuts] == [1024, 1024, 452] * COMMITS
    assert counts.slices == {"chunk": len(cuts)}       # and no "column"
    for (arrays, validity, _dicts, n), (seg, lo, hi) in zip(chunks, cuts):
        assert n == hi - lo
        for c in (col, "id"):
            data, valid = seg.arrays.host_pair(c)      # decoded numpy
            assert isinstance(data, np.ndarray)
            for got, want in ((arrays[c], data), (validity[c], valid)):
                assert isinstance(got, jax.Array)
                assert got.dtype == want.dtype
                assert got.shape == want[lo:hi].shape
                np.testing.assert_array_equal(np.asarray(got), want[lo:hi])
    data, valid = table.segments[0].arrays.host_pair(col)
    assert valid.dtype == np.bool_
    assert data.ndim == (2 if col == "v" else 1)
    if col in ("qty", "flag"):
        assert not valid.all()                         # NULLs are in it


def test_a_chunk_that_is_its_whole_segment_dispatches_nothing(reopened):
    """With `batch_rows` over the segment's length the tier's arrays are
    handed on as they are."""
    eng, _s = reopened
    table = eng.get_table("li")
    counts = _Counts()
    chunks = list(map(engmod.live_rows,
                      table.iter_chunks(["id", "qty"], 1 << 20)))
    assert counts.slices == {}
    assert [n for *_, n in chunks] == [ROWS // COMMITS] * COMMITS
    for (arrays, validity, _d, _n), seg in zip(chunks, table.segments):
        assert arrays["qty"] is seg.arrays["qty"]
        assert validity["qty"] is seg.validity["qty"]


def test_two_programs_serve_every_chunk_of_a_column_set(reopened):
    """`start` is traced and the length static: a full-chunk program and
    a ragged-tail program, whatever the chunk's place in its segment."""
    eng, _s = reopened
    table = eng.get_table("li")
    engmod._slice_rows.clear_cache()
    list(table.iter_chunks(["id", "ship"], BATCH_ROWS))
    assert engmod._slice_rows._cache_size() == 2
    with _compiled() as names:
        list(table.iter_chunks(["id", "ship"], BATCH_ROWS))
    assert names == []


# ------------------------------------- (a) a summary is computed once

Q = ("select count(*), sum(qty) from li where ship >= date '{lo}' and"
     " ship < date '{hi}' and disc between {d0} and {d1} and qty < {q}")
N_PREDICATES = 5                     # over three columns: ship, disc, qty


def test_the_second_statement_waits_for_no_zonemap(tmp_path, monkeypatch):
    """A filtered statement over a re-opened table: its first run fills
    each chunk's summary with at most one wait a (column, chunk), in fact
    one a chunk; a second run with other literals reads host scalars:
    no wait, every check `memo`."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    eng, s = _load(tmp_path)
    n_chunks = 3 * COMMITS
    counts = _Counts()
    first = s.execute(Q.format(lo="1992-01-01", hi="1999-01-01", d0="0.00",
                               d1="0.10", q=51)).rows()
    assert first[0][0] == sum(1 for i in range(ROWS) if i % 97)
    assert counts.chunks == {"scanned": n_chunks}
    assert counts.waits == n_chunks <= 3 * n_chunks
    assert counts.checks == {"device": N_PREDICATES * n_chunks}

    counts = _Counts()
    with _compiled() as names:
        again = s.execute(Q.format(lo="1992-01-02", hi="1999-01-02",
                                   d0="0.00", d1="0.10", q=52)).rows()
    assert again == first
    assert counts.waits == 0
    assert counts.checks == {"memo": N_PREDICATES * n_chunks}
    assert counts.slices == {"chunk": n_chunks}
    assert [n for n in names if "slice" in n or "summar" in n] == []
    s.close()
    eng.close()


def test_kept_summaries_are_the_host_truth(reopened):
    """What is kept with the object, against numpy over the decoded
    column: valid rows, min and max as Python scalars in stored units."""
    eng, s = reopened
    s.execute(Q.format(lo="1992-01-01", hi="1999-01-01", d0="0.00",
                       d1="0.10", q=51))
    table = eng.get_table("li")
    for seg in table.segments:
        kept = seg.arrays.chunk_summaries
        assert kept is seg.validity.chunk_summaries    # one loader, one store
        assert len(kept) == 3 * 3                      # columns x chunks
        for (col, lo, hi), (n_valid, least, most) in kept.items():
            data, valid = seg.arrays.host_pair(col)
            vals = data[lo:hi][valid[lo:hi]]
            assert (n_valid, least, most) == (len(vals), vals.min().item(),
                                              vals.max().item())
            assert type(least) is int and type(n_valid) is int


SUMMARIZED = {
    "int64": np.array([5, -3, 9, 7, 2], np.int64),
    "int32": np.array([5, -3, 9, 7, 2], np.int32),
    "int8_codes": np.array([1, 0, 2, 1, 0], np.int8),
    "float64": np.array([0.5, -1.5, 2.5, 9.0, 0.0], np.float64),
    "float32": np.array([0.5, -1.5, 2.5, 9.0, 0.0], np.float32),
    "bool": np.array([True, False, True, True, False]),
    "vector": np.arange(10, dtype=np.float32).reshape(5, 2),
}


@pytest.mark.parametrize("valid", [
    [True] * 5, [True, False, False, True, True], [False] * 5],
    ids=["all_valid", "some_null", "all_null"])
@pytest.mark.parametrize("kind", list(SUMMARIZED))
def test_device_and_host_summaries_agree(kind, valid):
    """One program and one fetch give what numpy gives: the extremes are
    over the valid rows only, and a column that is not 1-d has none."""
    data, valid = SUMMARIZED[kind], np.array(valid)
    host = engmod._summarize_on_host(data, valid)
    counts = _Counts()
    found = engmod._chunk_summaries(
        ["c"], {"c": jnp.asarray(data)}, {"c": jnp.asarray(valid)},
        None, None)
    assert counts.waits == 1
    summary, source = found["c"]
    assert source == "device"
    n_valid = int(valid.sum())
    assert summary[0] == host[0] == n_valid
    if kind == "vector" or n_valid == 0:
        assert host[1:] == (None, None)
    if kind != "vector" and n_valid:
        assert summary == host == (n_valid, data[valid].min().item(),
                                   data[valid].max().item())
        assert type(summary[1]) is type(host[1])


def test_two_scans_filling_one_summary_agree(tmp_path):
    """The prefetch thread of a cold scan and another statement's own
    thread may fill one key at once: one entry stays, the same for both."""
    eng, s = _load(tmp_path)
    table = eng.get_table("li")
    filters = [_pred("ge", "id", dt.INT64, 0)]
    seen, errors = [], []

    def scan():
        try:
            seen.append(sum(n for *_, n, _live in table.iter_chunks(
                ["id"], BATCH_ROWS, filters=filters)))
        except Exception as e:                         # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=scan) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert errors == [] and seen == [ROWS] * 4
    for seg in table.segments:
        base = seg.base_gid        # ids were inserted in gid order
        assert seg.arrays.chunk_summaries == {
            ("id", lo, min(lo + BATCH_ROWS, seg.n_rows)):
            (min(lo + BATCH_ROWS, seg.n_rows) - lo, base + lo,
             base + min(lo + BATCH_ROWS, seg.n_rows) - 1)
            for lo in range(0, seg.n_rows, BATCH_ROWS)}
    s.close()
    eng.close()


# ------------------------------------------- (c) numpy takes the host path

def test_numpy_segments_dispatch_and_compile_nothing(tmp_path):
    """Fresh commits are numpy in RAM: a view a column, the check on the
    host and uncached, no program of any kind."""
    eng, s = _load(tmp_path, lazy=False)
    table = eng.get_table("li")
    assert not any(seg.is_lazy for seg in table.segments)
    filters = [_pred("ge", "id", dt.INT64, 0),
               _pred("lt", "qty", dt.decimal64(15, 2), 51, dt.INT64)]
    counts = _Counts()
    with _compiled() as names:
        chunks = list(map(engmod.live_rows, table.iter_chunks(
            ["id", "qty", "v"], BATCH_ROWS, filters=filters)))
    assert names == []
    assert counts.slices == {} and counts.waits == 0
    assert counts.checks == {"host": 2 * len(chunks)}
    assert counts.chunks == {"scanned": 3 * COMMITS}
    for arrays, validity, _d, _n in chunks:
        for a in list(arrays.values()) + list(validity.values()):
            assert isinstance(a, np.ndarray)
    first = table.segments[0]
    assert np.shares_memory(chunks[0][0]["id"], first.arrays["id"])
    s.close()
    eng.close()


# ------------------------------------- (d) a summary belongs to an object

def _ints(path, values, commits=1):
    """Table `t (a bigint, b bigint)` holding `values` in `commits`
    commits, re-opened from its checkpoint."""
    eng = Engine(LocalFS(str(path)))
    s = Session(catalog=eng)
    s.execute("create table t (a bigint, b bigint)")
    per = len(values) // commits
    for lo in range(0, len(values), per):
        s.execute("insert into t values " + ",".join(
            f"({a},{a % 7})" for a in values[lo:lo + per]))
    return _reopen(eng, s, path)


def _reopen(eng, s, path):
    eng.checkpoint()
    s.close()
    eng.close()
    eng = Engine.open(LocalFS(str(path)))
    s = Session(catalog=eng)
    s.execute("set batch_rows = 1000")
    return eng, s


def _sum_b(values, lo):
    return [(sum(a % 7 for a in values if a >= lo) or None,)]


def test_a_merge_rewrites_the_summaries_with_the_object(
        tmp_path, monkeypatch):
    """Chunk (0, 1000) of the first object holds a = 0..999, chunk (1000,
    2000) a = 1000..1999; the merged object's hold 500..1499 and
    1500..2499.  `a >= 1200` prunes the first under the old object and
    must scan it under the new; `a < 1500` scans the second under the old
    and prunes it under the new: straight after the merge (a RAM segment)
    and from the merged object re-opened."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    values = list(range(4000))
    eng, s = _ints(tmp_path, values, commits=2)

    def ask(outcomes_ge, outcomes_lt, source, checks_ge, checks_lt):
        # the second statement finds what the first one kept
        then = "host" if source == "host" else "memo"
        counts = _Counts()
        assert s.execute("select sum(b) from t where a >= 1200").rows() \
            == _sum_b(values, 1200)
        assert counts.chunks == outcomes_ge
        assert counts.checks == {source: checks_ge}
        counts = _Counts()
        assert s.execute("select count(*) from t where a < 1500").rows() \
            == [(sum(1 for a in values if a < 1500),)]
        assert counts.chunks == outcomes_lt
        assert counts.checks == {then: checks_lt}

    ask({"pruned_chunk": 1, "scanned": 3},
        {"scanned": 2, "pruned_segment": 2}, "device", 4, 2)
    old = eng.get_table("t").segments[0].arrays.chunk_summaries
    assert old == {("a", 0, 1000): (1000, 0, 999),
                   ("a", 1000, 2000): (1000, 1000, 1999)}

    s.execute("delete from t where a < 500")
    values = values[500:]
    assert eng.merge_table("t") == len(values)
    after = ({"scanned": 4}, {"scanned": 1, "pruned_chunk": 3})
    ask(*after, "host", 4, 4)                         # RAM: uncached

    eng, s = _reopen(eng, s, tmp_path)
    blockcache.CACHE.clear()
    ask(*after, "device", 4, 4)
    ask(*after, "memo", 4, 4)
    (merged,) = eng.get_table("t").segments
    assert merged.arrays.chunk_summaries[("a", 0, 1000)] == (1000, 500, 1499)
    assert len(old) == 2                              # and nobody wrote there
    s.close()
    eng.close()


def test_two_engines_objects_at_one_path_keep_their_own(
        tmp_path, monkeypatch):
    """Two engines of one process with different objects at the same path
    (the `_fs_token` case): ascending in one, descending in the other.
    Each prunes by its own values."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    up = list(range(2000))
    eng_a, s_a = _ints(tmp_path / "a", up)
    eng_b, s_b = _ints(tmp_path / "b", up[::-1])
    seg_a, seg_b = (e.get_table("t").segments[0] for e in (eng_a, eng_b))
    assert seg_a.obj_path == seg_b.obj_path and seg_a.zonemaps["a"][:2] \
        == seg_b.zonemaps["a"][:2] == [0, 1999]
    for _round in range(2):
        for s in (s_a, s_b):
            counts = _Counts()
            assert s.execute("select sum(b) from t where a >= 1500").rows() \
                == _sum_b(up, 1500)
            assert counts.chunks == {"scanned": 1, "pruned_chunk": 1}
    assert seg_a.arrays.chunk_summaries == {
        ("a", 0, 1000): (1000, 0, 999), ("a", 1000, 2000): (1000, 1000, 1999)}
    assert seg_b.arrays.chunk_summaries == {
        ("a", 0, 1000): (1000, 1000, 1999), ("a", 1000, 2000): (1000, 0, 999)}
    for s, eng in ((s_a, eng_a), (s_b, eng_b)):
        s.close()
        eng.close()


# ------------------------- (e) tombstones make the check prune less only

def test_dead_extremes_leave_the_chunk_scanned(tmp_path, monkeypatch):
    """The rows that hold chunk (0, 1000)'s maximum are deleted.  The
    kept summary is of the object, not of the snapshot: the chunk is
    scanned where an exact check of the visible rows would prune it, and
    the answer is the visible rows'.  A thinned chunk keeps its length:
    its dead rows ride the row mask, and no program runs a column."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    values = list(range(4000))
    eng, s = _ints(tmp_path, values, commits=2)
    s.execute("select sum(b) from t where a >= 0")     # summaries filled
    s.execute("delete from t where a >= 900 and a < 1000")
    visible = [a for a in values if not 900 <= a < 1000]
    counts = _Counts()
    assert s.execute("select sum(b), count(*) from t where a >= 950").rows() \
        == [(sum(a % 7 for a in visible if a >= 950),
             sum(1 for a in visible if a >= 950))]
    assert counts.chunks == {"scanned": 4}
    assert counts.checks == {"memo": 4} and counts.waits == 0
    # chunk (0, 1000) alone has dead rows: masked, not gathered
    assert counts.slices == {"chunk": 4}
    # a chunk with every row dead is still skipped before any read
    s.execute("delete from t where a < 1000")
    counts = _Counts()
    assert s.execute("select count(*) from t where a >= 0").rows() \
        == [(3000,)]
    assert counts.chunks == {"all_dead": 1, "scanned": 3}
    s.close()
    eng.close()


# ------------------------------------------------ (f) pruning from memory

@pytest.mark.parametrize("where, chunks, checks", [
    ("a >= 1500", {"scanned": 3, "pruned_chunk": 1}, 4),
    ("a < 500", {"scanned": 1, "pruned_chunk": 1, "pruned_segment": 2}, 2),
    ("a >= 3000 and b >= 0", {"scanned": 1, "pruned_chunk": 1,
                              "pruned_segment": 2}, 3),
    ("b >= 0 and a >= 3000", {"scanned": 1, "pruned_chunk": 1,
                              "pruned_segment": 2}, 4),
    ("b >= 7", {"pruned_segment": 4}, 0),
    ("a = 3000", {"scanned": 1, "pruned_chunk": 1, "pruned_segment": 2}, 2),
])
def test_a_kept_range_prunes_with_no_wait(tmp_path, monkeypatch, where,
                                          chunks, checks):
    """Once the summaries are kept, a chunk whose range excludes the
    predicate is `pruned_chunk` from them alone; the first predicate that
    excludes ends the chunk's check."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    values = list(range(4000))
    eng, s = _ints(tmp_path, values, commits=2)
    s.execute("select sum(b) from t where a >= 0 and b >= 0")
    counts = _Counts()
    want = s.execute(f"select count(*) from t where {where}").rows()
    assert counts.chunks == chunks
    assert counts.waits == 0
    assert counts.checks == ({"memo": checks} if checks else {})
    a, b = np.array(values), np.array(values) % 7     # noqa: F841
    expr = where.replace(" and ", ") & (").replace(" = ", " == ")
    assert want == [(int(eval(f"(({expr}))").sum()),)]
    s.close()
    eng.close()
