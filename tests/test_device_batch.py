"""`container/device.from_numpy` over device-resident input.

The block cache's device tier hands a scan `jax.Array` slices.  A full
chunk is at its bucket length and is taken as it is; a segment's ragged last
chunk (or one thinned by tombstones) is under it, and is padded on the
device: it must come out as the host path builds it, without the column
ever passing through the host (PERF.md section 5: that round trip was 47%
of a TPC-H statement on the v5e).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from matrixone_tpu.container import device as dev, dtypes as dt
from matrixone_tpu.frontend import Session
from matrixone_tpu.storage import blockcache
from matrixone_tpu.storage.engine import Engine
from matrixone_tpu.storage.fileservice import LocalFS
from matrixone_tpu.utils import metrics as M, qa

BUCKET = 1024
PATHS = ("device", "device_pad", "host", "roundtrip")

#: name -> (declared SQL type, numpy dtype of the array handed in)
COLUMNS = {
    "int64": (dt.INT64, np.int64),
    "int32": (dt.INT32, np.int32),
    "int8_codes": (dt.INT32, np.int8),       # narrow dict codes stay narrow
    "int16_codes": (dt.INT32, np.int16),
    "float64": (dt.FLOAT64, np.float64),
    "float32": (dt.FLOAT32, np.float32),
    "date": (dt.DATE, dt.DATE.np_dtype),
    "bool": (dt.BOOL, np.bool_),
    "vector": (dt.vecf32(8), np.float32),
}


def _values(kind, n, rng):
    sql_type, np_dtype = COLUMNS[kind]
    shape = (n, 8) if kind == "vector" else (n,)
    if np.dtype(np_dtype).kind == "f":
        return rng.standard_normal(shape).astype(np_dtype)
    if np.dtype(np_dtype).kind == "b":
        return rng.integers(0, 2, shape).astype(np.bool_)
    return rng.integers(-100, 100, shape).astype(np_dtype)


def _paths():
    return {p: M.from_numpy_columns.get(path=p) for p in PATHS}


def _moved(before):
    return {p: v - before[p] for p, v in _paths().items() if v != before[p]}


class _HostPullRefused:
    """Stands in for `np` inside container/device.py: `asarray` of a device
    array raises.  On the CPU backend a device-to-host copy is free and
    `jax.transfer_guard_device_to_host` lets it pass, so the guard alone
    would show nothing here; on the chip the guard is what bites."""

    def __getattr__(self, name):
        return getattr(np, name)

    @staticmethod
    def asarray(a, *args, **kw):
        if isinstance(a, jax.Array):
            raise AssertionError("a device array was pulled to the host")
        return np.asarray(a, *args, **kw)


@pytest.fixture
def no_host_pull(monkeypatch):
    monkeypatch.setattr(dev, "np", _HostPullRefused())
    with jax.transfer_guard_device_to_host("disallow"):
        yield


@pytest.mark.parametrize("n", [1, BUCKET - 1, BUCKET, 0])
@pytest.mark.parametrize("validity", ["none", "numpy", "jax"])
@pytest.mark.parametrize("kind", list(COLUMNS))
def test_device_resident_input_equals_the_host_path(kind, validity, n,
                                                    no_host_pull):
    sql_type, _ = COLUMNS[kind]
    rng = np.random.default_rng(n + len(kind))
    host = _values(kind, n, rng)
    host_val = None if validity == "none" else rng.integers(0, 4, n) > 0
    want = dev.from_numpy({"c": host}, {"c": sql_type},
                          {"c": host_val}, n_rows=n)
    val = jnp.asarray(host_val) if validity == "jax" else host_val
    before = _paths()
    got = dev.from_numpy({"c": jnp.asarray(host)}, {"c": sql_type},
                         {"c": val}, n_rows=n)
    assert _moved(before) == {
        "device" if n == BUCKET else "device_pad": 1}
    c, w = got.columns["c"], want.columns["c"]
    assert int(got.n_rows) == n and got.padded_len == BUCKET
    assert c.dtype == sql_type
    assert c.data.dtype == w.data.dtype == host.dtype
    assert c.data.shape == w.data.shape
    assert c.validity.dtype == jnp.bool_ and c.validity.shape == (BUCKET,)
    data, valid = jax.device_get((c.data, c.validity))
    np.testing.assert_array_equal(data[:n].view(np.uint8),
                                  host.view(np.uint8))     # bit-equal
    np.testing.assert_array_equal(valid, jax.device_get(w.validity))
    assert not valid[n:].any()
    if not qa.armed():
        assert not data[n:].any()         # the host path's zeros


@pytest.mark.parametrize("kind", list(COLUMNS))
def test_the_tail_is_poisoned_when_the_canary_is_armed(kind, no_host_pull):
    """`qa.pad_fill`'s contract on the device path: moqa's pad-leak drill
    must see a poisoned tail whichever path built the batch."""
    sql_type, np_dtype = COLUMNS[kind]
    n = 700
    host = _values(kind, n, np.random.default_rng(7))
    with qa.armed_scope():
        got = dev.from_numpy({"c": jnp.asarray(host)}, {"c": sql_type},
                             n_rows=n)
        want = dev.from_numpy({"c": host}, {"c": sql_type}, n_rows=n)
    tail = jax.device_get(got.columns["c"].data)[n:]
    canary = qa.canary_value(np_dtype)
    assert len(tail) == BUCKET - n
    if np.dtype(np_dtype).kind == "f":
        assert np.isnan(canary) and np.isnan(tail).all()
    else:
        assert (tail == canary).all()
    np.testing.assert_array_equal(
        tail.view(np.uint8),
        jax.device_get(want.columns["c"].data)[n:].view(np.uint8))
    assert not jax.device_get(got.columns["c"].validity)[n:].any()


def test_a_chunk_of_mixed_columns_is_one_batch(no_host_pull):
    """A scan's chunk as `_read_chunk` hands it: device columns with device
    validity beside a host row-id column.  The batch keeps the order of its
    columns: fused programs are keyed by it."""
    n = 600
    arrays = {"a": jnp.arange(n, dtype=jnp.int64),
              "rowid": np.arange(n, dtype=np.int64),
              "b": jnp.ones(n, jnp.float32)}
    types = {"a": dt.INT64, "rowid": dt.INT64, "b": dt.FLOAT32}
    before = _paths()
    got = dev.from_numpy(arrays, types,
                         {"a": jnp.arange(n) % 2 == 0, "rowid": None,
                          "b": None}, n_rows=n)
    assert list(got.columns) == ["a", "rowid", "b"]
    assert _moved(before) == {"device_pad": 2, "host": 1}
    assert {c.padded_len for c in got.columns.values()} == {BUCKET}
    assert int(jax.device_get(got.columns["a"].validity).sum()) == n // 2


def test_a_dtype_the_column_cannot_keep_counts_as_a_round_trip():
    """The one case in which a device array still goes through the host."""
    before = _paths()
    got = dev.from_numpy({"c": jnp.arange(10, dtype=jnp.int64)},
                         {"c": dt.INT32}, n_rows=10)
    assert _moved(before) == {"roundtrip": 1}
    assert got.columns["c"].data.dtype == jnp.int32


# ------------------------------------------------------- through the engine

ROWS, COMMITS, BATCH_ROWS = 6000, 2, 2048      # chunks of 2048 and 952 rows

Q1 = ("select flag, status, sum(qty), sum(price * (1 - disc)), avg(qty),"
      " avg(disc), count(*) from li where ship <= date '1998-09-02'"
      " group by flag, status order by flag, status")
Q6 = ("select sum(price * disc), count(qty) from li where ship >= date"
      " '1994-01-01' and ship < date '1995-01-01' and disc between 0.05"
      " and 0.07 and qty < 24")


def _load(path):
    eng = Engine(LocalFS(str(path)))
    s = Session(catalog=eng)
    s.execute("create table li (id bigint primary key, qty decimal(15,2),"
              " price decimal(15,2), disc decimal(15,2), flag char(1),"
              " status char(1), ship date, note varchar(10))")
    rng = np.random.default_rng(30)
    per = ROWS // COMMITS
    for lo in range(0, ROWS, per):
        rows = []
        for i in range(lo, lo + per):
            qty = "null" if i % 97 == 0 else f"{rng.integers(1, 51)}.00"
            day = np.datetime64("1992-01-02") + int(rng.integers(0, 2500))
            rows.append(
                f"({i},{qty},{rng.integers(90000, 10000000) / 100:.2f},"
                f"0.{rng.integers(0, 11):02d},'{'ANR'[i % 3]}',"
                f"'{'FO'[i % 2]}','{day}','n{i % 7}')")
        s.execute("insert into li values " + ",".join(rows))
    return eng, s


def _answers(s):
    s.execute(f"set batch_rows = {BATCH_ROWS}")
    return s.execute(Q1).rows(), s.execute(Q6).rows()


def test_ragged_chunks_of_a_reopened_table_stay_on_the_device(
        tmp_path, monkeypatch):
    """Two commits, checkpoint, close, re-open: every segment is served by
    the device tier and ends in a ragged chunk.  The statements answer as
    they did from host arrays, no column goes through the host, and once
    warm nothing compiles."""
    monkeypatch.setenv("MO_FUSION_MIN_ROWS", "0")
    eng, s = _load(tmp_path)
    before = _paths()
    from_host = _answers(s)
    assert set(_moved(before)) == {"host"}     # in-memory segments: numpy
    assert len(from_host[0]) == 6 and from_host[1][0][1] > 0
    eng.checkpoint()
    eng.close()
    blockcache.CACHE.clear()
    s = Session(catalog=Engine.open(LocalFS(str(tmp_path))))

    before = _paths()
    assert _answers(s) == from_host
    moved = _moved(before)
    assert "roundtrip" not in moved and "host" not in moved
    # of a segment's two chunks the first is full, the second ragged
    assert moved["device_pad"] == moved["device"] > 0

    compiles = []

    def on_duration(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name", "?"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        assert _answers(s) == from_host
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert "roundtrip" not in _moved(before)
