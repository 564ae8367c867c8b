"""Compile-only tests for a described TPU v5e (`v5e:2x2`), no chip needed:
the Pallas kernel of `ops/pallas_kernels.py` and the programs the served
path hands the chip, at the widths `chip_smoke.py` and the benchmark
drive: one fused TPC-H Q1 step at the SF1 batch shape, Q3's fused join
programs, the IVF-Flat and IVF-PQ searches over 1M x 768, the unfused
float32 grouped step, the scan's one-program chunk slice and chunk
summary at an SF1 segment's shape.  The chip's
compiler is installed in the sandbox; what it refuses here (int64 block
indices, unaligned blocks, fast-memory overflow, a program that does not
fit) it refuses on the chip, and interpret mode shows none of it.

The only file of its kind: the topology is described inside a
module-scoped fixture (never at import — one process holds the TPU
library at a time, and every xdist worker imports every test file), the
compiles run in this process, and the persistent compilation cache is off
around them (an entry compiled for a described device cannot be read
back without the chip).  Nothing runs: no result, no time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from matrixone_tpu.ops import kernels as HK


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *specs):
    return jax.jit(fn).lower(*specs).compile().as_text()


def _spec(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def test_adc_kernel_compiles_for_v5e(one_chip):
    """The ADC kernel at the 96 subspaces IVF-PQ picks for 768
    dimensions, 256 query-probe groups, its 128-lane tile."""
    from matrixone_tpu.ops import pallas_kernels as PK
    spec = _spec(one_chip)
    text = _compiled_text(
        lambda codes, lut: PK.adc_score_pallas(codes, lut, tile_c=128,
                                               interpret=False),
        spec((256, 128, 96), jnp.uint8), spec((256, 96, 256), jnp.float32))
    assert "tpu_custom_call" in text


def _lowered_for(one_chip, fn, compiled):
    """`fn`, a step the CPU run of a statement traced, compiled again
    for the described chip at the shapes that run gave it."""
    args, _kwargs = compiled.args_info
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    return specs, jax.jit(fn).lower(*specs).compile()


@pytest.fixture(scope="module")
def q3_steps():
    """name -> [(step function, its CPU compile)] of the fused programs a
    Q3 statement traces under the policies a TPU resolves."""
    from matrixone_tpu.frontend.session import Session
    from matrixone_tpu.utils import tpch
    from matrixone_tpu.vm import fusion as FF
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(HK, "platform", lambda: "tpu")
        mp.setenv("MO_PLAN_FUSION", "1")
        mp.setenv("MO_FUSION_MIN_ROWS", "0")
        s = Session()
        try:
            s.execute("set batch_rows = 8192")
            tpch.load_lineitem(s.catalog, 20_000, seed=2)
            tpch.load_tpch_q3(s.catalog, 4_000, seed=2)
            FF.CACHE.clear()    # so that this statement traces its own
            assert len(s.execute(tpch.Q3_SQL).rows()) == 10
        finally:
            s.close()
    steps = {}
    for e in FF.CACHE._lru.snapshot():
        for slot, compiled in e["compiled"].items():
            steps.setdefault(e["fn"][slot].__name__, []).append(
                (e["fn"][slot], compiled))
    return steps


@pytest.mark.parametrize("program", ["frag_join_build",
                                     "frag_join_stream_step"])
def test_q3_fused_join_programs_hold_no_custom_call(one_chip, q3_steps,
                                                    program):
    """Q3's fused build and probe steps (both builds are unique on an
    integer key: a direct-address table, one gather a probe row),
    lowered for the described v5e: they compile, and no hand-written
    kernel is in them."""
    assert q3_steps.get(program), f"Q3 traced no {program}"
    for fn, compiled in q3_steps[program]:
        _specs, lowered = _lowered_for(one_chip, fn, compiled)
        assert "tpu_custom_call" not in lowered.as_text()


@pytest.fixture(scope="module")
def ssb_q41_steps():
    """name -> [(step function, its CPU compile)] of the fused programs
    SSB Q4.1 (four joins under a grouped aggregate) traces."""
    from matrixone_tpu.frontend.session import Session
    from matrixone_tpu.utils import ssb
    from matrixone_tpu.vm import fusion as FF
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(HK, "platform", lambda: "tpu")
        mp.setenv("MO_FUSION_MIN_ROWS", "0")
        s = Session()
        try:
            s.execute("set batch_rows = 8192")
            ssb.load_ssb(s.catalog, ssb.gen_ssb(0.005, 2))
            FF.CACHE.clear()
            assert s.execute(ssb.render("q4.1", ssb.PAPER_PARAMS["q4.1"])
                             ).rows()
        finally:
            s.close()
    steps = {}
    for e in FF.CACHE._lru.snapshot():
        for slot, compiled in e["compiled"].items():
            steps.setdefault(e["fn"][slot].__name__, []).append(
                (e["fn"][slot], compiled))
    return steps


def _rank3_index_gathers(text):
    """Gathers of a lowered program whose index is [rows, lanes, 1]."""
    import re
    return re.findall(r"stablehlo\.gather.*tensor<\d+x\d+x1xi32>", text)


@pytest.mark.parametrize("statement", ["tpch q3", "ssb q4.1"])
def test_probe_steps_are_small_and_gather_through_flat_indexes(
        one_chip, q3_steps, ssb_q41_steps, statement):
    """What made a probe step compile for 132-138 s on the chip (PERF.md
    section 6, PR 33), pinned by structure and not by the clock: no gather
    through a [rows, lanes] index, no tensor with a minor dimension of 4
    lanes, and a lowered step of a few hundred lines.  The steps also
    compile for the described v5e, 16 times wider than the test traced
    them."""
    steps = q3_steps if statement == "tpch q3" else ssb_q41_steps
    probes = steps.get("frag_join_stream_step", [])
    assert len(probes) >= (2 if statement == "tpch q3" else 4)
    for fn, compiled in probes:
        args, _kwargs = compiled.args_info
        wide = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(d * 16 if d >= 1024 else d for d in a.shape),
                a.dtype, sharding=one_chip), args)
        lowered = jax.jit(fn).lower(*wide)
        text = lowered.as_text()
        assert not _rank3_index_gathers(text)
        assert "x4xi" not in text and "x4xui" not in text
        assert text.count("\n") < 400
        lowered.compile()


@pytest.fixture(scope="module")
def ssb_probe_levels():
    """template -> {dimension table: (probe step, its CPU compile)} of the
    stream steps Q2.1 and Q4.1 trace, a level named by the table its
    build side scans (asked of the operator that keys the program)."""
    from matrixone_tpu.frontend.session import Session
    from matrixone_tpu.utils import ssb
    from matrixone_tpu.vm import fusion as FF
    from matrixone_tpu.vm import fusion_join as FJ
    levels = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(HK, "platform", lambda: "tpu")
        mp.setenv("MO_FUSION_MIN_ROWS", "0")
        keyed = {}                               # build table -> program key
        runtime_key = FJ.FusedJoinProbeOp._probe_runtime_key

        def noting_the_table(op, *args, **kwargs):
            key = runtime_key(op, *args, **kwargs)
            keyed[FJ._scanned_table(op._join.node.right)] = key
            return key
        mp.setattr(FJ.FusedJoinProbeOp, "_probe_runtime_key",
                   noting_the_table)
        s = Session()
        try:
            s.execute("set batch_rows = 8192")
            ssb.load_ssb(s.catalog, ssb.gen_ssb(0.005, 2))
            for template in ("q2.1", "q4.1"):
                FF.CACHE.clear()
                keyed.clear()
                assert s.execute(ssb.render(
                    template, ssb.PAPER_PARAMS[template])).rows()
                levels[template] = {
                    table: (e["fn"]["step"], e["compiled"]["step"])
                    for table, e in ((t, FF.CACHE.entry(k))
                                     for t, k in keyed.items())
                    if "step" in e["compiled"]}
        finally:
            s.close()
    return levels


def _probe_length_gathers(fn, compiled, one_chip):
    """Gathers of the step lowered for the described chip whose result has
    as many lanes as the probe batch (the lookup and a build column's data
    and validity; a gather into a dictionary's few entries is not one)."""
    import re
    args, _kwargs = compiled.args_info
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    lanes = args[3].shape[0]                      # the probe batch's mask
    lowered = jax.jit(fn).lower(*specs)
    return len(re.findall(rf"stablehlo\.gather.*-> tensor<{lanes}x[^>]*>",
                          lowered.as_text())), lowered


@pytest.mark.parametrize("template,table,gathers", [
    ("q2.1", "part", 3), ("q2.1", "supplier", 1), ("q2.1", "dates", 3),
    ("q4.1", "supplier", 1), ("q4.1", "customer", 3), ("q4.1", "part", 1),
    ("q4.1", "dates", 3)])
def test_a_probe_step_gathers_only_what_is_read_above_the_join(
        one_chip, ssb_probe_levels, template, table, gathers):
    """The lookup is one gather at probe length, and every build column
    the join hands up is two more (data, validity): a level whose build
    only filters (Q2.1's supplier) holds ONE, Q4.1's customer level
    (`c_nation`) three, where all of the build side's columns were
    gathered before (PERF.md section 6, PR 34)."""
    fn, compiled = ssb_probe_levels[template][table]
    n, lowered = _probe_length_gathers(fn, compiled, one_chip)
    assert n == gathers, lowered.as_text()
    lowered.compile()


def test_a_four_lane_probe_lowers_lane_major(one_chip):
    """A build with duplicates still expands four lanes a probe row: the
    lanes are concatenated ([4, rows], lane-major), never interleaved, so
    no array of the lowered probe has 4 as its minor dimension."""
    from matrixone_tpu.container import dtypes as dt
    from matrixone_tpu.container.device import DeviceBatch, DeviceColumn
    from matrixone_tpu.sql import plan as P
    from matrixone_tpu.sql.expr import BoundCol
    from matrixone_tpu.vm import join as J
    from matrixone_tpu.vm.exprs import ExecBatch
    rows, build_rows = 1 << 17, 1 << 14
    left = P.Scan("l", ["k", "v"], [("l.k", dt.INT64), ("l.v", dt.INT64)])
    right = P.Scan("r", ["k", "w"], [("r.k", dt.INT64), ("r.w", dt.INT64)])
    node = P.Join("left", left, right, [BoundCol("l.k", dt.INT64)],
                  [BoundCol("r.k", dt.INT64)], None,
                  left.schema + right.schema)

    def batch(names, k, v, mask):
        valid = jnp.ones(k.shape, jnp.bool_)
        cols = {names[0]: DeviceColumn(k, valid, dt.INT64),
                names[1]: DeviceColumn(v, valid, dt.INT64)}
        return ExecBatch(batch=DeviceBatch(columns=cols,
                                           n_rows=jnp.sum(mask)),
                         dicts={}, mask=mask)

    def probe(pk, pv, pmask, bk, bw, bmask):
        pex = batch(("l.k", "l.v"), pk, pv, pmask)
        build = batch(("r.k", "r.w"), bk, bw, bmask)
        bkeys, _ = J.build_key_columns(node, build)
        sorted_hash, order, _bv = J.build_sorted_hash(bkeys, build.mask)
        pkeys = J.probe_key_columns(node, pex, [None])
        phash, pvalid = J.hash_valid_keys(pkeys, pex.mask)
        out, overflow, _ = J.expand_probe(node, pex, build, sorted_hash,
                                          order, phash, pvalid, pkeys,
                                          bkeys, 4, None)
        return out.mask, out.batch.columns["r.w"].data, overflow

    spec = _spec(one_chip)
    text = jax.jit(probe).lower(
        spec((rows,), jnp.int64), spec((rows,), jnp.int64),
        spec((rows,), jnp.bool_), spec((build_rows,), jnp.int64),
        spec((build_rows,), jnp.int64), spec((build_rows,), jnp.bool_)
    ).as_text()
    assert f"tensor<{4 * rows}xi64>" in text       # four lanes a row
    assert not _rank3_index_gathers(text)
    assert "x4xi" not in text and "x4xui" not in text


def test_fused_q1_step_compiles_for_v5e(one_chip, monkeypatch):
    """One fused TPC-H Q1 step (scan batch of 2^20 rows, DECIMAL money
    columns: filter + dense group-by + int64 sums).  The test stands in
    for the platform, so the CPU run of the statement traces the step
    under the policies a TPU resolves (narrow encodings, carry
    donation); that step is then lowered for the described chip."""
    from matrixone_tpu.frontend.session import Session
    from matrixone_tpu.ops import encodings as ENC
    from matrixone_tpu.utils import tpch_full as T
    from matrixone_tpu.vm import fusion as FF
    monkeypatch.setattr(HK, "platform", lambda: "tpu")
    monkeypatch.delenv("MO_NARROW_ENCODINGS", raising=False)
    assert ENC.enabled()
    s = Session()
    T.load_tpch(s.catalog, tables={
        "lineitem": T.gen_tpch(0.18, seed=1)["lineitem"]})
    before = {id(e) for e in FF.CACHE._lru.snapshot()}
    assert len(s.execute(T.QUERIES[1]).rows()) == 4
    steps = [(e["fn"][slot], compiled)
             for e in FF.CACHE._lru.snapshot() if id(e) not in before
             for slot, compiled in e["compiled"].items()]
    assert steps, "Q1 did not run fused"
    rows = 0
    for fn, compiled in steps:
        specs, lowered = _lowered_for(one_chip, fn, compiled)
        rows = max([rows] + [a.shape[0] for a in jax.tree.leaves(specs)
                             if a.shape])
        assert lowered.memory_analysis()
    assert rows == 1 << 20, f"largest step input has {rows} rows"


@pytest.mark.parametrize("batch", [1, 128])
def test_ivf_search_with_exact_rerank_compiles_for_v5e(one_chip, batch):
    """The program a vector top-k statement waits for: the IVF-Flat
    search (centroid probe, bfloat16 list scan) with the exact float32
    re-rank of its 60 candidates, at the benchmark's shapes (1M x 768
    float32 residuals, 1,024 lists of at most 4,096 rows, nprobe 8), for
    one query a statement and for a 128-query batch in chunks of 32.  It
    has to fit beside the 3.1 GB index."""
    from matrixone_tpu.vectorindex import ivf_flat
    spec = _spec(one_chip)
    n, d, lists, pad = 1_000_000, 768, 1024, 4096
    index = ivf_flat.IvfFlatIndex(
        centroids=spec((lists, d), jnp.float32),
        vectors=spec((n, d), jnp.float32),
        r_norm2=spec((n,), jnp.float32), r_dot_c=spec((n,), jnp.float32),
        ids=spec((n,), jnp.int32), offsets=spec((lists + 1,), jnp.int32),
        metric=ivf_flat.METRIC_L2, max_cluster_size=pad, n=n)
    compiled = ivf_flat._search.lower(
        index, spec((batch, d), jnp.float32), k=60, nprobe=8,
        query_chunk=min(batch, 32), compute_dtype=jnp.bfloat16,
        exact=True).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (1 << 30) * min(batch, 32), \
        mem.temp_size_in_bytes
    assert "ivf_rerank_exact" in compiled.as_text()
    assert "tpu_custom_call" not in compiled.as_text()


def test_ivf_pq_search_compiles_for_v5e(one_chip, monkeypatch):
    """IVF-PQ over 1M x 768 as SQL builds it: 96 subspaces of 8
    dimensions (`indexing._pick_subspaces(768)`), 1,024 lists padded to
    1,152 rows, nprobe 8, one query: the lookup tables and the ADC
    scores of the candidates' code bytes, which on a TPU (the test
    stands in for the platform) are the kernel's."""
    from matrixone_tpu.indexing import _pick_subspaces
    from matrixone_tpu.vectorindex import ivf_pq
    monkeypatch.setattr(HK, "platform", lambda: "tpu")
    spec = _spec(one_chip)
    n, d, lists, pad = 1_000_000, 768, 1024, 1152
    m = _pick_subspaces(d)
    assert m == 96
    index = ivf_pq.IvfPqIndex(
        centroids=spec((lists, d), jnp.float32),
        codebooks=spec((m, 256, d // m), jnp.float32),
        codes=spec((n, m), jnp.uint8), ids=spec((n,), jnp.int32),
        offsets=spec((lists + 1,), jnp.int32),
        metric=ivf_pq.METRIC_L2, max_cluster_size=pad, n=n)
    compiled = ivf_pq._search.lower(
        index, spec((1, d), jnp.float32), k=60, nprobe=8,
        query_chunk=1).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30
    assert "tpu_custom_call" in compiled.as_text()


def test_unfused_float32_grouped_step_compiles_for_v5e(one_chip):
    """`AggOp`'s per-batch partial for `sum(<FLOAT column>)` over a 2^20
    row batch in 4,096 groups: a float32 scatter-add and a count."""
    from types import SimpleNamespace

    from matrixone_tpu.container import dtypes as dt
    from matrixone_tpu.container.device import DeviceColumn
    from matrixone_tpu.sql.expr import AggCall, BoundCol
    from matrixone_tpu.vm.operators import _grouped_step
    spec = _spec(one_chip)
    call = AggCall("sum", BoundCol("x", dt.FLOAT32), False, dt.FLOAT32)

    def step(gids, data, validity, row_mask):
        return _grouped_step(call, SimpleNamespace(gids=gids),
                             DeviceColumn(data, validity, dt.FLOAT32),
                             row_mask, 4096)
    rows = 1 << 20
    text = _compiled_text(step, spec((rows,), jnp.int32),
                          spec((rows,), jnp.float32),
                          spec((rows,), jnp.bool_), spec((rows,), jnp.bool_))
    assert "tpu_custom_call" not in text


#: one lineitem segment of TPC-H SF1 as the device tier holds it for Q1:
#: four DECIMALs, two dictionary-code columns, a DATE, and their validity
SF1_SEGMENT_ROWS = 1_500_304
Q1_COLUMN_DTYPES = [jnp.int64] * 4 + [jnp.int32] * 3 + [jnp.bool_] * 7


@pytest.mark.parametrize("length", [1 << 20, SF1_SEGMENT_ROWS - (1 << 20)],
                         ids=["full_chunk", "ragged_tail"])
def test_scan_chunk_slice_compiles_for_v5e(one_chip, length):
    """`storage/engine._slice_rows`: the fourteen arrays of a Q1 chunk
    cut out of their segment by one program, `start` traced."""
    from matrixone_tpu.storage import engine as engmod
    spec = _spec(one_chip)
    cols = tuple(spec((SF1_SEGMENT_ROWS,), d) for d in Q1_COLUMN_DTYPES)
    compiled = engmod._slice_rows.lower(
        cols, spec((), jnp.int32), length=length).compile()
    out = jax.tree.leaves(compiled.out_info)
    assert [(o.shape, o.dtype) for o in out] \
        == [((length,), jnp.dtype(d)) for d in Q1_COLUMN_DTYPES]
    assert "dynamic-slice" in compiled.as_text()


def test_scan_chunk_summary_compiles_for_v5e(one_chip):
    """`storage/engine._summarize_on_device` over Q6's predicate columns
    of a full chunk: a DATE and two DECIMALs, each (n_valid, min, max)."""
    from matrixone_tpu.storage import engine as engmod
    spec = _spec(one_chip)
    dtypes = [jnp.int32, jnp.int64, jnp.int64]
    compiled = engmod._summarize_on_device.lower(
        tuple(spec((1 << 20,), d) for d in dtypes),
        tuple(spec((1 << 20,), jnp.bool_) for _ in dtypes)).compile()
    out = jax.tree.leaves(compiled.out_info)
    assert [o.dtype for o in out] == [
        jnp.dtype(d) for dt_ in dtypes for d in (jnp.int64, dt_, dt_)]
    assert all(o.shape == () for o in out)
