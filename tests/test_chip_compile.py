"""Compile-only tests for a described TPU v5e (`v5e:2x2`), no chip needed:
the Pallas kernels of `ops/pallas_kernels.py` at the widths
`chip_smoke.py` drives, and one fused TPC-H Q1 step at the SF1 batch
shape.  The chip's compiler is installed in the sandbox; what it refuses
here (int64 block indices, unaligned blocks, fast-memory overflow) it
refuses on the chip, and interpret mode shows none of it.

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
from matrixone_tpu.ops import pallas_kernels as PK


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


# (kernel, its keyword arguments, argument shapes and dtypes): the
# widths are chip_smoke.py's — 768-d vectors, 1,024 lists, one query per
# SQL statement or a 64-query batch, the seam's 512-row tile over 4,096
# groups, an SF1 orders build side under a 2^20-row probe batch, and the
# 96 subspaces IVF-PQ picks for 768 dimensions at its 128-lane pad
KERNELS = {
    "l2_one_query": (
        PK.l2_distance_sq_pallas, dict(tile_m=1024),
        [((1024, 768), jnp.float32), ((1, 768), jnp.float32)]),
    "l2_query_batch": (
        PK.l2_distance_sq_pallas, dict(tile_m=1024),
        [((8192, 768), jnp.float32), ((64, 768), jnp.float32)]),
    "l2_masked": (
        PK.l2_distance_sq_masked_pallas, dict(tile_m=1024),
        [((8192, 768), jnp.float32), ((64, 768), jnp.float32),
         ((8192,), jnp.bool_)]),
    "segment_sum_seam_tile": (
        PK.segment_sum_pallas, dict(num_segments=4096, tile_n=512),
        [((1 << 20,), jnp.float32), ((1 << 20,), jnp.int32),
         ((1 << 20,), jnp.bool_)]),
    "segment_sum_default_tile": (
        PK.segment_sum_pallas, dict(num_segments=4096),
        [((1 << 20,), jnp.float32), ((1 << 20,), jnp.int32),
         ((1 << 20,), jnp.bool_)]),
    "sorted_search_sf1": (
        PK.sorted_search_pallas, {},
        [((1_500_000,), jnp.uint64), ((1 << 20,), jnp.uint64)]),
    "adc_768d": (
        PK.adc_score_pallas, dict(tile_c=128),
        [((256, 128, 96), jnp.uint8), ((256, 96, 256), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, kwargs, shapes = KERNELS[name]
    spec = _spec(one_chip)
    text = _compiled_text(
        lambda *a: fn(*a, interpret=False, **kwargs),
        *[spec(shape, dtype) for shape, dtype in shapes])
    assert "tpu_custom_call" in text


def test_seam_routes_the_probe_to_the_kernel_on_tpu(one_chip, monkeypatch):
    """Where the devices are TPUs the seam's auto route compiles the
    Pallas kernel (never interprets it); the test stands in for the
    platform, the program has no option for that."""
    monkeypatch.setattr(HK, "platform", lambda: "tpu")
    monkeypatch.delenv("MO_HAND_KERNELS", raising=False)
    assert HK.enabled() and not HK.interpret()
    spec = _spec(one_chip)
    text = _compiled_text(HK.sorted_lookup,
                          spec((1_500_000,), jnp.uint64),
                          spec((1 << 20,), jnp.uint64))
    assert "tpu_custom_call" in text


def test_fused_q1_step_compiles_for_v5e(one_chip, monkeypatch):
    """One fused TPC-H Q1 step (scan batch of 2^20 rows, DECIMAL money
    columns: filter + dense group-by + int64 sums).  The test stands in
    for the platform, so the CPU run of the statement traces the step
    under the policies a TPU resolves (narrow encodings, hand kernels,
    carry donation); that step is then lowered for the described chip."""
    from matrixone_tpu.frontend.session import Session
    from matrixone_tpu.ops import encodings as ENC
    from matrixone_tpu.utils import tpch_full as T
    from matrixone_tpu.vm import fusion as FF
    monkeypatch.setattr(HK, "platform", lambda: "tpu")
    for knob in ("MO_HAND_KERNELS", "MO_NARROW_ENCODINGS"):
        monkeypatch.delenv(knob, raising=False)
    assert HK.enabled() and ENC.enabled()
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
        args, _kwargs = compiled.args_info
        specs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), args)
        rows = max([rows] + [a.shape[0] for a in jax.tree.leaves(specs)
                             if a.shape])
        assert jax.jit(fn).lower(*specs).compile().memory_analysis()
    assert rows == 1 << 20, f"largest step input has {rows} rows"


def test_ivf_search_with_exact_rerank_compiles_for_v5e(one_chip):
    """The program a vector top-k statement waits for: the IVF-Flat
    search of one query with the exact float32 re-rank of its 60
    candidates, at the benchmark's shapes (1M x 768 float32 residuals,
    1,024 lists of at most 4,096 rows, nprobe 8).  It has to fit beside
    the 3.1 GB index."""
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
        index, spec((1, d), jnp.float32), k=60, nprobe=8, query_chunk=1,
        compute_dtype=jnp.bfloat16, use_pallas=False, exact=True).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 30, mem.temp_size_in_bytes
    assert "ivf_rerank_exact" in compiled.as_text()
