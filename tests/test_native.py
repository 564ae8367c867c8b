"""Native C++ kernels vs numpy fallback vs device hashing
(reference analogue: cgo/test/)."""

import numpy as np
import pytest

from matrixone_tpu import native


def test_native_lib_compiles():
    assert native.get_lib() is not None, "g++ toolchain present; must build"


def test_concurrent_builds_each_load_a_whole_library(tmp_path):
    """Six xdist workers used to build to ONE temp name and race on the
    rename; now every process builds to its own name and all load."""
    import os
    import subprocess
    import sys
    code = (
        "import os, sys\n"
        "from matrixone_tpu import native as N\n"
        f"N._BUILD_DIR = {str(tmp_path)!r}\n"
        "N._SO = os.path.join(N._BUILD_DIR, 'libmo_native.so')\n"
        "assert N._compile()\n"
        "lib = N.get_lib()\n"
        "assert lib is not None and lib.mo_bitset_count\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = [subprocess.Popen([sys.executable, "-c", code], env=env,
                              cwd=root, stderr=subprocess.PIPE, text=True)
             for _ in range(4)]
    for p in procs:
        _, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
    assert os.listdir(tmp_path) == ["libmo_native.so"]   # no stray temps


def test_hash64_matches_device_and_fallback(rng):
    vals = rng.integers(-2**62, 2**62, 1000)
    h_native = native.hash64(vals)
    h_np = native._splitmix_np(np.ascontiguousarray(vals, np.int64).view(np.uint64))
    np.testing.assert_array_equal(h_native, h_np)
    # device parity
    import jax.numpy as jnp
    from matrixone_tpu.ops import hash as H
    h_dev = np.asarray(H.hash_column(jnp.asarray(vals)))
    np.testing.assert_array_equal(h_native, h_dev)


def test_bloom_no_false_negatives(rng):
    keys = rng.integers(0, 10**12, 5000)
    bf = native.BloomFilter(len(keys))
    bf.add_int64(keys)
    assert bf.probe_int64(keys).all()          # zero false negatives
    other = rng.integers(10**13, 10**14, 5000)
    fpr = bf.probe_int64(other).mean()
    assert fpr < 0.05                          # ~1% expected at 10 bits/item


def test_bloom_fallback_parity(rng, monkeypatch):
    keys = rng.integers(0, 10**9, 500)
    probes = rng.integers(0, 10**9, 500)
    bf1 = native.BloomFilter(500)
    bf1.add_int64(keys)
    r1 = bf1.probe_int64(probes)
    monkeypatch.setattr(native, "get_lib", lambda: None)
    bf2 = native.BloomFilter(500)
    bf2.add_int64(keys)
    np.testing.assert_array_equal(bf1.bits, bf2.bits)
    np.testing.assert_array_equal(r1, bf2.probe_int64(probes))


def test_bitset(rng):
    bs = native.Bitset(10000)
    ids = np.unique(rng.integers(0, 10000, 3000))
    bs.set_ids(ids)
    assert bs.count() == len(ids)
    probe = np.arange(10000)
    got = bs.test_ids(probe)
    expect = np.isin(probe, ids)
    np.testing.assert_array_equal(got, expect)
    other = native.Bitset(10000)
    other.set_ids(np.arange(0, 10000, 2))
    bs.and_(other)
    assert bs.count() == len([i for i in ids if i % 2 == 0])


def test_sorted_contains(rng):
    hay = np.unique(rng.integers(0, 100000, 5000))
    ids = rng.integers(0, 100000, 2000)
    got = native.sorted_contains(hay, ids)
    np.testing.assert_array_equal(got, np.isin(ids, hay))
    assert not native.sorted_contains(np.array([], np.int64), ids).any()
