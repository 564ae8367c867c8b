"""Test rig: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's in-process cluster testing strategy
(`pkg/embed/cluster.go:73` — multi-service cluster in one process): here the
"cluster" is 8 XLA host devices, so sharding/collective paths compile and
run without TPU hardware. Must set env before the first jax import.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Tests run on the virtual 8-device CPU mesh whatever the machine holds:
# the chip is reached only through chip_smoke.py.
jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the suite's wall time is dominated by
# recompiling the same kernels run after run (measured: a 64-list IVF
# build drops 3.7s -> 1.8s across processes). The threshold is LOW on
# purpose — the suite compiles hundreds of distinct small programs at
# 0.05-0.3s each, and that tail is minutes of every run. The cache is
# where JAX_COMPILATION_CACHE_DIR says, else `<checkout>/.jax_cache`
# (git-ignored), and also serves subprocess tests (bench smoke).
# MO_JAX_CACHE=0 disables.
from matrixone_tpu.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache(min_compile_seconds=0.05)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running; excluded from the tier-1 run")
    config.addinivalue_line(
        "markers", "chaos: fault-injected resilience drill (runs in "
                   "tier-1; each drill must stay under 30s)")
    # mosan: the runtime concurrency sanitizer is ON by default under
    # pytest (MO_SAN=0 opts out); its findings gate tier-1 via
    # tests/test_mosan.py::test_suite_runs_sanitizer_clean
    if os.environ.get("MO_SAN", "1").lower() not in ("0", "false", "off"):
        from matrixone_tpu.utils import san
        san.arm()
    # mokey runtime half: the trace-capture / cache-key auditor is ON
    # by default under pytest (MO_KEY_AUDIT=0 opts out); its mismatch
    # findings gate tier-1 via tests/test_mokey.py::
    # test_suite_runs_key_audit_clean
    if os.environ.get("MO_KEY_AUDIT", "1").lower() not in ("0", "false",
                                                           "off"):
        from matrixone_tpu.utils import keys
        keys.arm()


def pytest_collection_modifyitems(session, config, items):
    # the mosan gate must see the WHOLE run: move it to the end of the
    # collection (file order would leave every test after test_mosan.py
    # outside its coverage)
    gate = [i for i in items
            if i.nodeid.endswith("test_suite_runs_sanitizer_clean")
            or i.nodeid.endswith("test_suite_runs_key_audit_clean")]
    for g in gate:
        items.remove(g)
        items.append(g)


def pytest_sessionfinish(session, exitstatus):
    from matrixone_tpu.utils import keys, san
    if keys.armed():
        # regenerate the checked-in runtime capture-inventory export
        # that mokey's static pass unions (README "Static analysis");
        # opt-in so ordinary runs never dirty the working tree
        if os.environ.get("MO_KEY_EXPORT", "").lower() in ("1", "true",
                                                           "on"):
            path = os.path.join(os.path.dirname(__file__), "..",
                                "tools", "mokey",
                                "observed_captures.json")
            n = keys.export_observed(os.path.abspath(path))
            print(f"\n[mokey] exported {n} audited captures -> {path}")
        leftover = keys.findings()
        if leftover:
            print(f"\n[mokey] {len(leftover)} capture-mismatch "
                  f"finding(s) accumulated this run (the gate test "
                  f"fails on these when tests/test_mokey.py is part "
                  f"of the selection):")
            for f in leftover[:5]:
                print(f.format())
    if not san.armed():
        return
    # regenerate the checked-in runtime lock-order edge export that
    # molint's lock-discipline checker reconciles against (see README
    # "Concurrency sanitizer"); opt-in so ordinary runs never dirty the
    # working tree
    if os.environ.get("MO_SAN_EXPORT", "").lower() in ("1", "true", "on"):
        path = os.path.join(os.path.dirname(__file__), "..", "tools",
                            "molint", "observed_lock_edges.json")
        n = san.export_edges(os.path.abspath(path))
        print(f"\n[mosan] exported {n} lock-order edges -> {path}")
    leftover = san.findings()
    if leftover:
        print(f"\n[mosan] {len(leftover)} finding(s) accumulated this "
              f"run (the gate test runs last and fails on these when "
              f"tests/test_mosan.py is part of the selection):")
        for f in leftover[:10]:
            print(f.format())


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(autouse=True)
def _disarm_faults():
    """No drill may leak armed fault points into the next test."""
    yield
    from matrixone_tpu.utils.fault import INJECTOR
    INJECTOR.clear()


@pytest.fixture(autouse=True)
def _san_thread_leaks(request):
    """mosan per-test leak check: threads alive after a test that were
    not alive before it (minus san.daemon()-registered immortals) are
    findings — a service that never joins its workers surfaces at the
    test that leaked it, not as a mystery slowdown three PRs later."""
    from matrixone_tpu.utils import san
    if not san.armed():
        yield
        return
    before = san.thread_snapshot()
    yield
    san.check_thread_leaks(before, request.node.nodeid)
