"""Tier-1-safe smoke of the headline bench: the IVF path must run end to
end on the CPU backend in under a minute and emit the one-line JSON
contract the driver scrapes (metric/value/recall/build_stages/
search_stages). Guards against bench.py rot between chip rounds — the
r05 postmortem was a scoreboard that silently stopped trending."""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_ivf_smoke_under_60s():
    env = dict(os.environ)
    env.update({
        "MO_BENCH_SMOKE": "1",
        "MO_BENCH_NO_Q1": "1",           # IVF path only, <60s budget
        "MO_BENCH_N": "8000",            # tier-1 rides every PR: keep the
        "MO_BENCH_D": "32",              # smoke shapes tiny but end-to-end
        "MO_BENCH_Q": "128",
        "JAX_PLATFORMS": "cpu",          # as tests/conftest.py asks
    })
    t0 = time.time()
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=120)
    dt = time.time() - t0
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert lines, r.stdout[-2000:]
    out = json.loads(lines[-1])
    assert out["metric"].startswith("ivfflat_search_qps_")
    assert out["unit"] == "qps"
    assert out["value"] > 0
    assert out["recall_at_20"] >= 0.5, out     # smoke shapes, loose floor
    assert out["backend"] == "cpu"
    assert set(out["build_stages"]) == {"kmeans", "assign", "pack"}
    assert set(out["search_stages"]) == {"probe", "score", "merge"}
    assert dt < 60, f"bench smoke took {dt:.1f}s"
