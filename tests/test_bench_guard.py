"""tools/bench_guard.py: the scoreboard regression gate — >20% drops in
headline metrics (qps, rows/s) against the best prior round must fail,
improvements and within-tolerance noise must pass, and explicit
BENCH_FLOORS.json floors override history."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))
import bench_guard  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _round(tmp, n, metrics):
    entries = [{"metric": m, "value": v, "unit": u, "vs_baseline": None,
                "backend": "cpu"} for m, v, u in metrics]
    top = dict(entries[0])
    top["extra_metrics"] = entries[1:]
    path = os.path.join(tmp, f"BENCH_r{n:02d}.json")
    with open(path, "w") as f:
        json.dump({"n": n, "rc": 0, "tail": "noise\n" + json.dumps(top)},
                  f)
    return path


def test_family_normalization():
    assert bench_guard.family(
        "ivfflat_search_qps_200000x256_top20_nprobe8") == \
        "ivfflat_search_qps"
    assert bench_guard.family("tpch_q1_rows_per_sec_6001215") == \
        "tpch_q1_rows_per_sec"
    assert bench_guard.family("serving_hot_qps") == "serving_hot_qps"
    assert bench_guard.family(
        "ivfflat_sharded_qps_1000000x768_top20_nprobe8x4dev") == \
        "ivfflat_sharded_qps"


def test_regression_fails(tmp_path):
    tmp = str(tmp_path)
    _round(tmp, 1, [("ivfflat_search_qps_1000x64_top20_nprobe8",
                     1000.0, "qps"),
                    ("tpch_q1_rows_per_sec_1000", 2e6, "rows/s")])
    _round(tmp, 2, [("ivfflat_search_qps_1000x64_top20_nprobe8",
                     700.0, "qps"),      # -30%: regression
                    ("tpch_q1_rows_per_sec_1000", 1.9e6, "rows/s")])
    ok, report = bench_guard.check(tmp)
    assert not ok
    assert any("FAIL ivfflat_search_qps" in ln for ln in report)
    assert any(ln.startswith("ok   tpch_q1") for ln in report)


def test_within_tolerance_and_improvement_pass(tmp_path):
    tmp = str(tmp_path)
    _round(tmp, 1, [("ivfflat_search_qps_1000x64", 1000.0, "qps")])
    _round(tmp, 2, [("ivfflat_search_qps_1000x64", 850.0, "qps")])
    ok, _ = bench_guard.check(tmp)          # -15% < 20% tolerance
    assert ok
    _round(tmp, 3, [("ivfflat_search_qps_1000x64", 2000.0, "qps")])
    ok, _ = bench_guard.check(tmp)
    assert ok


def test_missing_family_warns_not_fails(tmp_path):
    tmp = str(tmp_path)
    _round(tmp, 1, [("ivfflat_search_qps_1000x64", 1000.0, "qps"),
                    ("serving_hot_qps", 500.0, "qps")])
    _round(tmp, 2, [("ivfflat_search_qps_1000x64", 990.0, "qps")])
    ok, report = bench_guard.check(tmp)
    assert ok
    assert any("WARN serving_hot_qps" in ln for ln in report)


def test_error_entries_ignored(tmp_path):
    tmp = str(tmp_path)
    _round(tmp, 1, [("ivfflat_search_qps_1000x64", 1000.0, "qps")])
    path = _round(tmp, 2, [("ivfflat_search_qps_1000x64", 990.0, "qps")])
    with open(path) as f:
        rec = json.load(f)
    top = json.loads(rec["tail"].splitlines()[-1])
    top["extra_metrics"] = [{"metric": "tpch_q1_rows_per_sec",
                             "value": 0, "unit": "error",
                             "vs_baseline": None, "error": "wedge"}]
    rec["tail"] = json.dumps(top)
    with open(path, "w") as f:
        json.dump(rec, f)
    ok, _ = bench_guard.check(tmp)
    assert ok


def test_unreadable_latest_round_fails(tmp_path):
    """A truncated/corrupt NEWEST record is exactly the bench-crash
    signal the guard exists for — it must fail, not silently compare
    the previous round."""
    tmp = str(tmp_path)
    _round(tmp, 1, [("m_qps_10", 100.0, "qps")])
    _round(tmp, 2, [("m_qps_10", 110.0, "qps")])
    with open(os.path.join(tmp, "BENCH_r03.json"), "w") as f:
        f.write('{"n": 3, "tail": "Traceback (most recent')   # truncated
    ok, report = bench_guard.check(tmp)
    assert not ok
    assert any("unreadable" in ln and "BENCH_r03" in ln for ln in report)
    # an unreadable OLD round is only a warning
    os.rename(os.path.join(tmp, "BENCH_r03.json"),
              os.path.join(tmp, "BENCH_r00.json"))
    ok, report = bench_guard.check(tmp)
    assert ok
    assert any("WARN unreadable" in ln for ln in report)


def test_floors_sidecar_excluded_and_natural_round_order(tmp_path):
    tmp = str(tmp_path)
    # unpadded round names: lexicographic order puts r10 BEFORE r9, so a
    # name sort would miss that the unreadable r10 is the newest round
    with open(os.path.join(tmp, "BENCH_r9.json"), "w") as f:
        json.dump({"n": 9, "tail": json.dumps(
            {"metric": "m_qps_10", "value": 100.0, "unit": "qps",
             "backend": "cpu"})}, f)
    with open(os.path.join(tmp, "BENCH_r10.json"), "w") as f:
        f.write("garbage")
    with open(os.path.join(tmp, "BENCH_FLOORS.json"), "w") as f:
        json.dump({"m_qps": {"cpu": 50.0}}, f)
    ok, report = bench_guard.check(tmp)
    assert not ok
    assert any("BENCH_r10" in ln and "unreadable" in ln for ln in report)
    # the floors sidecar is config, never an "unreadable round"
    assert not any("BENCH_FLOORS" in ln and "unreadable" in ln
                   for ln in report)


def test_floors_file_overrides_history(tmp_path):
    tmp = str(tmp_path)
    _round(tmp, 1, [("tpch_q1_rows_per_sec_1000", 2e6, "rows/s")])
    _round(tmp, 2, [("tpch_q1_rows_per_sec_1000", 1e6, "rows/s")])
    ok, _ = bench_guard.check(tmp)
    assert not ok                            # -50% vs history: fail
    with open(os.path.join(tmp, "BENCH_FLOORS.json"), "w") as f:
        json.dump({"tpch_q1_rows_per_sec": {"cpu": 0.9e6}}, f)
    ok, report = bench_guard.check(tmp)      # explicit floor: pass
    assert ok, report


def test_real_repo_history_passes():
    """The committed BENCH_FLOORS.json (and any round record beside it)
    must gate green — a red guard on main would mask real regressions
    in the next PR."""
    ok, report = bench_guard.check(REPO)
    assert ok, "\n".join(report)


def test_floors_without_any_round_record_pass(tmp_path):
    """The repo holds no round record until one is taken on a chip:
    floors alone have nothing to be held against."""
    tmp = str(tmp_path)
    with open(os.path.join(tmp, "BENCH_FLOORS.json"), "w") as f:
        json.dump({"tpch_q1_rows_per_sec": {"cpu": 1e6}}, f)
    ok, report = bench_guard.check(tmp)
    assert ok, report
    assert any("nothing to compare" in ln for ln in report)


def test_cli_exit_codes(tmp_path):
    tmp = str(tmp_path)
    _round(tmp, 1, [("m_qps_10", 100.0, "qps")])
    _round(tmp, 2, [("m_qps_10", 10.0, "qps")])
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "bench_guard.py"),
                        "--dir", tmp], capture_output=True, text=True)
    assert r.returncode == 1
    assert "REGRESSION" in r.stdout
    r2 = subprocess.run([sys.executable,
                         os.path.join(REPO, "tools", "bench_guard.py"),
                         "--dir", tmp, "--tolerance", "0.95"],
                        capture_output=True, text=True)
    assert r2.returncode == 0


def _round_d(tmp, n, metrics):
    """Round record whose entries carry fused_dispatches counts:
    (metric, value, unit, dispatches)."""
    entries = [{"metric": m, "value": v, "unit": u, "backend": "cpu",
                **({"fused_dispatches": d} if d is not None else {})}
               for m, v, u, d in metrics]
    top = dict(entries[0])
    top["extra_metrics"] = entries[1:]
    with open(os.path.join(tmp, f"BENCH_r{n:02d}.json"), "w") as f:
        json.dump({"n": n, "rc": 0,
                   "tail": "noise\n" + json.dumps(top)}, f)


def test_dispatch_budget_over_cap_fails(tmp_path):
    tmp = str(tmp_path)
    _round_d(tmp, 1, [("tpch_q1_fused_rows_per_sec_1000", 2e6,
                       "rows/s", 24)])
    _round_d(tmp, 2, [("tpch_q1_fused_rows_per_sec_1000", 2e6,
                       "rows/s", 40)])      # fusion broke: 40 > 24
    with open(os.path.join(tmp, "BENCH_FLOORS.json"), "w") as f:
        json.dump({"_dispatch_budgets":
                   {"tpch_q1_fused_rows_per_sec": {"cpu": 24}}}, f)
    ok, report = bench_guard.check(tmp)
    assert not ok
    assert any("FAIL dispatch budget tpch_q1_fused_rows_per_sec" in ln
               for ln in report)


def test_dispatch_budget_within_cap_passes(tmp_path):
    tmp = str(tmp_path)
    _round_d(tmp, 1, [("tpch_q1_fused_rows_per_sec_1000", 2e6,
                       "rows/s", 30)])      # history had MORE: only the
    _round_d(tmp, 2, [("tpch_q1_fused_rows_per_sec_1000", 2e6,
                       "rows/s", 20)])      # latest round is judged
    with open(os.path.join(tmp, "BENCH_FLOORS.json"), "w") as f:
        json.dump({"_dispatch_budgets":
                   {"tpch_q1_fused_rows_per_sec": {"cpu": 24}}}, f)
    ok, report = bench_guard.check(tmp)
    assert ok, report
    assert any("ok   dispatch budget" in ln and "20 <= 24" in ln
               for ln in report)


def test_dispatch_budget_absent_family_warns(tmp_path):
    tmp = str(tmp_path)
    _round_d(tmp, 1, [("tpch_q1_fused_rows_per_sec_1000", 2e6,
                       "rows/s", None)])    # no dispatch counts at all
    _round_d(tmp, 2, [("tpch_q1_fused_rows_per_sec_1000", 2e6,
                       "rows/s", None)])
    with open(os.path.join(tmp, "BENCH_FLOORS.json"), "w") as f:
        json.dump({"_dispatch_budgets":
                   {"tpch_q1_fused_rows_per_sec": {"cpu": 24}}}, f)
    ok, report = bench_guard.check(tmp)
    assert ok, report
    assert any("WARN dispatch budget" in ln for ln in report)


def test_dispatch_budgets_never_become_floor_families(tmp_path):
    """The "_"-prefixed sidecar sections must not parse as metric
    floors (a nested dict would TypeError into a dead guard)."""
    tmp = str(tmp_path)
    _round_d(tmp, 1, [("tpch_q1_fused_rows_per_sec_1000", 2e6,
                       "rows/s", 10)])
    _round_d(tmp, 2, [("tpch_q1_fused_rows_per_sec_1000", 2e6,
                       "rows/s", 10)])
    with open(os.path.join(tmp, "BENCH_FLOORS.json"), "w") as f:
        json.dump({"_comment": "sidecar",
                   "_dispatch_budgets":
                   {"tpch_q1_fused_rows_per_sec": {"cpu": 24}}}, f)
    ok, report = bench_guard.check(tmp)
    assert ok, report
    assert not any("unreadable" in ln and "FLOORS" in ln
                   for ln in report)
    assert not any(ln.startswith("FAIL _") or "ok   _" in ln
                   for ln in report)
