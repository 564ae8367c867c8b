"""`vec-wiki-1m-ivf`: its reference and the bfloat16 control kept apart at
test size, both cells rehearsed on the CPU, the recall number tripped by a
planted fault (the control keeps every id, so it cannot), and the loader's
guard tripped (a deployment whose single warmed search outlasts
`guard_seconds` ends the run at once, non-zero, with no result line)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import loadgen
import run
from loaders import vec_ivf as loader
from references import vec_ivf

from conftest import BENCH, ROOT

CFG = {"k": 20}
# `search-c100` is a cell of BENCHMARK.json; `search-c1` is staged: its
# `sql_rows_per_s` spread over half the bound in two of three sets of six
# on the chip (stalled statements of seconds, PERF.md section 7)
STAGED = "benchmark/staged/vec-wiki-1m-ivf.json"
CELLS = ["vec-wiki-1m-ivf.search-c1", "vec-wiki-1m-ivf.search-c100"]
MANIFEST = {CELLS[0]: STAGED, CELLS[1]: "BENCHMARK.json"}


def _over(numbers):
    return {k for k, (v, limit) in numbers.items() if v > limit}


@pytest.fixture(scope="module")
def vectors():
    rng = np.random.default_rng(9)
    cent = rng.standard_normal((16, 768), dtype=np.float32)
    x = (cent[rng.integers(0, 16, 20_000)]
         + 2.0 * rng.standard_normal((20_000, 768), dtype=np.float32))
    q = (cent[rng.integers(0, 16, 48)]
         + 2.0 * rng.standard_normal((48, 768), dtype=np.float32))
    return {"x": x, "queries": q}


def _statements(ids_per_query):
    return [{"template": "search", "params": {"query": j}, "error": None,
             "rows": [[str(i)] for i in ids]}
            for j, ids in enumerate(ids_per_query)]


def test_bf16_round_is_round_to_nearest_even():
    import ml_dtypes
    v = np.random.default_rng(0).standard_normal(4096).astype(np.float32)
    v = np.concatenate([v, np.float32([1.00390625, 1.01171875, 0.0, -0.0,
                                       3.0e38, 1e-30])])
    want = v.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.array_equal(vec_ivf.bf16_round(v), want)


def test_brute_force_is_the_exact_top_k(vectors):
    x, q = vectors["x"], vectors["queries"]
    got = vec_ivf.brute_force_topk(x, q[:4], 20)
    for qv, ids in zip(q[:4].astype(np.float64), got):
        d = ((x.astype(np.float64) - qv) ** 2).sum(1)
        assert ids.tolist() == np.argsort(d, kind="stable")[:20].tolist()


def test_exact_answers_are_correct_and_the_bf16_control_is_not(vectors):
    """The control keeps every id (the recall is the program's) and trips
    the order number, alone."""
    x, q = vectors["x"], vectors["queries"]
    exact = _statements(vec_ivf.brute_force_topk(x, q, 20).tolist())
    numbers, facts = vec_ivf.compare(CFG, vectors, exact)
    assert not _over(numbers), numbers
    assert numbers["ivf_order_descent"][0] == 0.0
    assert facts["recall_at_k"] == 1.0
    control = vec_ivf.control_answers(CFG, vectors, exact)
    assert [sorted(r[0] for r in st["rows"]) for st in control] \
        == [sorted(r[0] for r in st["rows"]) for st in exact]
    numbers, facts = vec_ivf.compare(CFG, vectors, control)
    assert _over(numbers) == {"ivf_order_descent"}, numbers
    assert numbers["ivf_order_descent"][0] > 10 * vec_ivf.LIMITS[
        "ivf_order_descent"]
    assert facts["recall_at_k"] == 1.0


def test_a_float32_rerank_stays_under_the_order_limit(vectors):
    """One step above the control: answers ordered by float32 distances
    of the stored float32 rows (what the configuration states) read well
    under the limit."""
    x, q = vectors["x"], vectors["queries"]
    answers = []
    for j, ids in enumerate(vec_ivf.brute_force_topk(x, q, 20)):
        diff = x[ids] - q[j]
        d32 = np.einsum("nd,nd->n", diff, diff)
        answers.append(ids[np.argsort(d32, kind="stable")].tolist())
    numbers, _ = vec_ivf.compare(CFG, vectors, _statements(answers))
    assert not _over(numbers), numbers
    assert numbers["ivf_order_descent"][0] \
        < vec_ivf.LIMITS["ivf_order_descent"] / 5


def test_altered_and_malformed_answers_are_caught(vectors):
    x, q = vectors["x"], vectors["queries"]
    truth = vec_ivf.brute_force_topk(x, q[:8], 20)
    shifted = _statements([[(i + 1) % len(x) for i in ids]
                           for ids in truth.tolist()])
    numbers, facts = vec_ivf.compare(CFG, vectors, shifted)
    assert {"ivf_recall_deficit", "ivf_order_descent"} <= _over(numbers)
    base = {"template": "search", "error": None}
    bad = [dict(base, params={"query": 0}, rows=[["1"]] * 20),      # repeats
           dict(base, params={"query": 1}, rows=[[str(i)] for i in range(19)]),
           dict(base, params={"query": 2},
                rows=[[str(len(x) + i)] for i in range(20)]),       # no rows
           dict(base, params={"query": 3}, rows=[["x"]] * 20),
           dict(base, params={"query": 4}, rows=None, error="WireError: x"),
           dict(base, params={"query": 5},
                rows=[[str(i)] for i in truth[5][::-1]])]           # descending
    numbers, _ = vec_ivf.compare(CFG, vectors, bad)
    assert numbers["ivf_answers_malformed"][0] == 4
    assert numbers["ivf_statements_failed"][0] == 1
    assert numbers["ivf_order_descent"][0] > 1e-3
    # the control passes what it cannot re-rank through as it was
    assert vec_ivf.control_answers(CFG, vectors, bad)[:5] == bad[:5]


# ------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("cell", CELLS)
def test_the_rehearsal_of_each_cell_is_correct(cell):
    result = run.run_cell(cell, seed=2**31 + 99, seconds=2.0, trace=True,
                          rehearse=True, control=True,
                          manifest=MANIFEST[cell])
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    for name in ("rows_not_read_back", "index_not_in_plan",
                 "offramp_events", "ivf_statements_failed",
                 "ivf_answers_malformed"):
        assert compared[name]["value"] == 0, name
    assert result["control"]["correct"] is False
    assert {k for k, (v, limit) in result["control"]["compared"].items()
            if v > limit} == {"ivf_order_descent"}
    got = {k: v["value"] for k, v in result["metrics"].items()}
    manifest = run.load_json(ROOT, MANIFEST[cell])
    device = {m["name"] for m in manifest["per_layer"]
              if m["source"] == "device_trace"}
    declared = {m["name"] for m in run.cell_metrics(manifest, cell,
                                                    "per_layer")}
    assert declared - device <= set(got), declared - set(got)
    assert got["upload_bytes_per_stmt.vec"] == 0
    assert got["fetch_bytes_per_stmt.vec"] == 20 * 9     # 20 rows of `id`
    assert got["device_waits_per_stmt.vec"] == 1
    assert got["spans_dropped_per_stmt.vec"] == 0
    assert got["compiles_in_window.vec"] == 0
    spans = {m["name"] for m in manifest["per_layer"]
             if m["source"] == "program_span"}
    # under 100 callers a span's time is where its thread waited for the
    # GIL, which does not repeat: c100 lists no span metric (PERF.md 3)
    assert bool(declared & spans) == (cell == CELLS[0])
    if cell == CELLS[0]:
        assert got["run_unattributed_share.vec"] < 0.25
    end_to_end = run.run_cell(cell, seed=7, seconds=1.0, trace=False,
                              rehearse=True, manifest=MANIFEST[cell])
    assert set(end_to_end["metrics"]) == {"sql_rows_per_s", "setup_s"}
    rate = end_to_end["metrics"]["sql_rows_per_s"]["value"]
    elapsed = (end_to_end["attempted"] * 16384) / rate
    assert 0.5 < elapsed < 30             # queries/s x the table's rows


# ------------------------------------------------------- planted faults

def lose_the_last_commits(lost):
    """The guarantee `answer` broken: the rows of the last `lost` of the
    configuration's commits are not reachable by a search any more
    (deleted behind the reference's back, which still holds them).  One
    search absorbs the index's refresh before the window starts."""
    commits = run.load_json(
        ROOT, "benchmark/configs/vec-wiki-1m-ivf.json")["commits"]

    def fault(srv, eng):
        conn = loadgen.Connection(srv.port, timeout=3600.0)
        n = int(conn.query("select count(*) from docs")[0][0])
        conn.query(f"delete from docs where id >= "
                   f"{n * (commits - lost) // commits}")
        dim = dict(eng.get_table("docs").meta.schema)["v"].dim
        conn.query(f"select id from docs order by l2_distance(v, "
                   f"'[{','.join(['0.5'] * dim)}]') limit 20")
        conn.close()
    return fault


@pytest.mark.parametrize("cell", CELLS)
def test_rows_out_of_reach_trip_the_recall_number(cell):
    """What the control cannot show: the harness comes out not correct
    through `ivf_recall_deficit` while the order number stays quiet (what
    is answered still ascends).  Two of the four commits here: a toy
    window holds few distinct queries (c100's callers all start at the
    first), and half of their neighbours is well over the limit whichever
    they are; on the chip one lost commit is (PERF.md section 2)."""
    result = run.run_cell(cell, seed=2**31 + 41, seconds=2.0, trace=False,
                          rehearse=True, fault=lose_the_last_commits(2),
                          manifest=MANIFEST[cell])
    assert result["correct"] is False
    assert result["attempted"] > 0 and result["failed"] == 0
    compared = result["compared"]
    assert compared["ivf_recall_deficit"]["value"] \
        > 1.5 * compared["ivf_recall_deficit"]["limit"], compared
    assert compared["ivf_order_descent"]["value"] \
        <= compared["ivf_order_descent"]["limit"], compared


# ------------------------------------------------------------- the guard

def _hasty_manifest(tmp_path, guard_seconds):
    """The manifest with the configuration's guard shortened, in files of
    its own (absolute paths: `run.py` joins them to the repo's root)."""
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = run.find(manifest["configs"], "vec-wiki-1m-ivf", "configuration")
    cfg = run.load_json(ROOT, entry["file"])
    cfg["guard_seconds"] = guard_seconds
    entry["file"] = str(tmp_path / "config.json")
    (tmp_path / "config.json").write_text(json.dumps(cfg))
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    return str(tmp_path / "manifest.json")


def test_the_guard_raises_when_a_warmed_search_outlasts_it(tmp_path):
    with pytest.raises(loader.SearchTooSlow, match="guard: one warmed"):
        run.run_cell(CELLS[1], seed=3, seconds=1.0, trace=False,
                     rehearse=True,
                     manifest=_hasty_manifest(tmp_path, 1e-4))


def test_run_py_then_ends_non_zero_with_no_result_line(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS[1], "--seed", "3", "--seconds", "1", "--trace", "0",
         "--rehearse", "--manifest", _hasty_manifest(tmp_path, 1e-4)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode not in (0, 3), done.stderr[-2000:]
    assert "SearchTooSlow" in done.stderr and "guard:" in done.stderr
    lines = done.stdout.strip().splitlines()
    assert lines and all('"correct"' not in ln for ln in lines)
    assert "rehearsal: no result line" not in done.stdout
