"""Drive the whole of a run (the CPU rehearsal: the same code path, child
process generator included, without the look for a chip) with the timed
path broken underneath, and see `correct` come out false.

Of the contract's faults these cells can have two: an answer altered where
it is produced, and part of the batch left out (rows missing from what the
statements scan).  A step that returns its state unchanged and an exchange
between chips do not exist in a one-chip serving cell."""

import pytest

import run


STAGED = "benchmark/staged/vec-wiki-1m.json"


def _run(workload, fault=None):
    manifest = STAGED if workload.startswith("vec-") else "BENCHMARK.json"
    return run.run_cell(workload, seed=2**31 + 77, seconds=2.0,
                        trace=False, rehearse=True, fault=fault,
                        manifest=manifest)


def _over(result):
    return {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}


def alter_decimal_text(srv, eng):
    """The wire renders every DECIMAL one unit in the last place up."""
    from matrixone_tpu.frontend import server
    real = server._decimal_text
    server._decimal_text = lambda scaled, scale: real(scaled + 1, scale)
    alter_decimal_text.undo = lambda: setattr(server, "_decimal_text", real)


def drop_half_of_lineitem(srv, eng):
    """Half of the rows are gone from what the statements scan."""
    import loadgen
    conn = loadgen.Connection(srv.port)
    conn.query("delete from lineitem where l_orderkey % 2 = 0")
    conn.close()
    drop_half_of_lineitem.undo = lambda: None


def shift_ids(srv, eng):
    """The index search returns the row next to each one it found."""
    from matrixone_tpu.vectorindex import ivf_flat
    real = ivf_flat.search

    def search(index, queries, *a, **kw):
        dists, pos = real(index, queries, *a, **kw)
        return dists, (pos + 1) % index.n

    ivf_flat.search = search
    shift_ids.undo = lambda: setattr(ivf_flat, "search", real)


def test_the_unbroken_rehearsal_is_correct_and_prints_no_result(capsys):
    assert run.main(["--workload", "tpch-sf1.scan-agg", "--seed", "5",
                     "--seconds", "2", "--trace", "0", "--rehearse"]) == 3
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last == "rehearsal: no result line"
    result = _run("vec-wiki-1m.search-c1")
    assert result["correct"] and not _over(result), result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"vec_qps", "vec_p95_ms",
                                      "vec_recall_at_20", "setup_s"}


@pytest.mark.parametrize("workload, fault, caught_by", [
    ("tpch-sf1.scan-agg", alter_decimal_text, "sql_cells_unequal"),
    ("tpch-sf1.scan-agg", drop_half_of_lineitem, "sql_cells_unequal"),
    ("vec-wiki-1m.search-c1", shift_ids, "vec_recall_deficit"),
    ("vec-wiki-1m.search-c100", shift_ids, "vec_recall_deficit"),
])
def test_a_broken_timed_path_is_not_correct(workload, fault, caught_by):
    try:
        result = _run(workload, fault)
    finally:
        fault.undo()
    assert result["correct"] is False
    assert caught_by in _over(result), result["compared"]


def test_no_accelerator_no_result():
    """Without `--rehearse` a machine with no TPU ends the run before any
    work: non-zero, no result line."""
    with pytest.raises(SystemExit) as e:
        run.run_cell("tpch-sf1.scan-agg", 1, 1.0, False)
    assert "TPU" in str(e.value.code)
