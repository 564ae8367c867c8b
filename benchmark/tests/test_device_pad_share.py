"""`scan_batch_device_pad_share.sql` (PR 30): a data file over the
`counter_share` reader.  It resolves by the name the manifest gives, reads
the share of `from_numpy`'s columns that were padded on the device out of a
recorded counter dump, and reads nothing from a program that has no such
counter (the parent)."""

import json
import os

from conftest import BENCH, ROOT

from readers import counter_share

NAME = "scan_batch_device_pad_share.sql"


def _spec():
    with open(os.path.join(BENCH, "metrics", NAME + ".json")) as f:
        return json.load(f)


def _dump():
    """Counters before and after a traced window of `tpch-sf1.scan-agg` on
    the chip (PR 30, seed 1664525077), as `run.counters()` flattens them."""
    with open(os.path.join(BENCH, "tests", "data",
                           "from_numpy_counters.json")) as f:
        return json.load(f)


def test_the_manifest_lists_it_for_the_scan_cell_only():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == [manifest["per_layer"][-1]]      # appended, once
    assert entry[0] == {
        "name": NAME, "unit": "ratio", "better": "lower",
        "source": "program_counter",
        "layer": "scan: batch assembly (container/device.py, "
                 "vm/operators.py)",
        "moves": "sql_rows_per_s", "workloads": ["tpch-sf1.scan-agg"]}


def test_it_reads_the_recorded_dump():
    spec, ctx = _spec(), _dump()
    assert spec["reader"] == "counter_share"
    key = 'mo_from_numpy_columns_total{path="%s"}'
    moved = {p: ctx["after"].get(key % p, 0) - ctx["before"].get(key % p, 0)
             for p in ("device", "device_pad", "host", "roundtrip")}
    assert moved["device_pad"] > 0 and moved["roundtrip"] == 0
    share = counter_share.read(ctx, **spec["args"])
    assert share == moved["device_pad"] / sum(moved.values())
    # four segments of lineitem, each two chunks: one full, one ragged
    assert share == 0.5


def test_a_program_without_the_counter_leaves_it_out():
    ctx = _dump()
    parent = {side: {k: v for k, v in ctx[side].items()
                     if not k.startswith("mo_from_numpy_columns_total")}
              for side in ("before", "after")}
    assert counter_share.read(parent, **_spec()["args"]) is None
