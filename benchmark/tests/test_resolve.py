"""Every cell, configuration, traffic mix, metric file and reader resolves
by the name the manifest gives, and the manifest keeps to its contract:
BENCHMARK.json, and each staged manifest under `staged/` (cells that wait
for a repair of the program; `run.py --manifest`)."""

import importlib
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

import traffic
import work

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


MANIFESTS = ["BENCHMARK.json"] + sorted(
    "benchmark/staged/" + f for f in os.listdir(os.path.join(BENCH, "staged")))


def _load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=MANIFESTS)
def manifest(request):
    return _load(request.param)


def test_manifest_shape(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= manifest["run_seconds"] <= 51
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in {"host_clock", "device_trace"}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in {"lower", "higher"}
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_cells_resolve(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"] for w in manifest["workloads"]}
    assert len(cells) == len(manifest["workloads"])
    pairs = {(w["config"], w["traffic"]) for w in manifest["workloads"]}
    assert len(pairs) == len(manifest["workloads"])
    for w in manifest["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        entry = configs[w["config"]]
        assert entry["file"].startswith("benchmark/configs/")
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == entry["source"] and len(entry["source"]) <= 200
        assert cfg["reduced"] == entry["reduced"]
        assert "guarantees" in cfg and "rehearsal" in cfg
        assert "reopen" not in cfg       # every run re-opens the engine
        loader = importlib.import_module("loaders." + cfg["loader"])
        for fn in ("generate", "load", "pools", "rows", "prepare"):
            assert callable(getattr(loader, fn))
        mix = traffic.load_mix(w["traffic"])
        assert mix["clients"] >= 1 and mix["templates"]
        for t in mix["templates"]:
            reference = importlib.import_module("references." + t["reference"])
            for fn in ("compare", "control_answers"):
                assert callable(getattr(reference, fn))
            if "work" in t:
                assert t["work"]["fn"] in work.FUNCTIONS
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            assert set(m.get("workloads", cells)) <= cells
    assert {c for w in manifest["workloads"] for c in [w["config"]]} \
        == set(configs)


def test_every_cell_reports_what_it_must(manifest):
    for w in manifest["workloads"]:
        e2e = [m["name"] for m in manifest["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in manifest["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer
        for m in layer:                  # it moves a metric this cell reports
            assert m["moves"] in e2e


def test_metric_files_and_readers_resolve(manifest):
    """A metric file holds the reader's name and its arguments and nothing
    that the manifest says already."""
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
                spec = json.load(f)
            assert set(spec) == {"reader", "args"}
            reader = importlib.import_module("readers." + spec["reader"])
            assert callable(reader.read)


def test_no_metric_file_is_left_over():
    listed = {m["name"] + ".json" for path in MANIFESTS
              for group in ("end_to_end", "per_layer")
              for m in _load(path)[group]}
    assert listed == set(os.listdir(os.path.join(BENCH, "metrics")))


def test_a_reader_with_nothing_to_read_returns_nothing():
    from readers import (counter_share, device_idle_share, fact, roofline,
                         span_self_ms, window_rate)
    ctx = {"trace": None, "trace_slice_perf_ns": None, "executed": [],
           "spans": [], "facts": {}, "before": {}, "after": {},
           "window": {"t_start_ns": 0, "t_last_done_ns": 0}}
    assert device_idle_share.read(ctx) is None
    assert roofline.read(ctx, "scan") is None
    traced = dict(ctx, trace={"busy_s": 1.0}, trace_slice_perf_ns=(0, 10),
                  device={"kind": "TPU v5 lite"},
                  executed=[{"t_send_ns": 1, "t_done_ns": 5, "error": None,
                             "work": None}])
    assert roofline.read(traced, "scan") is None   # no statement names it
    assert span_self_ms.read(ctx, "statement", ["run"]) is None
    assert fact.read(ctx, "recall_at_k") is None
    assert counter_share.read(ctx, ["a"], ["a", "b"]) is None
    assert window_rate.read(ctx, "statements") is None
