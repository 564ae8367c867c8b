"""The reduction from a profiler trace to busy / idle time, time per XLA
module and idle gaps: on hand-made events, and on a small trace recorded on
a TPU v5e (`data/fixture.xplane.pb`, PR 26: three calls each of two jitted
functions, `fixture_step` and `fixture_other`, with sleeps between them,
taken by `xplane.Recorder`)."""

import os

import pytest

from conftest import HERE

import xplane

FIXTURE = os.path.join(HERE, "data", "fixture.xplane.pb")


def test_union_and_module_name():
    assert xplane.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert xplane.module_name("jit__search(3808139069870405639)") \
        == "jit__search"
    assert xplane.module_name("jit_add") == "jit_add"


def _hand_made():
    ms = 1e6
    ops = [("%fusion.1", 10 * ms, 5 * ms), ("%while.2", 20 * ms, 10 * ms),
           ("%add.3", 22 * ms, 2 * ms),             # inside the while
           ("%copy.4", 60 * ms, 10 * ms)]
    modules = [("jit_step(111)", 10 * ms, 20 * ms),
               ("jit_other(222)", 60 * ms, 10 * ms)]
    return {"devices": {0: {"XLA Ops": ops, "XLA Modules": modules,
                            "Async XLA Ops": [("%copy-start", 0, 90 * ms)]}},
            "marker_ns": 0.0}


def test_busy_idle_modules_and_gaps_on_hand_made_events():
    ms = 1e6
    spans = [("statement", 0, 100 * ms, 0), ("run", 30 * ms, 58 * ms, 1)]
    r = xplane.reduce(_hand_made(), (0.0, 100 * ms), spans)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.025)       # 5 + 10 + 10 ms
    assert r["modules"] == pytest.approx({"jit_step": 0.02, "jit_other": 0.01})
    assert r["module_calls"] == {"jit_step": 1, "jit_other": 1}
    assert r["device_ops"][0] == ["jit_step", pytest.approx(0.02)]
    # gaps: 0-10 and 15-20 and 70-100 under `statement`, 30-60 under `run`
    gaps = dict(r["idle_gaps"])
    assert gaps["statement"] == pytest.approx(0.045)
    assert gaps["run"] == pytest.approx(0.03)
    assert r["longest_gap_s"] == pytest.approx(0.03)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_the_window_clips_and_unowned_gaps_are_unattributed():
    ms = 1e6
    r = xplane.reduce(_hand_made(), (12 * ms, 25 * ms))
    assert r["busy_s"] == pytest.approx(0.008)       # 12-15 and 20-25
    assert dict(r["idle_gaps"]) == {"unattributed": pytest.approx(0.005)}
    whole = xplane.reduce(_hand_made())              # first to last event
    assert whole["window_s"] == pytest.approx(0.09)


def test_two_chips_are_averaged():
    t = _hand_made()
    t["devices"][1] = {"XLA Ops": [("%x", 0.0, 5e6)], "XLA Modules": []}
    r = xplane.reduce(t, (0.0, 1e8))
    assert r["busy_s_by_chip"] == pytest.approx({0: 0.025, 1: 0.005})
    assert r["busy_s"] == pytest.approx(0.015)


def test_a_one_chip_cell_on_a_host_with_more_chips_reads_its_own_chip():
    t = _hand_made()
    for chip in (1, 2, 3):                           # in the trace, idle
        t["devices"][chip] = {"XLA Ops": [], "XLA Modules": []}
    t["devices"][2]["XLA Ops"] = [("%x", 0.0, 1e6)]
    r = xplane.reduce(t, (0.0, 1e8), chips=1)
    assert r["busy_s_by_chip"] == pytest.approx({0: 0.025})
    assert r["busy_s"] == pytest.approx(0.025)
    two = xplane.reduce(t, (0.0, 1e8), chips=2)
    assert two["busy_s_by_chip"] == pytest.approx({0: 0.025, 2: 0.001})


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce({"devices": {}, "marker_ns": None})


def test_recorded_tpu_trace():
    trace = xplane.load(FIXTURE)
    assert list(trace["devices"]) == [0]
    assert {"XLA Modules", "XLA Ops"} <= set(trace["devices"][0])
    assert trace["marker_ns"] is not None            # the clock marker
    # the slice the recorder took: 98.760186 ms from the marker on
    window = (trace["marker_ns"], trace["marker_ns"] + 98760186.0)
    spans = [("host_span", trace["marker_ns"], trace["marker_ns"] + 5e7, 0)]
    r = xplane.reduce(trace, window, spans)
    assert r["module_calls"] == {"jit_fixture_step": 3, "jit_fixture_other": 3}
    assert r["busy_s"] == pytest.approx(7.6801e-05, rel=1e-6)
    assert r["modules"]["jit_fixture_step"] == pytest.approx(5.3659e-05,
                                                             rel=1e-4)
    assert 0.99 < 1 - r["busy_s"] / r["window_s"] < 1.0
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"host_span", "unattributed"}
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_spans_move_onto_the_trace_clock_through_the_marker():
    rec = xplane.Recorder("unused")
    rec.marker_wall_ns, rec.start_perf_ns, rec.stop_perf_ns = 10_000_000, 0, 5e9
    spans = [{"sid": "a", "psid": "", "name": "statement",
              "ts_us": 10_000, "dur_us": 2_000},
             {"sid": "b", "psid": "a", "name": "run",
              "ts_us": 10_500, "dur_us": 1_000}]
    window, host = rec.on_trace_clock(700.0, spans)
    assert window == (700.0, 700.0 + 5e9)
    assert host == [("statement", 700.0, 2_000_700.0, 0),
                    ("run", 500_700.0, 1_500_700.0, 1)]
    assert rec.on_trace_clock(None, spans) == (None, [])
