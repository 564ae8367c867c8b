"""From a profiler trace (`.xplane.pb`) to device busy / idle time, time per
XLA module, and the longest idle gaps by what the host was doing.

The trace is taken by the process that holds the chip (`Recorder`), with
the Python tracer off: it costs the host more than everything else and
the reduction reads only the device's planes and one marker of its own.
Read with `jax.profiler.ProfileData` and nothing else.

What a TPU trace holds (looked at by hand, PR 26): one plane per chip,
`/device:TPU:<n>`, with the lines `XLA Modules` (one event per executed
program, named `jit_<fun>(<fingerprint>)`), `XLA Ops` (one event per HLO
operation; a `while` covers its body's operations) and `Async XLA Ops`
(copies in flight, which overlap compute and are not counted as busy).
Event times are nanoseconds from the start of the profiling session, on
the host's clock.
"""

import glob
import os
import re
import time

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_FINGERPRINT = re.compile(r"\(\d+\)$")
MARKER = "benchmark_clock_sync"


class Recorder:
    """`start()` / `stop()` around the traced slice.  A `TraceAnnotation`
    of the benchmark's own marks a known instant of the host's clocks, so
    that spans timed with `time.time_ns()` can be laid over the trace."""

    def __init__(self, log_dir):
        self.log_dir = log_dir
        self.marker_wall_ns = None
        self.start_perf_ns = None
        self.stop_perf_ns = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.start_perf_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(MARKER):
            self.marker_wall_ns = time.time_ns()
            time.sleep(0.001)

    def stop(self):
        import jax
        self.stop_perf_ns = time.perf_counter_ns()
        jax.profiler.stop_trace()

    def on_trace_clock(self, marker_ns, spans):
        """-> (window, host spans) for `reduce`: the traced slice and the
        program's motrace spans (`ts_us` / `dur_us` on the wall clock, `sid`
        / `psid` links) moved onto the trace's clock through the marker.
        Without the marker: the whole trace, and no spans."""
        if marker_ns is None:
            return None, []
        shift = marker_ns - self.marker_wall_ns
        by_id, depth = {s["sid"]: s for s in spans}, {}

        def depth_of(s):
            if s["sid"] not in depth:
                parent = by_id.get(s["psid"])
                depth[s["sid"]] = 0 if parent is None else depth_of(parent) + 1
            return depth[s["sid"]]

        return ((marker_ns,
                 marker_ns + self.stop_perf_ns - self.start_perf_ns),
                [(s["name"], s["ts_us"] * 1000 + shift,
                  (s["ts_us"] + s["dur_us"]) * 1000 + shift, depth_of(s))
                 for s in spans])

    def path(self):
        found = sorted(glob.glob(os.path.join(
            self.log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.log_dir}")
        return found[-1]


def load(path):
    """-> {"devices": {chip: {line name: [(name, start_ns, dur_ns)]}},
    "marker_ns": start of the clock marker or None}."""
    from jax.profiler import ProfileData
    devices, marker = {}, None
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(2))] = {
                line.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:") and marker is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == MARKER:
                        marker = float(e.start_ns)
                        break
                if marker is not None:
                    break
    return {"devices": devices, "marker_ns": marker}


def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return out


def _clip(intervals, lo, hi):
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def module_name(event_name):
    """`jit__search(3808139069870405639)` -> `jit__search`."""
    return _FINGERPRINT.sub("", event_name)


def reduce(trace, window=None, spans=(), top=10, chips=None):
    """Busy and idle time of the traced slice.

    `chips` is how many chips the cell uses: a host may hold more than the
    cell asks for, and their planes are in the trace too, idle.  Only the
    `chips` busiest planes are read; by default every plane.

    `window` is (start_ns, end_ns) on the trace's clock; by default the
    span from the first to the last device event.  `spans` are host spans
    as (name, start_ns, end_ns, depth) on the trace's clock; each idle gap
    goes to the deepest span open at its middle, or to `unattributed`.

    -> {"window_s", "busy_s" (averaged over the chips read),
        "busy_s_by_chip",
        "modules": {name: seconds, summed over the chips},
        "module_calls": {name: count},
        "device_ops": [[name, seconds], ...]  the `top` longest modules,
        "idle_gaps": [[name, seconds], ...]   idle seconds by what the host
                                              was doing, the `top` largest,
        "longest_gap_s"}
    """
    devices = trace["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    if window is None:
        starts = [e[1] for d in devices.values() for evs in d.values()
                  for e in evs]
        ends = [e[1] + e[2] for d in devices.values() for evs in d.values()
                for e in evs]
        window = (min(starts), max(ends))
    lo, hi = window
    busy_of = {}
    for chip, lines in devices.items():
        ops = lines.get("XLA Ops") or lines.get("XLA Modules") or []
        busy_of[chip] = _clip(union([s, s + d] for _, s, d in ops), lo, hi)
    by_busy = sorted(busy_of, key=lambda c: (
        -sum(b - a for a, b in busy_of[c]), c))
    used = sorted(by_busy[:chips or len(by_busy)])
    busy_by_chip, modules, calls, gaps = {}, {}, {}, []
    for chip in used:
        lines, busy = devices[chip], busy_of[chip]
        busy_by_chip[chip] = sum(b - a for a, b in busy) / 1e9
        for name, s, d in lines.get("XLA Modules", []):
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                key = module_name(name)
                modules[key] = modules.get(key, 0.0) + part / 1e9
                calls[key] = calls.get(key, 0) + 1
        edge = lo
        for a, b in busy + [[hi, hi]]:
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
    by_host = {}
    for a, b in gaps:
        mid, owner, depth = (a + b) / 2, "unattributed", -1
        for name, s, e, d in spans:
            if s <= mid < e and d > depth:
                owner, depth = name, d
        by_host.setdefault(owner, []).append((b - a) / 1e9)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_by_chip.values()) / len(busy_by_chip),
        "busy_s_by_chip": busy_by_chip,
        "modules": modules, "module_calls": calls,
        "device_ops": [[k, v] for k, v in sorted(
            modules.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, sum(v)] for k, v in sorted(
            by_host.items(), key=lambda kv: -sum(kv[1]))[:top]],
        "longest_gap_s": max((b - a for a, b in gaps), default=0.0) / 1e9,
    }
