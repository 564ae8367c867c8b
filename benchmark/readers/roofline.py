"""Share of the roofline, in percent: the least time the chip needs for
the work the statements of the traced slice define (`work.py`, a function
of the query and the data; the statements are those whose template names
the work function `work`) over the device-busy time of the slice (the
profiler trace).  A statement that straddles an edge of the slice counts
by the part of it inside.  Nothing where the device did nothing."""

import peaks
import work as W


def read(ctx, work):
    tr, edges = ctx["trace"], ctx["trace_slice_perf_ns"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    peak = peaks.peaks(ctx["device"]["kind"])
    lo, hi = edges
    least = 0.0
    for s in ctx["executed"]:
        inside = min(s["t_done_ns"], hi) - max(s["t_send_ns"], lo)
        if (inside > 0 and s["error"] is None
                and (s.get("work") or {}).get("fn") == work):
            seconds, _ = W.least_seconds(W.of(ctx, s), peak)
            least += seconds * inside / (s["t_done_ns"] - s["t_send_ns"])
    return 100.0 * least / tr["busy_s"] if least > 0 else None
