"""How far motrace's clock lies from the profiler's.

    python tools/trace_clock_residual.py [--rows N] [--statements K]

The benchmark lays motrace spans (`time.time_ns()`) over a device trace
through one marker (`benchmark/xplane.py` `Recorder`).  While armed a span
also enters a `jax.profiler.TraceAnnotation` of its name, so the same
xplane holds each span a second time on the profiler's own clock.  This
script runs a fused aggregate under a profile and prints, over the
`fusion.dispatch` spans, the distance between the marker-shifted start of
each span and the start of its annotation twin: median and worst, in
microseconds.  A residual far under a device program's duration says the
marker is enough and nobody has to read the host plane.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

SPAN = "fusion.dispatch"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=600_000)
    ap.add_argument("--statements", type=int, default=20)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    import xplane
    from matrixone_tpu.frontend import Session
    from matrixone_tpu.utils import motrace

    s = Session()
    s.execute("create table r (a bigint, b bigint)")
    rng = np.random.default_rng(0)
    s.catalog.get_table("r").insert_numpy(
        {"a": np.arange(args.rows), "b": rng.integers(0, 100, args.rows)})
    sql = "select sum(b), count(*) from r where a >= {}"
    s.execute(sql.format(0))                      # compile outside
    motrace.TRACER.arm(sample=1.0)
    motrace.TRACER.clear()
    rec = xplane.Recorder(tempfile.mkdtemp(prefix="mo_residual_"))
    rec.start()
    for i in range(args.statements):
        s.execute(sql.format(i + 1))
    rec.stop()
    motrace.TRACER.disarm()

    spans = sorted(sp["ts_us"] * 1000 for tid in motrace.TRACER.trace_ids()
                   for sp in motrace.TRACER.spans_of(tid)
                   if sp["name"] == SPAN)
    twins, marker_ns = [], None
    for plane in ProfileData.from_file(rec.path()).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == SPAN:
                    twins.append(float(e.start_ns))
                elif e.name == xplane.MARKER and marker_ns is None:
                    marker_ns = float(e.start_ns)
    twins.sort()
    out = {"device": jax.devices()[0].device_kind, "spans": len(spans),
           "twins": len(twins), "marker_found": marker_ns is not None}
    if marker_ns is not None and spans and len(spans) == len(twins):
        shift = marker_ns - rec.marker_wall_ns
        # a span takes its start, then enters its twin: the twin is later
        residual_us = [(t - (sp + shift)) / 1e3
                       for sp, t in zip(spans, twins)]
        out.update(residual_us_median=statistics.median(residual_us),
                   residual_us_worst=max(residual_us, key=abs))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
