"""Share of the duration of the spans named `span` that no descendant of
theirs covers, on any thread: the union of the descendants' intervals,
clipped to the span, against the span.  For `run` it is what the spans
inside a statement cannot see.  Nothing where there is no such span
(motrace, armed in the traced run)."""

from readers._spans import by_trace, children, covered_us


def read(ctx, span):
    total_us = bare_us = 0
    for trace in by_trace(ctx["spans"]).values():
        below = children(trace)
        for s in trace:
            if s["name"] != span:
                continue
            descendants, stack = [], [s["sid"]]
            while stack:
                kids = below.get(stack.pop(), ())
                descendants.extend(kids)
                stack.extend(k["sid"] for k in kids)
            total_us += s["dur_us"]
            bare_us += s["dur_us"] - covered_us(s, descendants)
    return bare_us / total_us if total_us else None
