"""What the spans named in `spans` say of their own thread, from the thread
CPU time a span carries (`cpu_us`) beside its wall time (`dur_us`).
`measure`:

- `self_ms`: each span's duration less what its children on its own
  thread cover, ms a statement.  The scan's prefetch thread runs beside
  the statement's, so its spans do not take an operator's self time.
- `self_cpu_ms`: each span's CPU time less its own thread's children's,
  ms a statement.
- `cpu_ms`: the CPU time of each span that has no ancestor in `spans`
  (a re-entrant execute nests a `statement` in a `statement`), ms a
  statement.
- `offcpu_share`: 1 - the spans' CPU time over their duration: the
  share of their time their thread was off the CPU (the GIL, a lock,
  the device, I/O).

Only traces that hold a `statement` span are read.  Nothing where no span
of those names carries `cpu_us` (a program that does not record it), or
no statement was traced."""

from readers._spans import by_trace, children, covered_us, statements


def _own_thread(span, kids):
    return [k for k in kids if k["thread"] == span["thread"]
            and k.get("proc") == span.get("proc")]


def _outermost(span, names, by_sid):
    parent = by_sid.get(span["psid"])
    while parent is not None:
        if parent["name"] in names:
            return False
        parent = by_sid.get(parent["psid"])
    return True


def read(ctx, spans, measure):
    names = set(spans)
    n_statements, total, dur, found = 0, 0, 0, False
    for trace in by_trace(ctx["spans"]).values():
        roots = statements(trace)
        if not roots:
            continue
        n_statements += len(roots)
        below = children(trace)
        by_sid = {s["sid"]: s for s in trace}
        for s in trace:
            if s["name"] not in names or "cpu_us" not in s:
                continue
            found = True
            kids = _own_thread(s, below.get(s["sid"], ()))
            if measure == "self_ms":
                total += s["dur_us"] - covered_us(s, kids)
            elif measure == "self_cpu_ms":
                total += s["cpu_us"] - sum(k.get("cpu_us", 0) for k in kids)
            elif measure == "cpu_ms":
                if _outermost(s, names, by_sid):
                    total += s["cpu_us"]
            elif measure == "offcpu_share":
                total += s["cpu_us"]
                dur += s["dur_us"]
            else:
                raise ValueError(f"unknown measure {measure!r}")
    if not found:
        return None
    if measure == "offcpu_share":
        return 1 - total / dur if dur else None
    return total / n_statements / 1e3
