"""Busy time of a layer per statement, in ms: over the traces that hold a
`statement` span, the summed duration of the spans named in `spans`, per
`statement` span.  With `self` true each span counts less what its own
child spans cover (a `scan.chunk` without the loads below it).  The scan's
spans run on the prefetch thread beside the statement's own, so these are
busy times of a layer and may sum past the statement's duration.  Nothing
where no statement was traced or the program has no such span (motrace,
armed in the traced run)."""

from readers._spans import by_trace, children, covered_us, statements


def read(ctx, spans, self):
    n_statements, total_us, found = 0, 0, False
    for trace in by_trace(ctx["spans"]).values():
        roots = statements(trace)
        if not roots:
            continue
        n_statements += len(roots)
        below = children(trace) if self else {}
        for s in trace:
            if s["name"] in spans:
                found = True
                total_us += s["dur_us"] - covered_us(
                    s, below.get(s["sid"], ()))
    if not n_statements or not found:
        return None
    return total_us / n_statements / 1e3
