"""Mean latency of the completed statements on the client's clock (send
to last row) less the mean duration of the program's spans named `span`,
in ms: with `statement` it is what a statement spends outside the
session, reading the wire, encoding the result and sending it.  Nothing
where no statement completed or there is no such span (motrace, armed in
the traced run)."""

from readers._common import completed


def read(ctx, span):
    done = completed(ctx)
    inside = [s["dur_us"] for s in ctx["spans"] if s["name"] == span]
    if not done or not inside:
        return None
    latency_ms = sum(s["t_done_ns"] - s["t_send_ns"] for s in done) \
        / len(done) / 1e6
    return latency_ms - sum(inside) / len(inside) / 1e3
