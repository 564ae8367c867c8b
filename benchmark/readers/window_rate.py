"""Completed work over the whole window: from the window's start to the
last completion, statements in flight at the close included."""

from readers._common import completed, table_rows


def read(ctx, count):
    w = ctx["window"]
    elapsed = (w["t_last_done_ns"] - w["t_start_ns"]) / 1e9
    done = completed(ctx)
    if not done or elapsed <= 0:
        return None
    if count == "statements":
        return len(done) / elapsed
    if count == "table_rows":
        return table_rows(ctx, done) / elapsed
    raise ValueError(f"unknown count {count!r}")
