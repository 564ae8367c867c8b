"""delta(`counters`) over the window per completed statement or per table
row scanned (rows as counted for the rows-per-second rate)."""

from readers._common import completed, delta, table_rows


def read(ctx, counters, per):
    done = completed(ctx)
    n = len(done) if per == "statements" else table_rows(ctx, done)
    return delta(ctx, counters) / n if n else None
