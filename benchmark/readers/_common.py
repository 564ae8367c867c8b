"""What several readers share."""


def completed(ctx):
    """The statements of the window that were answered."""
    return [s for s in ctx["executed"] if s["error"] is None]


def table_rows(ctx, statements):
    """Rows of the base tables the statements name, from the
    configuration's own row counts."""
    return sum(ctx["table_rows"][t] for s in statements for t in s["tables"])


def delta(ctx, keys):
    """Delta of the program's counters over the window.  A key ending in
    `*` sums every counter that starts with what stands before it."""
    total = 0.0
    for key in keys:
        names = ([k for k in ctx["after"] if k.startswith(key[:-1])]
                 if key.endswith("*") else [key])
        for k in names:
            total += ctx["after"].get(k, 0) - ctx["before"].get(k, 0)
    return total
