"""The least work a statement defines: a function of the query and the
data, never of the implementation, so that a later kernel change cannot
make it stale.  A traffic template names its work function and gives it
what the query's own text fixes, e.g.

    "work": {"fn": "scan", "columns": {"lineitem": {"l_quantity":
             "decimal64", "l_shipdate": "date"}}}

`of(ctx, statement)` calls that function and returns {"bytes", "flops"}
for ONE statement; `least_seconds` turns it into the least time the chip
could take and says which peak binds."""

#: bytes of one value, by the column's type in the schema
#: (DECIMAL(15,2) is a scaled int64, DATE an int32, CHAR(1) one byte)
WIDTH = {"decimal64": 8, "int64": 8, "int32": 4, "date": 4, "char1": 1}


def scan_bytes(columns, table_rows):
    """`columns` is {table: {column: type}}, the columns the query's text
    names; each is read once for every row of its table.  A type is a name
    of `WIDTH` or a whole number of bytes (CHAR(10) is 10)."""
    return sum(table_rows[table]
               * sum(t if isinstance(t, int) else WIDTH[t]
                     for t in cols.values())
               for table, cols in columns.items())


def ivf_query_work(vectors, dim, lists, nprobe, itemsize=4):
    """One IVF-Flat query has to read the centroids and the probed lists
    (on average vectors * nprobe / lists rows) at the stored width, and
    multiply-add each element once."""
    rows = lists + vectors * nprobe / lists
    return {"bytes": rows * dim * itemsize, "flops": 2 * dim * rows}


def scan(ctx, statement, columns):
    """A scan-aggregate or a join has to read each named column of every
    row once; its arithmetic is a few operations a row and never binds.
    Rows are the configuration's own row counts."""
    return {"bytes": scan_bytes(columns, ctx["table_rows"]), "flops": 0}


def ivf_query(ctx, statement):
    cfg = ctx["config"]
    return ivf_query_work(cfg["vectors"], cfg["dim"], cfg["lists"],
                          cfg["nprobe"])


FUNCTIONS = {"scan": scan, "ivf_query": ivf_query}


def of(ctx, statement):
    """The work of one executed statement, by the function its template
    names; None for a template that names none."""
    spec = statement.get("work")
    if not spec:
        return None
    args = {k: v for k, v in spec.items() if k != "fn"}
    return FUNCTIONS[spec["fn"]](ctx, statement, **args)


def least_seconds(work, peak):
    """-> (seconds, "bytes" | "flops"): the larger of bytes over peak
    bytes/s and operations over peak FLOP/s, and which of the two it is."""
    by_bytes = work["bytes"] / peak["bytes_per_s"]
    by_flops = work["flops"] / peak["flops"]
    return (by_bytes, "bytes") if by_bytes >= by_flops else (by_flops, "flops")
