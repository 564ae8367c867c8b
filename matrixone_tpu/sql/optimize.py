"""Index-aware plan rewrites (reference: pkg/sql/plan/apply_indices*.go).

`apply_indices` rewrites

    TopK(k, key = distance(vec_col, const_vec) ASC)
      -> Project(..., distance(...), ...)
        -> Scan(table)                      [no pushed filters]

into the same tree with the Scan replaced by a VectorTopK source that runs
the vector index and yields only ~k candidate rows, fetched by row id.

An IVF-Flat index holds the vectors themselves, so it re-ranks its
candidates exactly in the search's own device program and yields them
nearest first with their distance as the column `P.VECTOR_DIST`; the
Project reads that column wherever it had `distance(vec_col, const_vec)`,
the source takes the TopK's own limit and offset (the TopK leaves the
plan), and the table is asked only for the k rows and the columns the
statement's text names (`select id ... order by l2_distance(v, ...)` never
reads `v`).  That holds only where the index's order IS the statement's:
`l2_distance`, `l2_distance_sq` and `cosine_distance` ascend with the
index's score.  `inner_product` does not (a `vector_ip_ops` index ranks by
1 - x.q, largest product first, while `ORDER BY inner_product(...)` asks
for the smallest first), and IVF-PQ and HNSW yield no distance: there the
Project recomputes the function over the fetched candidates (PQ's exact
re-rank) and the TopK re-orders them.  Either way the k rows are ordered
by the statement's own key, exactly — so the rewrite can only change WHICH
k rows are returned (index recall), never their values or order semantics.

`prune_columns` is the last pass of planning (reference:
plan/query_builder.go remapAllColRefs + the column-pruning half of
`plan/opt_misc.go`): every Scan is narrowed to the columns the plan above
it references, so the reader, the block cache, the upload and the fused
steps carry only those.  What is pruned depends on the plan alone; there
is no switch.
"""

from __future__ import annotations

from typing import Optional

from matrixone_tpu.container import dtypes as dt
from matrixone_tpu.sql import plan as P
from matrixone_tpu.sql.expr import (BoundCast, BoundCol, BoundFunc,
                                    BoundLiteral, columns_used)

_DIST_METRIC = {"l2_distance": "l2", "l2_distance_sq": "l2",
                "cosine_distance": "cosine", "inner_product": "ip"}
#: the distance functions that ascend with the index's own score, so that
#: an index which yields its rows nearest first may stand in for the TopK
_INDEX_ORDERED = ("l2_distance", "l2_distance_sq", "cosine_distance")


def apply_indices(node: P.PlanNode, catalog, nprobe: int = 8,
                  overfetch: int = 3, skip_tables=frozenset()) -> P.PlanNode:
    for attr in ("child", "left", "right"):
        c = getattr(node, attr, None)
        if c is not None:
            setattr(node, attr, apply_indices(c, catalog, nprobe, overfetch,
                                              skip_tables))
    if not isinstance(node, P.TopK):
        return node
    ft = _try_fulltext(node, catalog, skip_tables)
    if ft is not None:
        return ft
    if len(node.keys) != 1 or node.descendings[0]:
        return node
    key = node.keys[0]
    proj = node.child
    if not (isinstance(key, BoundCol) and isinstance(proj, P.Project)):
        return node
    # resolve the sort key to its projected expression
    try:
        kidx = [n for n, _ in proj.schema].index(key.name)
    except ValueError:
        return node
    dist = proj.exprs[kidx]
    if not (isinstance(dist, BoundFunc) and dist.op in _DIST_METRIC
            and len(dist.args) == 2):
        return node
    col_e, vec_e = dist.args
    if not isinstance(col_e, BoundCol):
        col_e, vec_e = vec_e, col_e
    if not (isinstance(col_e, BoundCol) and isinstance(vec_e, BoundLiteral)
            and isinstance(vec_e.value, list)):
        return node
    scan = proj.child
    if not (isinstance(scan, P.Scan) and not scan.filters
            and scan.as_of_ts is None):
        return node
    if scan.table in skip_tables:
        # txn snapshot / workspace reads: exact scan realizes the txn
        # view, the (frontier-built) index cannot — decline the rewrite
        return node
    # find a matching index on (table, column)
    raw_col = col_e.name.split(".")[-1]
    metric = _DIST_METRIC[dist.op]
    for ix in catalog.indexes_on(scan.table):
        if ix.algo in ("ivfflat", "ivfpq", "hnsw") \
                and ix.columns[0] == raw_col \
                and ix.options.get("_metric", "l2") == metric:
            # PQ candidates need a deeper pool: the exact re-rank above
            # (Project recompute + TopK) recovers ADC quantization loss
            factor = overfetch * (3 if ix.algo == "ivfpq" else 1)
            k = (node.k + node.offset) * factor
            source = P.VectorTopK(
                table=scan.table, index_name=ix.name,
                query_vector=list(vec_e.value), k=k, metric=metric,
                columns=scan.columns, schema=scan.schema, nprobe=nprobe)
            proj.child = source
            if ix.algo != "ivfflat" or dist.op not in _INDEX_ORDERED:
                return node
            # the index re-ranks exactly, yields the distance and the rows
            # nearest first, which is the statement's order: it takes the
            # TopK's place
            source.dist_op = dist.op
            source.limit, source.offset = node.k, node.offset
            source.columns = scan.columns + [P.VECTOR_DIST]
            source.schema = scan.schema + [(P.VECTOR_DIST, dt.FLOAT64)]
            column = BoundCol(P.VECTOR_DIST, dt.FLOAT64)
            proj.exprs = [_replace(e, dist, column) for e in proj.exprs]
            return proj
    return node


def _replace(e, target, column):
    """`e` with every occurrence of the expression `target` (under
    functions and casts) replaced by `column`; other nodes are kept as
    they are, not copied."""
    if e == target:
        return column
    if isinstance(e, BoundFunc):
        e.args = [_replace(a, target, column) for a in e.args]
    elif isinstance(e, BoundCast):
        e.arg = _replace(e.arg, target, column)
    return e


def _try_fulltext(node: P.TopK, catalog, skip_tables) -> "P.PlanNode | None":
    """TopK(desc, key = match_against(col, 'q')) over Project over Scan ->
    FulltextTopK replacing the whole subtree."""
    if len(node.keys) != 1 or not node.descendings[0]:
        return None
    key = node.keys[0]
    proj = node.child
    if not (isinstance(key, BoundCol) and isinstance(proj, P.Project)):
        return None
    try:
        kidx = [n for n, _ in proj.schema].index(key.name)
    except ValueError:
        return None
    mexpr = proj.exprs[kidx]
    if not (isinstance(mexpr, BoundFunc) and mexpr.op == "match_against"
            and len(mexpr.args) >= 2):
        return None
    col_exprs, q_e = mexpr.args[:-1], mexpr.args[-1]
    if not (all(isinstance(c, BoundCol) for c in col_exprs)
            and isinstance(q_e, BoundLiteral)
            and isinstance(q_e.value, str)):
        return None
    scan = proj.child
    if not (isinstance(scan, P.Scan) and not scan.filters
            and scan.as_of_ts is None
            and scan.table not in skip_tables):
        return None
    raw_cols_wanted = [c.name.split(".")[-1] for c in col_exprs]
    for ix in catalog.indexes_on(scan.table):
        if ix.algo != "fulltext" or ix.columns != raw_cols_wanted:
            continue
        # every projected output must be a plain column or the match expr
        out_exprs = []
        for e in proj.exprs:
            if e == mexpr:
                out_exprs.append(("score",))
            elif isinstance(e, BoundCol):
                out_exprs.append(("col", e.name.split(".")[-1]))
            else:
                return None
        return P.FulltextTopK(
            table=scan.table, index_name=ix.name, query=q_e.value,
            k=node.k, offset=node.offset, columns=scan.columns,
            out_exprs=out_exprs, schema=proj.schema)
    return None


# ------------------------------------------------------ projection pruning

def prune_columns(node: P.PlanNode) -> P.PlanNode:
    """Narrow every Scan (and VectorTopK source) to the columns the plan
    references, in place, and rebuild the schemas derived from them.

    Top-down: each node is told which of its output names its parent
    reads (`None` = all of them, which is what the root, a node type this
    pass does not know, DISTINCT, FILL and UNION arms ask for).  A node
    that computes its output (Project, Aggregate, UdfAggregate) asks its
    child for exactly what its own expressions reference; a node that
    passes rows through (Filter, Sort, TopK, Limit, Sample, Window, Join)
    adds its own references to its parent's.  A Join (not semi/anti, whose
    output is its probe side) asks its inputs for that sum and hands up
    only what its parent reads: its `schema` is what the probe gathers
    (vm/join.output_schema).  Expressions are never copied: the plan cache
    patches tagged parameter literals in place."""
    _prune(node, None)
    return node


def _cols(*exprs) -> set:
    out: set = set()
    for e in exprs:
        if e is not None:
            out.update(columns_used(e))
    return out


def _own_refs(node: P.PlanNode) -> set:
    """Names a pass-through node's own expressions read from its input."""
    if isinstance(node, P.Filter):
        return _cols(node.pred)
    if isinstance(node, (P.Sort, P.TopK)):
        return _cols(*node.keys)
    if isinstance(node, P.Window):
        return _cols(*[e for _fn, arg, part, okeys, *_ in node.entries
                       for e in (arg, *part, *okeys)])
    if isinstance(node, P.Join):
        return _cols(*node.left_keys, *node.right_keys, node.residual)
    return set()                                   # Limit, Sample


def _prune(node: P.PlanNode, needed: Optional[set]) -> None:
    if isinstance(node, (P.Scan, P.VectorTopK)):
        _narrow_source(node, needed)
    elif isinstance(node, P.Project):
        _prune(node.child, _cols(*node.exprs))
    elif isinstance(node, P.Aggregate):
        _prune(node.child, _cols(*node.group_keys,
                                 *[a.arg for a in node.aggs]))
    elif isinstance(node, P.UdfAggregate):
        _prune(node.child, _cols(*node.calls))
    elif isinstance(node, (P.Filter, P.Sort, P.TopK, P.Limit, P.Sample,
                           P.Window, P.Join)):
        own = _own_refs(node)
        below = None if needed is None else needed | own
        if isinstance(node, P.Join):
            semi = node.kind in ("semi", "anti")
            _prune(node.left, below)
            # a semi/anti build side is only matched against, never output
            _prune(node.right, own if semi else below)
            inputs = [node.left] if semi else [node.left, node.right]
        else:
            _prune(node.child, below)
            inputs = [node.child]
        # the derived schema keeps its order, less what the inputs no longer
        # produce; a Window's own hidden columns stay
        kept = {n for c in inputs for n, _ in c.schema}
        if isinstance(node, P.Window):
            kept |= {e[5] for e in node.entries}
        if isinstance(node, P.Join) and not semi and needed is not None:
            # a join computes its output (a gather a build column, a copy
            # a probe column), so it hands up only what is read above it:
            # its keys and residual are read inside, from its inputs
            kept &= needed
            if not kept:
                # nothing is read from the rows (count(*)): they are still
                # counted, through the probe column that costs least, and
                # never through a build column, which would cost a gather
                kept = {min(node.left.schema, key=_row_cost)[0]}
        node.schema = [(n, d) for n, d in node.schema if n in kept]
    else:
        # Distinct, Fill, Union, and anything this pass does not know:
        # every column of every child is needed
        for attr in ("child", "left", "right"):
            c = getattr(node, attr, None)
            if c is not None:
                _prune(c, None)
        for c in getattr(node, "children", None) or []:
            _prune(c, None)


def _narrow_source(node, needed: Optional[set]) -> None:
    """`columns` and `schema` of a Scan / VectorTopK narrow together
    (every consumer zips the two) to what is needed above, what the
    pushed filters read, and the row id where the scan had it."""
    from matrixone_tpu.storage.engine import ROWID
    if needed is None:
        return
    keep = needed | _cols(*getattr(node, "filters", ()))
    pairs = [(c, s) for c, s in zip(node.columns, node.schema)
             if s[0] in keep or c == ROWID]
    if not pairs:
        # nothing is read from the rows (count(*)): the scan still has to
        # count them, through the column that costs the fewest bytes a row
        pairs = [min(zip(node.columns, node.schema),
                     key=lambda pair: _row_cost(pair[1]))]
    node.columns = [c for c, _ in pairs]
    node.schema = [s for _, s in pairs]


def _row_cost(schema_entry) -> tuple:
    """Order of the one column a scan or a join keeps when none is needed:
    fixed width before varlen and vector columns, then bytes a row; `min`
    keeps the first of equals, so ties go by schema order and the plan
    cache and the peers of a distributed scan agree."""
    d = schema_entry[1]
    if d.is_varlen:
        return (1, 4)                      # dictionary codes
    width = d.np_dtype.itemsize * max(d.dim, 1)
    return (1 if d.is_vector else 0, width)
