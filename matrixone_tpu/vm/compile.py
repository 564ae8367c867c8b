"""Plan -> operator tree (reference: pkg/sql/compile/compile.go:670
compileScope, collapsed: one process, one pipeline per plan for now;
ParallelRun/RemoteRun equivalents live in matrixone_tpu.parallel).

After the tree is built, the whole-plan fusion pass (vm/fusion.py)
replaces maximal jit-traceable operator chains with FusedFragmentOp
nodes — one compiled XLA program per (plan-shape, dtype-signature,
padded-batch-bucket) instead of per-operator dispatches.  `MO_PLAN_FUSION=0`
(or `SET plan_fusion = 0`) preserves the per-operator path unchanged.
"""

from __future__ import annotations

from matrixone_tpu.sql import plan as P
from matrixone_tpu.vm import operators as ops
from matrixone_tpu.vm.process import ExecContext


def compile_plan(node: P.PlanNode, ctx) -> ops.Operator:
    if not isinstance(ctx, ExecContext):
        ctx = ExecContext(catalog=ctx)
    op = _compile_node(node, ctx)
    from matrixone_tpu.vm import fusion
    if fusion.enabled(ctx):
        op = fusion.fuse_operator_tree(op, ctx)
    return op


def iter_ops(root: ops.Operator):
    """Every operator reachable through the standard tree attributes
    (fragments expose their source as `child`, so this walks through
    them)."""
    stack = [root]
    while stack:
        op = stack.pop()
        yield op
        for attr in ("child", "left", "right"):
            c = getattr(op, attr, None)
            if isinstance(c, ops.Operator):
                stack.append(c)
        for c in getattr(op, "children", None) or []:
            if isinstance(c, ops.Operator):
                stack.append(c)


def retarget_tree(root: ops.Operator, ctx: ExecContext) -> None:
    """Prepare a cached compiled operator tree for a fresh execution:
    point every operator at the new ExecContext (snapshot ts, session
    variables) and clear per-execution state that would otherwise leak
    across runs (runtime filters injected by joins, union-wide string
    dictionaries)."""
    from matrixone_tpu.vm.operators import ScanOp, UnionOp
    for op in iter_ops(root):
        if hasattr(op, "ctx"):
            op.ctx = ctx
        if isinstance(op, ScanOp):
            op.runtime_filters = []
        if isinstance(op, UnionOp):
            op._union_dicts = {}
            op._union_lut = {}


def _compile_node(node: P.PlanNode, ctx: ExecContext) -> ops.Operator:
    catalog = ctx.catalog
    if isinstance(node, P.Scan):
        rel = catalog.get_table(node.table)
        return ops.ScanOp(node, rel, ctx=ctx)
    if isinstance(node, P.Values):
        return ops.ValuesOp(node)
    if isinstance(node, P.Materialized):
        return ops.MaterializedOp(node)
    if isinstance(node, P.Filter):
        return ops.FilterOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Project):
        return ops.ProjectOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.UdfAggregate):
        return ops.UdfAggregateOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Aggregate):
        return ops.AggOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Sort):
        return ops.SortOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.TopK):
        return ops.TopKOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Limit):
        return ops.LimitOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Window):
        from matrixone_tpu.vm.window import WindowOp
        return WindowOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Distinct):
        return ops.DistinctOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Sample):
        return ops.SampleOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Fill):
        return ops.FillOp(node, _compile_node(node.child, ctx))
    if isinstance(node, P.Union):
        return ops.UnionOp(node, [_compile_node(c, ctx)
                                  for c in node.children])
    if isinstance(node, P.FulltextTopK):
        from matrixone_tpu.vm.fulltext_scan import FulltextTopKOp
        return FulltextTopKOp(node, ctx)
    if isinstance(node, P.VectorTopK):
        from matrixone_tpu.vm.vector_scan import VectorTopKOp
        return VectorTopKOp(node, ctx)
    if isinstance(node, P.Join):
        from matrixone_tpu.vm.join import JoinOp
        return JoinOp(node, _compile_node(node.left, ctx),
                      _compile_node(node.right, ctx), ctx=ctx)
    raise NotImplementedError(f"compile: {type(node).__name__}")
