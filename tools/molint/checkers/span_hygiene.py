"""span-hygiene: motrace spans are balanced and trace propagation stays
single-definition.

The tracing plane (matrixone_tpu/utils/motrace.py) keeps the ambient
context stack consistent by construction — but only if every span goes
through the context-manager protocol and every wire hop goes through
the fabric.  Conventions encoded:

  * spans open ONLY via the `with` statement: a span factory call
    (`motrace.span(...)`, `statement_span(...)`, `root_span(...)`)
    anywhere but the context expression of a `with` item — assigned to
    a name, passed as an argument, a bare expression statement, or an
    explicit `.__enter__()` — risks an unbalanced enter/exit that
    corrupts the ambient context stack for every later span on the
    thread (`remote_session` is exempt: its object carries
    `attach()`/`harvest()` by design and is still entered via `with`);
  * a span in a generator wraps the work between two yields and never
    a `yield`: a span held open across a yield becomes the ambient
    parent of whatever the consumer opens next, and its duration counts
    the consumer's work;
  * trace injection is single-definition, exactly like the deadline
    checker's contract for `deadline_ms`: `RpcClient.call` /
    `WorkerClient.run` inject the ambient context themselves, so every
    call site threads trace ctx BY CONSTRUCTION.  Calling
    `motrace.inject(...)`/`motrace.merge_remote(...)` outside the
    fabric modules forks that propagation path, and a hand-built
    `"trace"` key in a header dict passed to `.call(`/`.run(` clobbers
    the fabric's injection with a stale/foreign context.

Suppress with `# molint: disable=span-hygiene -- why` (justification
required) for the rare deliberate exception.
"""

from __future__ import annotations

import ast
from typing import Iterable, Set

from tools.molint import Checker, Finding, Project
from tools.molint.astutil import aliases_of, dotted

_MOTRACE_MOD = "matrixone_tpu.utils.motrace"


def _span_call_names(mod, factories) -> Set[str]:
    """Local dotted prefixes that resolve to motrace span factories in
    this module: 'motrace.span', '_mt.root_span', bare 'span', ..."""
    out: Set[str] = set()
    for alias, target in aliases_of(mod).items():
        if target == _MOTRACE_MOD or target.endswith(".motrace"):
            for f in factories:
                out.add(f"{alias}.{f}")
        for f in factories:
            if target == f"{_MOTRACE_MOD}.{f}":
                out.add(alias)
    return out


def _yields_under(body) -> Iterable[ast.AST]:
    """`yield` / `yield from` nodes in `body` that belong to the same
    function as `body` (a nested def or lambda is another generator)."""
    stack = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, (ast.Yield, ast.YieldFrom)):
            yield node
        stack.extend(ast.iter_child_nodes(node))


def _injector_names(mod) -> Set[str]:
    out: Set[str] = set()
    for alias, target in aliases_of(mod).items():
        if target == _MOTRACE_MOD or target.endswith(".motrace"):
            out.add(f"{alias}.inject")
            out.add(f"{alias}.merge_remote")
        if target in (f"{_MOTRACE_MOD}.inject",
                      f"{_MOTRACE_MOD}.merge_remote"):
            out.add(alias)
    return out


class SpanHygieneChecker(Checker):
    rule = "span-hygiene"
    description = ("motrace spans open only via `with`; trace injection "
                   "stays in the RPC fabric (rpc.call / WorkerClient.run "
                   "thread ctx by construction)")
    default_config = {
        #: factory functions whose result must be entered immediately
        "factories": ("span", "statement_span", "root_span"),
        #: modules allowed to construct/inject spans freely (the tracer
        #: itself and the two fabric client definitions)
        "fabric_modules": ("utils/motrace.py", "cluster/rpc.py",
                           "worker/client.py"),
    }

    def check(self, project: Project, config: dict) -> Iterable[Finding]:
        factories = tuple(config["factories"])
        fabric = tuple(config["fabric_modules"])
        for mod in project.modules:
            if mod.tree is None:
                continue
            if any(mod.path.endswith(m) for m in fabric):
                continue
            # NOTE: modules without motrace imports still get scanned —
            # the hand-built "trace" wire-key check below is independent
            # of any import
            span_names = _span_call_names(mod, factories)
            inject_names = _injector_names(mod)
            with_exprs = set()
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    for item in node.items:
                        with_exprs.add(id(item.context_expr))
                    opened = [dotted(item.context_expr.func) or ""
                              for item in node.items
                              if isinstance(item.context_expr, ast.Call)]
                    opened = [d for d in opened if d in span_names]
                    if not opened:
                        continue
                    for y in _yields_under(node.body):
                        yield Finding(
                            self.rule, mod.path, y.lineno,
                            f"`yield` inside `with {opened[0]}(...)` — "
                            f"the span stays open while the consumer "
                            f"runs: it becomes the parent of the "
                            f"consumer's spans and its duration counts "
                            f"their work; end the span before the yield")
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                d = dotted(node.func) or ""
                if d in span_names and id(node) not in with_exprs:
                    yield Finding(
                        self.rule, mod.path, node.lineno,
                        f"span factory {d}(...) used outside a `with` "
                        f"statement — an unbalanced enter/exit corrupts "
                        f"the ambient trace-context stack; open spans "
                        f"only as `with {d}(...):`")
                if d in inject_names:
                    yield Finding(
                        self.rule, mod.path, node.lineno,
                        f"{d}(...) outside the RPC fabric — trace "
                        f"injection is single-definition (RpcClient."
                        f"call / WorkerClient.run thread the ambient "
                        f"ctx for every call site); route the hop "
                        f"through the fabric instead")
                # hand-built "trace" wire keys clobber fabric injection
                if isinstance(node.func, ast.Attribute) and \
                        node.func.attr in ("call", "run"):
                    for arg in list(node.args) + \
                            [kw.value for kw in node.keywords]:
                        if isinstance(arg, ast.Dict) and any(
                                isinstance(k, ast.Constant)
                                and k.value == "trace"
                                for k in arg.keys):
                            yield Finding(
                                self.rule, mod.path, arg.lineno,
                                "hand-built \"trace\" key in a wire "
                                "header — the fabric injects the "
                                "ambient trace ctx itself; a literal "
                                "key ships a stale/foreign context")
