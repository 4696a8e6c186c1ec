"""Shared AST plumbing for the rule families."""

from __future__ import annotations

import ast


def dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def const_str(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def self_attr(node: ast.AST) -> str | None:
    """``X`` when node is ``self.X``, else None."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def int_tuple(node: ast.AST) -> set[int] | None:
    """The ints a donate_argnums expression can evaluate to, unioned over
    both arms of an IfExp (``(0, 1, 2) if donate else ()`` → {0, 1, 2}); None
    when the expression is not statically resolvable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) and not isinstance(node.value, bool):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: set[int] = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.add(e.value)
            else:
                return None
        return out
    if isinstance(node, ast.IfExp):
        a, b = int_tuple(node.body), int_tuple(node.orelse)
        if a is None or b is None:
            return None
        return a | b
    return None


def call_args_with_kw(call: ast.Call, kw_name: str, pos: int | None) -> ast.AST | None:
    """The argument bound to keyword ``kw_name`` or position ``pos``."""
    for kw in call.keywords:
        if kw.arg == kw_name:
            return kw.value
    if pos is not None and len(call.args) > pos:
        return call.args[pos]
    return None


def param_names(fn: ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda) -> set[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n != "self"}


def names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


class ScopeIndex:
    """Function defs by qualified position, with parent links — enough
    name resolution for same-module call-graph walking."""

    def __init__(self, tree: ast.AST):
        self.parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
        self.defs: list[ast.FunctionDef | ast.AsyncFunctionDef] = [
            n for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]

    def enclosing_defs(self, node: ast.AST):
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield cur
            cur = self.parents.get(cur)

    def enclosing_class(self, node: ast.AST) -> ast.ClassDef | None:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, ast.ClassDef):
                return cur
            cur = self.parents.get(cur)
        return None

    def resolve(self, name: str, at: ast.AST):
        """The function def ``name`` visible from ``at``: innermost
        enclosing scope outward, then module level. Best-effort (no
        imports, no reassignment tracking) — exactly enough for the
        ``make_prefill``-style local factories the engines use."""
        scopes = list(self.enclosing_defs(at))
        for scope in scopes:
            for stmt in ast.walk(scope):
                if (
                    isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and stmt.name == name
                    and stmt is not at
                ):
                    return stmt
        for d in self.defs:
            if d.name == name and self.parents.get(d).__class__ is ast.Module:
                return d
        return None

    def returned_defs(self, factory: ast.FunctionDef | ast.AsyncFunctionDef):
        """Local function defs that ``factory`` returns (the
        ``def make_step(...): ... return step_fn`` closure-factory idiom)."""
        local = {
            n.name: n
            for n in ast.walk(factory)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) and n is not factory
        }
        out = []
        for node in ast.walk(factory):
            if isinstance(node, ast.Return) and isinstance(node.value, ast.Name):
                hit = local.get(node.value.id)
                if hit is not None:
                    out.append(hit)
        return out


def body_calls(fn: ast.FunctionDef | ast.AsyncFunctionDef):
    """Call nodes in ``fn``'s own body, not descending into nested defs
    (nested defs are traced only if called, and then they are visited as
    their own reachable node)."""
    stack: list[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if isinstance(node, ast.Call):
            yield node
        stack.extend(ast.iter_child_nodes(node))


def body_nodes(fn: ast.FunctionDef | ast.AsyncFunctionDef):
    """All nodes in ``fn``'s own body, not descending into nested defs."""
    stack: list[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))
