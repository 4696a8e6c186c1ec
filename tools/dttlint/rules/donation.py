"""Rule family 2: donation safety.

``donate_argnums`` hands the argument's buffer to XLA — after the call
the Python reference points at freed (or aliased-output) memory, and a
read produces garbage or a crash *only under real allocators*, so CPU
tests pass while TPU serving corrupts KV pages. The engines and the
kv_pool pool-scatter entry points all follow the rebind idiom
(``self.layers = _fused_page_scatter(self.layers, ...)``); this rule flags any
call site that reads a donated argument again before rebinding it.

Detection is module-local and name-based: a binding whose value is
``jax.jit(..., donate_argnums=...)`` or ``*._jit_program(fn, kind,
donate)`` records its donated positions (unioning both arms of the
trainers' ``(0, 1, 2) if donate else ()`` conditional); at each call of
that binding, a plain-Name or ``self.X`` argument in a donated position
must not be loaded again in the enclosing function until rebound.
"""

from __future__ import annotations

import ast

from tools.dttlint.core import Finding, Repo, Rule
from tools.dttlint.rules.common import ScopeIndex, dotted, int_tuple, self_attr


def _donated_positions(call: ast.Call) -> set[int] | None:
    """Donated argnums for a jit-ish call, or None when not donating."""
    name = dotted(call.func) or ""
    donate_expr: ast.AST | None = None
    if name in ("jax.jit", "jit", "pjit", "jax.pjit") or name.endswith(".pjit"):
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                donate_expr = kw.value
    elif name.endswith("._jit_program"):
        # def _jit_program(self, fn, kind, donate) — donate is positional 3
        # at the call site (self bound), or the `donate` keyword.
        if len(call.args) >= 3:
            donate_expr = call.args[2]
        for kw in call.keywords:
            if kw.arg == "donate":
                donate_expr = kw.value
    if donate_expr is None:
        return None
    positions = int_tuple(donate_expr)
    if positions is None:
        # Unresolvable donate expression: assume the convention (leading
        # buffer operand) rather than staying silent.
        return {0}
    return positions or None


def _expr_key(node: ast.AST) -> str | None:
    """Stable key for 'the same storage': bare Name or self.X."""
    if isinstance(node, ast.Name):
        return node.id
    attr = self_attr(node)
    if attr is not None:
        return f"self.{attr}"
    return None


def _assign_targets(stmt: ast.stmt) -> set[str]:
    """Keys rebound by ``stmt`` (tuple targets included)."""
    out: set[str] = set()
    targets: list[ast.AST] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.For):
        targets = [stmt.target]
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                k = _expr_key(e)
                if k:
                    out.add(k)
        else:
            k = _expr_key(t)
            if k:
                out.add(k)
    return out


def _loads_of(stmt: ast.AST, key: str, skip: ast.AST | None = None):
    """Load-context uses of ``key`` in ``stmt`` (skipping subtree ``skip``)."""
    stack = [stmt]
    while stack:
        node = stack.pop()
        if node is skip:
            continue
        k = _expr_key(node)
        if k == key and isinstance(getattr(node, "ctx", None), ast.Load):
            yield node
            continue  # self.X's inner Name load is the same use
        stack.extend(ast.iter_child_nodes(node))


class DonationRule(Rule):
    id = "donation"
    doc = "an argument at a donate_argnums position is never read after the call"

    def run(self, repo: Repo) -> list[Finding]:
        out: list[Finding] = []
        for sf in repo.modules():
            if sf.path.startswith("tests/"):
                continue
            out.extend(self._run_module(sf))
        return out

    def _run_module(self, sf) -> list[Finding]:
        index = ScopeIndex(sf.tree)
        # binding key ("name" or "self.attr" or "._attr" method-style) →
        # donated positions.
        donating: dict[str, set[int]] = {}
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Assign) or not isinstance(node.value, ast.Call):
                continue
            pos = _donated_positions(node.value)
            if pos is None:
                continue
            for t in node.targets:
                k = _expr_key(t)
                if k:
                    donating[k] = donating.get(k, set()) | pos
        # Conditional bindings: `self._spec = (self._jit_program(...) if c else None)`
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.IfExp):
                inner = node.value.body
                if isinstance(inner, ast.Call):
                    pos = _donated_positions(inner)
                    if pos:
                        for t in node.targets:
                            k = _expr_key(t)
                            if k:
                                donating[k] = donating.get(k, set()) | pos
        if not donating:
            return []

        out: list[Finding] = []
        for call in (n for n in ast.walk(sf.tree) if isinstance(n, ast.Call)):
            key = _expr_key(call.func)
            if key is None or key not in donating:
                continue
            for pos in donating[key]:
                if pos >= len(call.args):
                    continue
                arg_key = _expr_key(call.args[pos])
                if arg_key is None:
                    continue
                out.extend(self._check_after(sf, index, call, arg_key, key, pos))
        return out

    def _check_after(self, sf, index: ScopeIndex, call: ast.Call,
                     arg_key: str, fn_key: str, pos: int) -> list[Finding]:
        encl = next(index.enclosing_defs(call), None)
        if encl is None:
            return []
        # The statement containing the call, and its statement list.
        stmt_list, idx = self._locate(encl, call)
        if stmt_list is None:
            return []
        stmt = stmt_list[idx]
        # Rebind-by-result: `x = fn(x, ...)` / `self.a = fn(self.a, ...)`
        # is the sanctioned idiom — the donated key dies at this statement.
        if arg_key in _assign_targets(stmt):
            return []
        for later in stmt_list[idx + 1:]:
            hits = list(_loads_of(later, arg_key))
            if hits:
                return [Finding(
                    self.id, sf.path, hits[0].lineno,
                    f"{arg_key!r} is read after being donated at position "
                    f"{pos} of {fn_key}() (line {call.lineno}) — the buffer "
                    "is freed/aliased by XLA after that call",
                )]
            if arg_key in _assign_targets(later):
                break
        return []

    @staticmethod
    def _locate(encl: ast.AST, call: ast.Call):
        """(statement list, index) of the statement holding ``call``."""
        for node in ast.walk(encl):
            for fname in ("body", "orelse", "finalbody"):
                block = getattr(node, fname, None)
                if not isinstance(block, list):
                    continue
                for i, stmt in enumerate(block):
                    if not isinstance(stmt, ast.stmt):
                        continue
                    if any(n is call for n in ast.walk(stmt)):
                        # Descend: prefer the innermost statement list.
                        inner = DonationRule._locate(stmt, call)
                        if inner[0] is not None and inner[0] is not block:
                            return inner
                        return block, i
        return None, -1
