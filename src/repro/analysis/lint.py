"""AST lint for the repo's hot-path and API hygiene invariants.

Four rules, each born from a bug class this codebase has already paid for
(or been one review away from):

``public-assert``
    Public ``src/`` API paths must raise ``ValueError`` on bad input, not
    ``assert``: asserts vanish under ``python -O`` and read as internal
    invariants, not argument validation.  A function is *private* when any
    enclosing scope name starts with a single underscore (dunders are
    public).

``metric-name``
    Metric names are a cross-cutting namespace; dashboards and the drift
    monitor join on them.  Literal names passed to ``.counter`` /
    ``.gauge`` / ``.histogram`` must match ``repro.<subsystem>.<name>``
    (lowercase, dot-separated, at least three segments).

``hot-path-alloc``
    A span site in the serving and plan layers runs on every request even
    when nothing records, and its keyword args are evaluated before the
    span knows that.  So ``.span(...)`` there takes only free args
    (literals, names, attribute loads); anything computed (calls,
    f-strings, displays, arithmetic, subscripts, ``**`` unpacking) goes on
    the live span under ``if sp: sp.set(...)``.

``bare-except``
    Bare ``except:`` is forbidden everywhere.  Broad handlers
    (``except Exception``/``BaseException``) in the serving and obs
    layers must either carry ``# noqa: BLE001`` on the clause line (a
    reviewed, deliberate swallow) or re-raise with a bare ``raise``.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Iterable, List, Sequence

#: Directories (relative to the lint root) whose span sites sit on the
#: per-request path (rule ``hot-path-alloc``).
HOT_PATH_DIRS = ("serve", "plan")

#: Directories (relative to the lint root) whose broad excepts must be
#: explicitly reviewed (rule ``bare-except``, second half).
GUARDED_EXCEPT_DIRS = ("serve", "obs")

_METRIC_METHODS = frozenset({"counter", "gauge", "histogram"})
_METRIC_NAME_RE = re.compile(
    r"^repro\.[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)+$")


@dataclasses.dataclass(frozen=True)
class LintFinding:
    """One lint violation, pointing at a source line."""

    code: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.code}] {self.message}"


def _is_private_scope(scope_names: Sequence[str]) -> bool:
    """Private iff any enclosing function/class name is ``_name`` (single
    leading underscore); dunders like ``__init__`` count as public."""
    for name in scope_names:
        if name.startswith("_") and not (name.startswith("__")
                                         and name.endswith("__")):
            return True
    return False


def _is_free_arg(node: ast.expr) -> bool:
    """A literal, a name or an attribute load off one: nothing to compute
    before the span knows whether anything records."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return isinstance(node, (ast.Constant, ast.Name))


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, lines: Sequence[str], guarded: bool,
                 hot_path: bool):
        self.path = path
        self.lines = lines
        self.guarded = guarded  # broad-except review required (serve/obs)
        self.hot_path = hot_path  # span args must be free (serve/plan)
        self.scopes: List[str] = []
        self.findings: List[LintFinding] = []

    # -- scope tracking ----------------------------------------------------
    def _scoped(self, node) -> None:
        self.scopes.append(node.name)
        self.generic_visit(node)
        self.scopes.pop()

    visit_FunctionDef = visit_AsyncFunctionDef = visit_ClassDef = _scoped

    # -- rule: public-assert ----------------------------------------------
    def visit_Assert(self, node: ast.Assert) -> None:
        if not _is_private_scope(self.scopes):
            where = ".".join(self.scopes) or "<module>"
            self.findings.append(LintFinding(
                "public-assert", self.path, node.lineno,
                f"assert on public path {where}: raise ValueError instead "
                f"(asserts vanish under -O)"))
        self.generic_visit(node)

    # -- rules: metric-name, hot-path-alloc ---------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        if (self.hot_path and isinstance(node.func, ast.Attribute)
                and node.func.attr == "span"):
            for kw in node.keywords:
                if kw.arg is None or not _is_free_arg(kw.value):
                    what = (f"arg {kw.arg}=" if kw.arg else "**-unpacked args")
                    self.findings.append(LintFinding(
                        "hot-path-alloc", self.path, kw.value.lineno,
                        f"computed span {what} is evaluated even when "
                        f"nothing records; set it under 'if sp: sp.set(...)'"))
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _METRIC_METHODS and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            name = node.args[0].value
            if not _METRIC_NAME_RE.match(name):
                self.findings.append(LintFinding(
                    "metric-name", self.path, node.lineno,
                    f"metric name {name!r} does not match "
                    f"repro.<subsystem>.<name>"))
        self.generic_visit(node)

    # -- rule: bare-except -------------------------------------------------
    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self.findings.append(LintFinding(
                "bare-except", self.path, node.lineno,
                "bare 'except:' swallows KeyboardInterrupt/SystemExit; "
                "name the exception type"))
        elif self.guarded and self._is_broad(node.type):
            line = self.lines[node.lineno - 1] if (
                0 < node.lineno <= len(self.lines)) else ""
            noqa = "noqa" in line and "BLE001" in line
            reraises = any(isinstance(n, ast.Raise) and n.exc is None
                           for stmt in node.body for n in ast.walk(stmt))
            if not (noqa or reraises):
                self.findings.append(LintFinding(
                    "bare-except", self.path, node.lineno,
                    "broad except in a serving/obs hook must re-raise or "
                    "carry '# noqa: BLE001' with a justification"))
        self.generic_visit(node)

    @staticmethod
    def _is_broad(tp: ast.expr) -> bool:
        names = tp.elts if isinstance(tp, ast.Tuple) else [tp]
        return any(isinstance(n, ast.Name)
                   and n.id in ("Exception", "BaseException")
                   for n in names)


def lint_source(src: str, path: str = "<string>", *,
                guarded_except: bool = False,
                hot_path: bool = False) -> List[LintFinding]:
    """Lint one module's source text.  ``guarded_except`` applies the
    strict broad-except rule (serving/obs layers), ``hot_path`` the span-arg
    rule (serving/plan layers)."""
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [LintFinding("syntax-error", path, e.lineno or 0, str(e))]
    linter = _Linter(path, src.splitlines(), guarded_except, hot_path)
    linter.visit(tree)
    return sorted(linter.findings, key=lambda f: (f.path, f.line, f.code))


def _iter_py(paths: Iterable[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = sorted(d for d in dirs if not d.startswith("__"))
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def _under(path: str, dirs: Sequence[str]) -> bool:
    parts = os.path.normpath(path).split(os.sep)[:-1]
    return any(d in parts for d in dirs)


def lint_paths(paths: Iterable[str] | str) -> List[LintFinding]:
    """Lint every ``.py`` file under the given paths (files or dirs)."""
    if isinstance(paths, str):
        paths = [paths]
    findings: List[LintFinding] = []
    for path in _iter_py(paths):
        with open(path, "r") as f:
            src = f.read()
        findings.extend(lint_source(
            src, path, guarded_except=_under(path, GUARDED_EXCEPT_DIRS),
            hot_path=_under(path, HOT_PATH_DIRS)))
    return sorted(findings, key=lambda f: (f.path, f.line, f.code))
