"""Dependency-free fallback for ``ruff check`` (stdlib ast only).

The container images this repo targets do not ship ruff/pyflakes and
installing packages is off-limits, so ``tools/check.sh`` falls back to
this checker when ``ruff`` is absent.  It implements the highest-signal
subset of the configured ``[tool.ruff]`` rules:

  * E999  syntax errors (everything must parse)
  * F401  unused imports (module scope; ``__init__.py`` facades and
          ``# noqa`` lines exempt, matching the pyproject config)
  * F811  import redefinition at module scope
  * F632  ``is`` comparisons against str/int literals

It intentionally implements NO undefined-name analysis (F821 needs real
scope resolution; false positives would make the gate ignorable).  When
ruff is available it takes precedence and this file is not consulted.

Usage: python tools/pyflakes_lite.py [paths...]   (exit 1 on findings)
"""
from __future__ import annotations

import ast
import os
import sys

DEFAULT_PATHS = ("elemental_tpu", "perf", "examples", "tests", "tools")


def _py_files(paths):
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, _dirs, files in os.walk(p):
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def _noqa_lines(src: str) -> set:
    return {i + 1 for i, line in enumerate(src.splitlines())
            if "# noqa" in line}


class _ImportVisitor(ast.NodeVisitor):
    """Module-scope imports + every name/attribute-root used anywhere."""

    def __init__(self):
        self.imports: dict = {}        # name -> (lineno, display)
        self.used: set = set()
        self._depth = 0

    def visit_Import(self, node):
        if self._depth == 0:
            for a in node.names:
                name = (a.asname or a.name).split(".")[0]
                self.imports[name] = (node.lineno, a.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node):
        if self._depth == 0 and node.module != "__future__":
            for a in node.names:
                if a.name == "*":
                    continue
                name = a.asname or a.name
                disp = f"{node.module or '.'}.{a.name}"
                self.imports[name] = (node.lineno, disp)
        self.generic_visit(node)

    def _scoped(self, node):
        self._depth += 1
        self.generic_visit(node)
        self._depth -= 1

    visit_FunctionDef = visit_AsyncFunctionDef = visit_ClassDef = _scoped

    def visit_Name(self, node):
        if isinstance(node.ctx, (ast.Load, ast.Del)):
            self.used.add(node.id)
        self.generic_visit(node)

    def visit_Attribute(self, node):
        self.generic_visit(node)


def check_file(path: str) -> list:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [(path, e.lineno or 0, "E999", f"syntax error: {e.msg}")]
    findings = []
    noqa = _noqa_lines(src)
    base = os.path.basename(path)

    # F811: module-scope import redefinition
    seen: dict = {}
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                if a.name == "*":
                    continue
                name = a.asname or a.name.split(".")[0]
                if name in seen and node.lineno not in noqa:
                    findings.append((path, node.lineno, "F811",
                                     f"redefinition of {name!r} "
                                     f"(first at line {seen[name]})"))
                seen[name] = node.lineno

    # F401: unused module-scope imports (skip package facades)
    if base != "__init__.py":
        v = _ImportVisitor()
        v.visit(tree)
        exported = set()
        for node in tree.body:
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Name) and t.id == "__all__" \
                            and isinstance(node.value, (ast.List, ast.Tuple)):
                        for elt in node.value.elts:
                            if isinstance(elt, ast.Constant):
                                exported.add(str(elt.value))
        for name, (lineno, disp) in v.imports.items():
            if name.startswith("_") or name in exported:
                continue
            if name not in v.used and lineno not in noqa:
                findings.append((path, lineno, "F401",
                                 f"{disp!r} imported but unused"))

    # F632: `is` against literals
    for node in ast.walk(tree):
        if isinstance(node, ast.Compare) and node.lineno not in noqa:
            for op, cmp_ in zip(node.ops, node.comparators):
                if isinstance(op, (ast.Is, ast.IsNot)) and \
                        isinstance(cmp_, ast.Constant) and \
                        type(cmp_.value) in (str, int, bytes):
                    findings.append((path, node.lineno, "F632",
                                     "use ==/!= to compare with literals"))
    return findings


def main(argv=None) -> int:
    paths = (argv or sys.argv[1:]) or list(DEFAULT_PATHS)
    findings = []
    for path in _py_files(paths):
        findings.extend(check_file(path))
    for path, lineno, code, msg in findings:
        print(f"{path}:{lineno}: {code} {msg}")
    print(f"{len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
