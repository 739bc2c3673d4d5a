#!/usr/bin/env python3
"""Dependency-free lint gate (the reference runs gometalinter in `make
test`, test/test.make:53-56; this image ships no Python linter and installs
are off-limits, so the same checks run from the stdlib).

Checks: syntax (ast parse), unused imports, line length, tabs in
indentation, trailing whitespace, stray debugger calls. `# noqa` on a line
suppresses findings for that line. ruff.toml is committed too — `make lint`
prefers real ruff whenever the environment has it.
"""

from __future__ import annotations

import ast
from pathlib import Path

MAX_LINE = 100
ROOTS = ("oim_tpu", "tests", "scripts", "chip_smoke.py",
         "__graft_entry__.py")
EXCLUDE = {"oim_tpu/spec/oim_pb2.py"}  # generated
DEBUGGERS = ("breakpoint(", "pdb.set_trace(")  # noqa


def iter_files(repo: Path):
    for root in ROOTS:
        p = repo / root
        if p.is_file():
            yield p
        else:
            yield from sorted(p.rglob("*.py"))


def used_names(tree: ast.AST) -> set[str]:
    used: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            base = node
            while isinstance(base, ast.Attribute):
                base = base.value
            if isinstance(base, ast.Name):
                used.add(base.id)
        elif isinstance(node, ast.Assign):
            # __all__ re-export lists count as usage.
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    for elt in getattr(node.value, "elts", []):
                        if isinstance(elt, ast.Constant):
                            used.add(str(elt.value))
    return used


def unused_imports(tree: ast.AST, is_init: bool) -> list[tuple[int, str]]:
    if is_init:
        return []  # __init__ files import to re-export
    used = used_names(tree)
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                name = alias.asname or alias.name.split(".")[0]
                if name not in used:
                    out.append((node.lineno, f"unused import {alias.name!r}"))
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for alias in node.names:
                if alias.name == "*":
                    continue
                name = alias.asname or alias.name
                if name not in used:
                    out.append((node.lineno, f"unused import {alias.name!r}"))
    return out


def lint_file(path: Path, repo: Path) -> list[str]:
    rel = path.relative_to(repo).as_posix()
    if rel in EXCLUDE:
        return []
    src = path.read_text()
    problems: list[str] = []
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as err:
        return [f"{rel}:{err.lineno}: syntax error: {err.msg}"]
    findings = unused_imports(tree, path.name == "__init__.py")
    lines = src.splitlines()
    for lineno, line in enumerate(lines, 1):
        if line.rstrip() != line:
            findings.append((lineno, "trailing whitespace"))
        if line[:len(line) - len(line.lstrip())].count("\t"):
            findings.append((lineno, "tab indentation"))
        if len(line) > MAX_LINE:
            findings.append((lineno, f"line too long ({len(line)} > {MAX_LINE})"))
        for dbg in DEBUGGERS:
            if dbg in line and not line.lstrip().startswith("#"):
                findings.append((lineno, f"debugger call {dbg!r}"))
    for lineno, msg in sorted(findings):
        if lineno <= len(lines) and "# noqa" in lines[lineno - 1]:
            continue
        problems.append(f"{rel}:{lineno}: {msg}")
    return problems


def main() -> int:
    repo = Path(__file__).resolve().parent.parent
    problems = []
    n = 0
    for path in iter_files(repo):
        n += 1
        problems += lint_file(path, repo)
    for p in problems:
        print(p)
    print(f"lint: {n} files, {len(problems)} problem(s)")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
