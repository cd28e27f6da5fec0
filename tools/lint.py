#!/usr/bin/env python
"""Minimal lint for `make verify` (reference `make verify` runs
gofmt/goimports/golint, Makefile:13-17; no Python linter is installed in
this image, so this is a stdlib AST pass).

Checks, per file:
- unused imports (the bound name never appears again in the file),
- duplicate imports of the same binding,
- `from x import *` (hides the above),
- syntax errors (ast.parse).

A `# noqa` comment on the import line suppresses it. Exit 1 with
file:line findings; 0 when clean.
"""

import ast
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TARGETS = ("kube_batch_tpu", "tests", "tools", "bench.py",
           "chip_smoke.py", "__graft_entry__.py")


def iter_py_files():
    for target in TARGETS:
        path = os.path.join(REPO, target)
        if os.path.isfile(path):
            yield path
            continue
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def check_file(path):
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [(e.lineno or 0, f"syntax error: {e.msg}")]

    lines = src.splitlines()
    findings = []
    bound = {}  # name -> (lineno, statement source line)

    # Module-level imports only (plus one level of top-level if/try, for
    # TYPE_CHECKING / fallback-import idioms): function-scoped lazy
    # imports legitimately repeat names and vanish from module scope.
    # Package __init__.py files are re-export surfaces — skip their
    # unused check entirely.
    is_init = os.path.basename(path) == "__init__.py"
    top = list(tree.body)
    for node in tree.body:
        if isinstance(node, (ast.If, ast.Try)):
            top.extend(getattr(node, "body", []))
            top.extend(getattr(node, "orelse", []))
            for h in getattr(node, "handlers", []):
                top.extend(h.body)

    for node in top:
        if not isinstance(node, (ast.Import, ast.ImportFrom)):
            continue
        if isinstance(node, ast.ImportFrom) and node.module == "__future__":
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if "noqa" in line:
            continue
        for alias in node.names:
            if alias.name == "*":
                findings.append(
                    (node.lineno, "star import hides unused names")
                )
                continue
            name = alias.asname or alias.name.split(".")[0]
            if name in bound and bound[name][0] != node.lineno:
                findings.append(
                    (node.lineno,
                     f"duplicate import of {name!r} "
                     f"(first at line {bound[name][0]})")
                )
            bound[name] = (node.lineno, node)
    if is_init:
        bound = {}

    for name, (lineno, node) in bound.items():
        # Token-level usage scan over everything except the import
        # statement itself (strings count: keeps annotations/doctests
        # from being flagged; comments count too — this lint prefers
        # false negatives over false positives).
        pattern = re.compile(rf"\b{re.escape(name)}\b")
        used = False
        for i, line in enumerate(lines, start=1):
            if node.lineno <= i <= getattr(node, "end_lineno", node.lineno):
                continue
            if pattern.search(line):
                used = True
                break
        if not used:
            findings.append((lineno, f"unused import: {name!r}"))
    return findings


def main():
    total = 0
    for path in sorted(iter_py_files()):
        for lineno, msg in sorted(check_file(path)):
            rel = os.path.relpath(path, REPO)
            print(f"{rel}:{lineno}: {msg}")
            total += 1
    if total:
        print(f"lint: {total} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
