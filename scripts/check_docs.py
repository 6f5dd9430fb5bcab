#!/usr/bin/env python
"""Docs consistency check: code references in docs/*.md must resolve.

Scans every fenced code block and inline code span in ``docs/*.md`` (and
README.md), plus the *module docstrings* of ``examples/*.py`` and
``benchmarks/*.py`` (they are user-facing documentation too), for

* module paths (``repro.sweep.runner``, ``repro.dist.sharding.foo`` —
  attribute tails are stripped by retrying shorter prefixes), and
* repo file paths (``src/repro/sweep/spec.py``, ``scripts/ci.sh``, ...)

and fails listing every reference that does not resolve to a real file
under the repo.  Keeps the docs layer honest as modules move: CI runs
this after the test suite (see ``scripts/ci.sh``).
"""

from __future__ import annotations

import ast
import glob
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

FENCE_RE = re.compile(r"```.*?```", re.S)
INLINE_RE = re.compile(r"`([^`\n]+)`")
MODULE_RE = re.compile(r"\brepro(?:\.\w+)+")
PATH_RE = re.compile(
    r"\b(?:src|docs|scripts|tests|benchmarks|results|examples)"
    r"/[\w./-]+\.(?:py|md|sh|json|toml)\b")


def code_regions(text: str):
    for m in FENCE_RE.finditer(text):
        yield m.group(0)
    without_fences = FENCE_RE.sub("", text)
    for m in INLINE_RE.finditer(without_fences):
        yield m.group(1)


def module_resolves(dotted: str) -> bool:
    """True if some prefix of ``dotted`` maps to a file under src/."""
    parts = dotted.split(".")
    while parts:
        rel = os.path.join(SRC, *parts)
        if os.path.isfile(rel + ".py") or \
                os.path.isfile(os.path.join(rel, "__init__.py")):
            return True
        parts = parts[:-1]
    return False


def module_docstring(path: str) -> str:
    """A script's module docstring, or "" when absent/unparseable."""
    with open(path) as f:
        try:
            tree = ast.parse(f.read())
        except SyntaxError:
            return ""
    return ast.get_docstring(tree) or ""


def main() -> int:
    docs = sorted(glob.glob(os.path.join(REPO, "docs", "*.md")))
    docs.append(os.path.join(REPO, "README.md"))
    scripts = sorted(glob.glob(os.path.join(REPO, "examples", "*.py"))
                     + glob.glob(os.path.join(REPO, "benchmarks", "*.py")))
    bad: list[tuple[str, str]] = []
    n_refs = 0

    def scan(rel: str, region: str) -> None:
        nonlocal n_refs
        for mod in MODULE_RE.findall(region):
            n_refs += 1
            if not module_resolves(mod):
                bad.append((rel, mod))
        for p in PATH_RE.findall(region):
            if "*" in p:
                continue  # glob examples
            n_refs += 1
            if not os.path.isfile(os.path.join(REPO, p)):
                bad.append((rel, p))

    for path in docs:
        with open(path) as f:
            text = f.read()
        rel = os.path.relpath(path, REPO)
        for region in code_regions(text):
            scan(rel, region)
    for path in scripts:
        # Module docstrings are documentation: references must resolve
        # the same way doc-file references do.
        scan(os.path.relpath(path, REPO), module_docstring(path))

    if bad:
        print("unresolved doc references:")
        for doc, ref in sorted(set(bad)):
            print(f"  {doc}: {ref}")
        return 1
    print(f"docs check OK ({n_refs} code references across "
          f"{len(docs)} doc files + {len(scripts)} script docstrings "
          f"resolve)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
