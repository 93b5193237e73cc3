#!/usr/bin/env python
"""Static check: every Pallas kernel entry point has an
interpret-mode oracle test AND a compile-for-the-TPU test.

The repo-wide testing convention (docs/testing.md, PR 3 onward): a
Pallas kernel never ships on trust — some tier-1 test runs it under
``interpret=True`` (or the module's ``force_interpret()`` hook) and
pins it against a pure-XLA reference, bitwise or tolerance-matched.
The convention only protects kernels it actually covers, and nothing
structural used to enforce that: a new kernel module with no oracle
test would pass tier-1 silently and fail first on hardware, where a
miscompiled kernel is a wrong-NUMBERS bug, not a crash.

This linter closes the gap. It AST-parses ``distkeras_tpu/ops/*.py``
and finds every KERNEL ENTRY POINT — a public top-level function that
transitively (through same-module helpers) reaches a
``pl.pallas_call`` — then requires, for each, at least one
``tests/test_*.py`` that references the entry point by name AND
exercises interpreter mode (mentions ``interpret``; the
``force_interpret`` context managers and ``interpret=True`` kwargs
both match). A justified exception carries the marker comment
``lint: allow-no-oracle`` on the ``def`` line.

Interpreter mode cannot see what the chip's compiler refuses (two
kernels passed every oracle for twenty PRs and had never lowered
through Mosaic), so each entry point must ALSO be named in
``tests/test_tpu_compile.py`` — the one file that compiles the kernels
at real widths for a described TPU. That requirement has no exemption
mark.

Exit status 1 when findings exist (wired into tier-1 as
``tests/test_lint_kernel_oracles.py``).
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path
from typing import Dict, List, Set, Tuple

ALLOW_MARK = "lint: allow-no-oracle"

#: where kernels live and where their oracles live, repo-relative
OPS_DIR = "distkeras_tpu/ops"
TESTS_DIR = "tests"
#: the compile-only file (ONE file: its topology fixture must run in
#: one xdist worker)
COMPILE_TEST = "tests/test_tpu_compile.py"

Finding = Tuple[str, int, str]


def _calls_in(fn: ast.AST) -> Tuple[bool, Set[str]]:
    """(has a direct pallas_call, names the function refers to).
    Every name counts, not only a called one: a kernel wrapper handed
    to ``functools.partial`` or ``shard_map`` is reached all the same."""
    direct = False
    names: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "pallas_call":
            direct = True
    return direct, names


def kernel_entry_points(src: str, rel: str) -> List[Tuple[str, int]]:
    """Public top-level functions of one module that transitively
    reach a ``pallas_call`` — ``(name, lineno)`` pairs. A private
    helper holding the actual ``pl.pallas_call`` (the ``_kernel`` /
    wrapper split every kernel module uses) attributes to whichever
    public function calls it."""
    tree = ast.parse(src, filename=rel)
    fns: Dict[str, ast.AST] = {
        n.name: n for n in tree.body
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    direct: Set[str] = set()
    edges: Dict[str, Set[str]] = {}
    for name, fn in fns.items():
        d, called = _calls_in(fn)
        if d:
            direct.add(name)
        edges[name] = called & set(fns)
    # transitive closure to the direct set
    reaches = set(direct)
    changed = True
    while changed:
        changed = False
        for name, called in edges.items():
            if name not in reaches and called & reaches:
                reaches.add(name)
                changed = True
    return sorted((n, fns[n].lineno) for n in reaches
                  if not n.startswith("_"))


def _exempt(src_lines: List[str], lineno: int) -> bool:
    line = src_lines[lineno - 1] if 0 < lineno <= len(src_lines) else ""
    return ALLOW_MARK in line


def check_tree(root: Path) -> List[Finding]:
    """Every kernel entry point across ``ops/`` without an
    interpret-mode oracle test referencing it by name, or not named in
    the compile-only file."""
    test_texts: Dict[str, str] = {
        str(p.relative_to(root)): p.read_text()
        for p in sorted((root / TESTS_DIR).glob("test_*.py"))}
    compile_text = test_texts.get(COMPILE_TEST, "")
    findings: List[Finding] = []
    for mod in sorted((root / OPS_DIR).glob("*.py")):
        rel = str(mod.relative_to(root))
        src = mod.read_text()
        try:
            entries = kernel_entry_points(src, rel)
        except SyntaxError as e:
            findings.append((rel, e.lineno or 0,
                             f"syntax error: {e.msg}"))
            continue
        lines = src.splitlines()
        for name, lineno in entries:
            pat = re.compile(rf"\b{re.escape(name)}\b")
            if not pat.search(compile_text):
                findings.append((
                    rel, lineno,
                    f"kernel entry point '{name}' is not compiled for "
                    f"the TPU ({COMPILE_TEST} does not name it)"))
            if _exempt(lines, lineno):
                continue
            covered = any(
                pat.search(text) and "interpret" in text
                for text in test_texts.values())
            if not covered:
                findings.append((
                    rel, lineno,
                    f"kernel entry point '{name}' has no interpret-"
                    f"mode oracle test (no tests/test_*.py references "
                    f"it in a file exercising interpreter mode)"))
    return findings


def main(argv=None) -> int:
    root = Path(argv[0]) if argv else Path(__file__).resolve().parent.parent
    findings = check_tree(root)
    for rel, lineno, msg in findings:
        print(f"{rel}:{lineno}: {msg}")
    if findings:
        print(f"{len(findings)} kernel-oracle finding(s); add an "
              f"interpret-mode test pinning the kernel against its "
              f"XLA reference (or mark the def line with "
              f"'# {ALLOW_MARK}'), and a compile case in "
              f"{COMPILE_TEST}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
