#!/usr/bin/env python
"""Static check: no ad-hoc host syncs inside the epoch-loop modules.

The overlap PR (docs/overlap.md) made the trainer epoch loops
non-blocking: batches are staged onto device by the loader thread,
per-step loss/metric arrays stay on device until ONE epoch-boundary
fetch, and checkpoint snapshots fence through the manager's async-D2H
path. A stray ``jax.device_get`` / ``.block_until_ready()`` /
``float(<traced scalar>)`` dropped into one of these loops silently
reintroduces a per-step device round trip — the regression class this
linter pins down, the way ``lint_timing.py`` pins raw clock reads.

Scope is the LIBRARY EPOCH-LOOP MODULES only (``EPOCH_LOOP_MODULES``
below): the trainer loops this discipline governs. Everything else —
inference, serving, bench/driver code — fetches freely. Flags:

  * ``jax.device_get(...)`` calls (and ``from jax import device_get``
    alias imports);
  * ``.block_until_ready()`` method calls on anything;
  * ``float(x)`` where ``x`` is not a constant and contains no
    ``np``/``numpy`` reference — ``float(device_scalar)`` is an
    implicit blocking transfer, while ``float(np.mean(host))`` is
    host-side arithmetic (the heuristic). ``__init__`` bodies are
    exempt (constructor scalar coercions are not syncs).

Sanctioned fetch points — ``parallel.engine.host_fetch``/``host_async``
internals, the shared ``trainers.val_logs`` validation fetch, the
epoch-boundary fetches, callback-API ``get_weights`` providers and
end-of-train result fetches — carry the marker comment
``# lint: allow-host-sync`` on the offending line.

THE SERVING ITERATION LOOP (zero-bubble PR, docs/serving.md
§Zero-bubble loop) is the second blocking-sync-free zone: the
step/decode-path methods of ``serving/engine.py`` listed in
``SERVING_LOOP_FUNCS``. There the pipelined-dispatch contract is that
the device NEVER waits on per-iteration Python, so on top of the three
rules above, ``np.asarray(...)``/``np.array(...)`` — the fetch idiom
that used to sync every decode iteration — is banned too. Exactly ONE
marked site is sanctioned: the lagged fetch in ``_fetch()``; zero
marks (someone deleted the contract) or a second mark (someone snuck a
new sync past review) are both findings.

THE SPECULATION PATH (tree-speculation PR) is the third zone: the
draft propose/accept call graph of ``serving/speculation.py``
(``SPECULATION_LOOP_FUNCS`` — ``propose``/``propose_tree``, the
n-gram lookups, the tree builders) runs inside the synchronous
speculative iteration, so the three base rules apply there too;
``np.asarray`` stays allowed (the draft-model step's per-step fetch
is the sources' sanctioned medium).

Exit status 1 when findings exist (wired into tier-1 as
``tests/test_lint_host_sync.py``).
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path
from typing import List, Tuple

ALLOW_MARK = "lint: allow-host-sync"

#: the modules holding library epoch loops — the blocking-sync-free zone
EPOCH_LOOP_MODULES = (
    "distkeras_tpu/parallel/trainers.py",
    "distkeras_tpu/parallel/spmd.py",
    "distkeras_tpu/parallel/pipeline.py",
    "distkeras_tpu/parallel/distributed.py",
    "distkeras_tpu/parallel/engine.py",
)

#: the serving engine module whose iteration loop is the second zone
SERVING_LOOP_MODULE = "distkeras_tpu/serving/engine.py"

#: the step/decode-path methods forming the serving iteration loop.
#: Out of scope by design: submit/prefill intake (one-off per-request
#: work), ``_note_moe_route`` (the throttled stats tap — it reads
#: arrays of an already-consumed step on a 1-in-16 cadence), and the
#: out-of-band control surface (cancel, health, telemetry summaries).
SERVING_LOOP_FUNCS = frozenset({
    "step", "_advance_decode", "_spec_step", "_launch_step",
    "_process_step", "_flush_pending", "_flush_host_window", "_fetch",
    "_fuse_window", "_inflight", "_merge_keys", "_ensure_decode_pages",
    "_fragmentation", "_record_iteration", "_finish", "_admit",
    "_expire_deadlines",
    # tree speculation (tree-speculation PR): the tree draft/accept
    # call graph runs inside the iteration too
    "_spec_tree_step", "_tree_shape", "_adapt_tree", "_drop_swap",
    "_consume_spec",
    # block diffusion: the pipelined pass
    "_block_step", "_launch_pass", "_consume_pass", "_fresh_block",
})

#: how many ``# lint: allow-host-sync`` marks the serving loop may
#: carry: exactly one — the lagged fetch in ``_fetch()``
SERVING_ALLOWED_MARKS = 1

#: the draft-source module (tree-speculation PR): proposal and the
#: tree helpers run INSIDE the (synchronous) speculative iteration, so
#: the three base rules apply — a stray ``jax.device_get`` /
#: ``block_until_ready`` / ``float(<traced>)`` in the propose path is
#: a per-iteration sync regression. ``np.asarray`` stays ALLOWED here
#: (unlike the engine zone): the draft-model step's per-step fetch is
#: the sources' sanctioned medium — drafting is host-driven by design.
SPECULATION_MODULE = "distkeras_tpu/serving/speculation.py"
SPECULATION_LOOP_FUNCS = frozenset({
    "propose", "propose_tree", "lookup", "continuations", "_grow",
    "build_token_tree", "tree_ancestors", "_draft_steps", "_heal",
    "_context",
})

Finding = Tuple[str, int, str]


def _allowed(line: str) -> bool:
    return ALLOW_MARK in line


def _mentions_numpy(node: ast.AST) -> bool:
    """Does the expression reference ``np``/``numpy`` anywhere? Host-side
    arithmetic routes through numpy; a bare traced value does not."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in ("np", "numpy"):
            return True
    return False


def _init_ranges(tree: ast.AST) -> List[Tuple[int, int]]:
    return [(n.lineno, n.end_lineno or n.lineno)
            for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name == "__init__"]


def _func_ranges(tree: ast.AST, names) -> List[Tuple[int, int]]:
    return [(n.lineno, n.end_lineno or n.lineno)
            for n in ast.walk(tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and n.name in names]


def check_source(src: str, rel: str, only_funcs=None,
                 ban_np_fetch: bool = False,
                 allowed_marks: int = None) -> List[Finding]:
    """Findings for one file's source text. With ``only_funcs`` (a set
    of function names) only statements inside those functions are
    checked; ``ban_np_fetch`` adds the ``np.asarray``/``np.array`` rule
    (the serving-loop fetch idiom); ``allowed_marks`` asserts the exact
    number of ``# lint: allow-host-sync`` marks inside the scope."""
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:  # a broken file is its own finding
        return [(rel, e.lineno or 0, f"syntax error: {e.msg}")]
    lines = src.splitlines()
    inits = _init_ranges(tree)
    scope = (None if only_funcs is None
             else _func_ranges(tree, only_funcs))
    if scope is not None and not scope:
        # the zone evaporated (e.g. the loop methods were renamed
        # without updating the func set) — that is a finding, not a
        # silently-green empty scope
        return [(rel, 0,
                 "none of the scoped serving-loop functions "
                 f"({', '.join(sorted(only_funcs))}) exist in this "
                 "file — update the lint's function set so the zone "
                 "keeps covering the loop")]
    out: List[Finding] = []

    def line_of(node: ast.AST) -> str:
        ln = getattr(node, "lineno", 0)
        return lines[ln - 1] if 0 < ln <= len(lines) else ""

    def in_init(node: ast.AST) -> bool:
        ln = getattr(node, "lineno", 0)
        return any(lo <= ln <= hi for lo, hi in inits)

    def in_scope(node: ast.AST) -> bool:
        if scope is None:
            return True
        ln = getattr(node, "lineno", 0)
        return any(lo <= ln <= hi for lo, hi in scope)

    if allowed_marks is not None:
        n_marks = sum(
            1 for lo, hi in (scope or [(1, len(lines))])
            for ln in range(lo, hi + 1)
            if ln <= len(lines) and ALLOW_MARK in lines[ln - 1])
        if n_marks != allowed_marks:
            out.append((rel, 0,
                        f"{n_marks} '{ALLOW_MARK}' mark(s) in the "
                        f"serving loop scope, expected exactly "
                        f"{allowed_marks} (the _fetch lagged-fetch "
                        f"site) — a new sync needs a design review, "
                        f"not a marker"))

    for node in ast.walk(tree):
        if not in_scope(node):
            continue
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "device_get" \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id == "jax":
                if not _allowed(line_of(node)):
                    out.append((rel, node.lineno,
                                "jax.device_get() in an epoch-loop module "
                                "— route through host_fetch/the "
                                "epoch-boundary fetch, or mark the "
                                "sanctioned site"))
            elif isinstance(f, ast.Attribute) \
                    and f.attr == "block_until_ready":
                if not _allowed(line_of(node)):
                    out.append((rel, node.lineno,
                                ".block_until_ready() in an epoch-loop "
                                "module — a blocking device sync; let the "
                                "boundary fetch bound the epoch"))
            elif ban_np_fetch and isinstance(f, ast.Attribute) \
                    and f.attr in ("asarray", "array") \
                    and isinstance(f.value, ast.Name) \
                    and f.value.id in ("np", "numpy"):
                if not _allowed(line_of(node)):
                    out.append((rel, node.lineno,
                                f"np.{f.attr}() in the serving iteration "
                                "loop — the fetch idiom blocks the host "
                                "on the device here; consume tokens "
                                "through the lagged _fetch() or defer "
                                "the work to a host-window buffer"))
            elif isinstance(f, ast.Name) and f.id == "float" \
                    and node.args and not isinstance(node.args[0],
                                                     ast.Constant) \
                    and not _mentions_numpy(node.args[0]) \
                    and not in_init(node):
                if not _allowed(line_of(node)):
                    out.append((rel, node.lineno,
                                "float(<non-numpy value>) in an "
                                "epoch-loop module — on a traced/device "
                                "scalar this is an implicit blocking "
                                "transfer; fetch at the boundary (or go "
                                "through numpy) instead"))
        elif isinstance(node, ast.ImportFrom) and node.module == "jax":
            bad = [a.name for a in node.names if a.name == "device_get"]
            if bad and not _allowed(line_of(node)):
                out.append((rel, node.lineno,
                            "from jax import device_get — aliasing the "
                            "banned fetch; use host_fetch or a marked "
                            "site"))
    return sorted(out, key=lambda f: f[1])


def check_tree(root: Path) -> List[Finding]:
    findings: List[Finding] = []
    for entry in EPOCH_LOOP_MODULES:
        p = root / entry
        if p.exists():
            findings.extend(check_source(p.read_text(), entry))
    p = root / SERVING_LOOP_MODULE
    if p.exists():
        findings.extend(check_source(
            p.read_text(), SERVING_LOOP_MODULE,
            only_funcs=SERVING_LOOP_FUNCS, ban_np_fetch=True,
            allowed_marks=SERVING_ALLOWED_MARKS))
    p = root / SPECULATION_MODULE
    if p.exists():
        findings.extend(check_source(
            p.read_text(), SPECULATION_MODULE,
            only_funcs=SPECULATION_LOOP_FUNCS))
    return findings


def main(argv=None) -> int:
    root = Path(argv[0]) if argv else Path(__file__).resolve().parent.parent
    findings = check_tree(root)
    for rel, lineno, msg in findings:
        print(f"{rel}:{lineno}: {msg}")
    if findings:
        print(f"{len(findings)} host-sync finding(s); route through the "
              f"sanctioned fetch points or mark the line with "
              f"'# {ALLOW_MARK}'", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
