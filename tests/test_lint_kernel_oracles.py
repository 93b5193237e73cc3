"""tools/lint_kernel_oracles.py wired into tier-1: every Pallas kernel
entry point in ``ops/`` must carry an interpret-mode oracle test (the
docs/testing.md convention) and a compile case in
``tests/test_tpu_compile.py``, and the checker itself must detect the
gaps it claims to — negative injection below builds a synthetic repo
with an uncovered kernel and asserts the finding fires."""

import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "tools"))

from lint_kernel_oracles import (  # noqa: E402
    ALLOW_MARK, check_tree, kernel_entry_points)

KERNEL_MOD = textwrap.dedent("""
    from jax.experimental import pallas as pl

    def _kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def _launch(x):
        return pl.pallas_call(_kernel, out_shape=x)(x)

    def covered_op(x):
        return _launch(x)

    def naked_op(x):
        return _launch(x)

    def helper_without_kernel(n):
        return n % 128 == 0
""")


def _fake_repo(tmp_path, test_body,
               compile_body="covered_op(x); naked_op(x)"):
    ops = tmp_path / "distkeras_tpu" / "ops"
    ops.mkdir(parents=True)
    (ops / "newkernel.py").write_text(KERNEL_MOD)
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_newkernel.py").write_text(test_body)
    if compile_body is not None:
        (tests / "test_tpu_compile.py").write_text(compile_body)
    return tmp_path


def test_repo_kernels_all_have_interpret_oracles():
    findings = check_tree(REPO)
    assert not findings, "\n".join(
        f"{f}:{ln}: {msg}" for f, ln, msg in findings)


def test_entry_points_are_transitive_and_public_only():
    entries = [n for n, _ in kernel_entry_points(KERNEL_MOD, "m.py")]
    # covered_op/naked_op reach pallas_call through _launch; the
    # private helpers and the kernel-free public helper do not appear
    assert entries == ["covered_op", "naked_op"]


def test_negative_injection_uncovered_kernel_is_flagged(tmp_path):
    """A kernel module whose entry point no test names in an
    interpret-exercising file must produce a finding."""
    root = _fake_repo(tmp_path, textwrap.dedent("""
        from distkeras_tpu.ops.newkernel import covered_op

        def test_oracle():
            with force_interpret():
                covered_op(x)
    """))
    findings = check_tree(root)
    assert len(findings) == 1
    assert findings[0][2].startswith("kernel entry point 'naked_op'")


ORACLE_BOTH = textwrap.dedent("""
    def test_oracle():
        with force_interpret():
            covered_op(x); naked_op(x)
""")


@pytest.mark.parametrize("compile_body,missing", [
    ("covered_op(x)", {"naked_op"}),          # one kernel left out
    (None, {"covered_op", "naked_op"}),       # the file itself is gone
])
def test_kernel_missing_from_tpu_compile_file_is_flagged(
        tmp_path, compile_body, missing):
    """An interpret-mode oracle is not enough: a kernel the compile-
    only file does not name is a finding, and no mark exempts it."""
    root = _fake_repo(tmp_path, ORACLE_BOTH, compile_body)
    mod = root / "distkeras_tpu" / "ops" / "newkernel.py"
    mod.write_text(KERNEL_MOD.replace(
        "def naked_op(x):", f"def naked_op(x):  # {ALLOW_MARK}: no"))
    findings = check_tree(root)
    assert {f[2].split("'")[1] for f in findings} == missing
    assert all("not compiled for the TPU" in f[2] for f in findings)


def test_kernel_reached_through_partial_is_an_entry_point():
    """A wrapper handed to ``functools.partial`` (how
    ``flash_attention`` reaches ``_flash`` since the shard_map wrap)
    still makes its caller an entry point."""
    src = KERNEL_MOD + textwrap.dedent("""
        def partial_op(x):
            import functools
            return functools.partial(_launch)(x)
    """)
    assert "partial_op" in [n for n, _ in kernel_entry_points(src, "m.py")]


def test_name_mention_without_interpret_does_not_count(tmp_path):
    """Referencing the kernel in a test that never runs interpreter
    mode is not an oracle — both entries flag."""
    root = _fake_repo(tmp_path, textwrap.dedent("""
        from distkeras_tpu.ops.newkernel import covered_op, naked_op

        def test_shapes_only():
            assert covered_op is not naked_op
    """))
    assert {f[2].split("'")[1] for f in check_tree(root)} == \
        {"covered_op", "naked_op"}


def test_allow_mark_exempts_the_def_line(tmp_path):
    root = _fake_repo(tmp_path, "")
    mod = root / "distkeras_tpu" / "ops" / "newkernel.py"
    mod.write_text(KERNEL_MOD.replace(
        "def covered_op(x):",
        f"def covered_op(x):  # {ALLOW_MARK}: oracle rides on naked_op"
    ).replace(
        "def naked_op(x):",
        f"def naked_op(x):  # {ALLOW_MARK}: synthetic"))
    assert check_tree(root) == []


def test_syntax_error_is_its_own_finding(tmp_path):
    root = _fake_repo(tmp_path, "")
    (root / "distkeras_tpu" / "ops" / "broken.py").write_text(
        "def broken(:\n")
    findings = check_tree(root)
    assert any("syntax" in msg for _, _, msg in findings)
