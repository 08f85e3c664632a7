"""Lint tier: framework code must not use bare ``print()``.

Everything under ``autodist_tpu/`` logs through ``utils.logging`` (level
control, pid tagging, file sidecar) or records through the observability
layer — a bare ``print`` bypasses all of it and, on multi-host jobs,
interleaves uselessly across workers.  AST-based so prints inside string
literals (embedded subprocess scripts) don't false-positive, and so a
``# noqa``-style comment can't silently disable it.
"""
import ast
import pathlib

PKG = pathlib.Path(__file__).resolve().parent.parent / "autodist_tpu"


def test_no_bare_print_in_framework_code():
    assert PKG.is_dir(), PKG
    offenders = []
    for path in sorted(PKG.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"):
                offenders.append(
                    f"{path.relative_to(PKG.parent)}:{node.lineno}")
    assert not offenders, (
        "bare print() in framework code — use autodist_tpu.utils.logging "
        "or observability.record_event instead: " + ", ".join(offenders))
