"""Public API docstring presence (reference analog:
``coordination_test.py:15`` asserts the coordination surface is documented)."""

import fnmatch
import functools
import inspect
import os
import re

import pytest

import torchft_tpu


def test_public_exports_have_docstrings() -> None:
    undocumented = []
    for name in torchft_tpu.__all__:
        obj = getattr(torchft_tpu, name)
        if not (inspect.isclass(obj) or inspect.isfunction(obj)):
            continue
        if not (obj.__doc__ or "").strip():
            undocumented.append(name)
    assert not undocumented, f"missing docstrings: {undocumented}"


def test_coordination_surface_documented() -> None:
    from torchft_tpu import coordination

    for name in coordination.__all__:
        obj = getattr(coordination, name)
        if inspect.isclass(obj) or inspect.isfunction(obj):
            assert (obj.__doc__ or "").strip(), f"{name} undocumented"


def test_native_stub_covers_public_surface() -> None:
    """``native.pyi`` (the ``_torchft.pyi`` analog) must type every public
    class and its public methods, so the stub can't silently drift from
    the module."""
    import ast
    import os

    from torchft_tpu import native

    stub_path = os.path.join(os.path.dirname(native.__file__), "native.pyi")
    tree = ast.parse(open(stub_path).read())
    stub_names = set()
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            stub_names.add(node.name)
            if isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, ast.FunctionDef):
                        stub_names.add(f"{node.name}.{sub.name}")

    missing = []
    for name, obj in vars(native).items():
        if name.startswith("_") or not inspect.isclass(obj):
            continue
        if obj.__module__ != "torchft_tpu.native":
            continue
        if name not in stub_names:
            missing.append(name)
            continue
        for meth, fn in vars(obj).items():
            if meth.startswith("_"):
                continue
            if inspect.isfunction(fn) or isinstance(fn, property):
                if f"{name}.{meth}" not in stub_names:
                    missing.append(f"{name}.{meth}")
    for fname in ("available", "quantize_rowwise_native",
                  "dequantize_rowwise_native", "reduce_rowwise_native"):
        if fname not in stub_names:
            missing.append(fname)
    assert not missing, f"native.pyi missing: {missing}"


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCUMENTS = (
    "README.md",
    "docs/operations.md",
    "docs/analysis.md",
    "docs/assumptions.md",
    "docs/striped_heal.md",
    "docs/SCALE_REHEARSAL.md",
    ".claude/skills/verify/SKILL.md",
)
_FILE_SUFFIXES = (".py", ".md", ".json", ".jsonl", ".sh", ".h", ".cc", ".yaml", ".toml", ".pyi")
# what building, running and the chip tool leave in a checkout
_NOT_SOURCE = {
    ".git", "__pycache__", ".jax_cache", ".pytest_cache", ".hypothesis",
    "chiprun_out", "_checkout", "_parent", "_chip", "_v1", "_v2", "_v3", "out",
}


@functools.lru_cache(maxsize=None)
def _checkout_files() -> list:
    files = []
    for folder, dirs, names in os.walk(_ROOT):
        dirs[:] = [d for d in dirs if d not in _NOT_SOURCE]
        files += [os.path.relpath(os.path.join(folder, n), _ROOT) for n in names]
    return files


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_documents_name_files_that_exist(document: str) -> None:
    """Every file a document names in backticks is a file of the checkout:
    as written, or under some directory of it (``ling_hybrid.py`` for
    ``torchft_tpu/models/ling_hybrid.py``; ``*`` matches as in a shell).  A
    trailing ``:line`` is cut.  Not judged: what starts with ``/`` (a path
    outside the checkout, a URL's path), what holds a ``<placeholder>`` or a
    ``{field}`` (a name made at run time), and fenced blocks."""
    files = _checkout_files()
    with open(os.path.join(_ROOT, document), encoding="utf-8") as f:
        text = re.sub(r"^```.*?^```", "", f.read(), flags=re.S | re.M)
    missing = set()
    for span in re.findall(r"`([^`]+)`", text):
        for word in span.split():
            word = re.sub(r":\d+(-\d+)?$", "", word.rstrip(".,;:)"))
            if not word.endswith(_FILE_SUFFIXES) or word.startswith("/"):
                continue
            if any(c in word for c in "<>{}"):
                continue
            if not any(
                fnmatch.fnmatchcase(f, word) or fnmatch.fnmatchcase(f, "*/" + word)
                for f in files
            ):
                missing.add(word)
    assert not missing, f"{document} names files the checkout does not have: {sorted(missing)}"
