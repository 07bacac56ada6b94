"""The documents name only what exists.

Every backticked token of ``README.md``, ``ADVICE.md``, the verify skill
and the docstrings under ``elemental_tpu/`` that looks like a file of this
repository (``*.py``, ``*.sh``, ``<dir>/*.json``), and every ``python <file>.py``
or ``python -m <package>.<module>`` command in them, must resolve in the
tree.  A bare ``*.json`` is not checked: ``trace.json`` is as often what a
command writes as a file that is here.  ROADMAP D5 is the failure this
guards against: a gate on files that PR 25 deleted was described as live
for seven PRs.  ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are
histories and are not checked.
"""
import ast
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = re.compile(r"``([^`]+)``|`([^`]+)`")
_FILE = re.compile(r"^([\w.-]+(/[\w.-]+)*\.(py|sh)|[\w.-]+(/[\w.-]+)+\.json)$")
_COMMAND = re.compile(r"python3?\s+(?:-m\s+([\w.]+)|([\w./-]+\.py)\b)")


@pytest.fixture(scope="module")
def files():
    """The files of the checkout.  Hidden directories (``.git``, caches,
    scratch copies of another commit) and ``chiprun_out`` hold what a run
    leaves behind, never what git commits; ``.claude`` is the exception."""
    files = set()
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d == ".claude" or not (
            d.startswith(".") or d in ("chiprun_out", "__pycache__"))]
        rel = os.path.relpath(base, ROOT)
        files.update(os.path.normpath(os.path.join(rel, n)) for n in names)
    return files


def _resolves(token, files):
    """A path from the root, or the tail of one (``lapack/lu.py`` for
    ``elemental_tpu/lapack/lu.py``, a module's bare name beside it)."""
    token = os.path.normpath(token)
    return token in files or any(f.endswith("/" + token) for f in files)


def _dangling(text, files):
    out = []
    for span in _CODE.finditer(text):
        for word in (span.group(1) or span.group(2)).split():
            # tests/x.py::test_y, lapack/lu.py:91-94, a sentence's comma
            word = word.split(":")[0].strip("()[],;.'\"")
            if _FILE.match(word) and not _resolves(word, files):
                out.append(word)
    top = {f.split("/")[0] for f in files if "/" in f}
    for module, script in _COMMAND.findall(text):
        if script:
            if not script.startswith("/") and not _resolves(script, files):
                out.append(f"python {script}")
        elif module.split(".")[0] in top:          # not pytest, not jax
            path = module.replace(".", "/")
            if not (f"{path}.py" in files or f"{path}/__main__.py" in files):
                out.append(f"python -m {module}")
    return sorted(set(out))


def _docstrings(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            doc = ast.get_docstring(node, clean=False)
            if doc:
                yield doc


@pytest.mark.parametrize("document", [
    "README.md", "ADVICE.md", ".claude/skills/verify/SKILL.md"])
def test_a_document_names_only_files_that_exist(document, files):
    with open(os.path.join(ROOT, document)) as f:
        assert _dangling(f.read(), files) == []


def test_the_librarys_docstrings_name_only_files_that_exist(files):
    found = {}
    for f in sorted(files):
        if f.startswith("elemental_tpu/") and f.endswith(".py"):
            bad = [w for doc in _docstrings(os.path.join(ROOT, f))
                   for w in _dangling(doc, files)]
            if bad:
                found[f] = sorted(set(bad))
    assert found == {}


def test_the_check_itself_sees_a_file_that_went():
    files = {"perf/trace.py", "elemental_tpu/lapack/lu.py", "chip_smoke.py"}
    text = ("run `python gone.py` or ``python -m perf.gone lu``; see "
            "`tools/gone_too.py`, ``lapack/lu.py:91-94``, `chip_smoke.py`, "
            "``python -m perf.trace run --out trace.json``, `GONE_r*.json`, "
            "`python -m pytest tests/ -q`, `benchmark/configs/gone.json` "
            "and `workloads/<cell>.json`.")
    assert _dangling(text, files) == [
        "benchmark/configs/gone.json", "gone.py", "python -m perf.gone",
        "python gone.py", "tools/gone_too.py"]
