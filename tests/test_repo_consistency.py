"""The tree agrees with itself: what CI runs, what the documents name and
what the package's comments cite all exist.  Pure file reads, no jax."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CI = ".github/workflows/ci.yml"
DOCUMENTS = ("README.md", "COMPONENTS.md", "docs/architecture.md",
             "docs/migrating.md")
# A path a document names under one of the tree's own directories, up to
# its last word character (so a sentence's full stop is not part of it).
_TREE_PATH = re.compile(
    r"(?<![\w/.-])((?:raft_stereo_tpu|benchmark|tools|scripts|tests)"
    r"/[\w./-]*\w/?)")
# Script names that are this repo's by their form alone, wherever they sit.
_OWN_SCRIPT = re.compile(r"(?<![\w/.-])(bench\w*\.py|\w+_smoke\.py)\b")
_DATED_RECORD = re.compile(r"_r\d+\.json")


def _read(relpath):
    with open(os.path.join(REPO, relpath), errors="replace") as f:
        return f.read()


def _ci_scripts():
    return sorted(set(re.findall(r"\bpython\s+([\w./-]+\.py)\b", _read(CI))))


@pytest.mark.parametrize("script", _ci_scripts())
def test_ci_step_runs_a_file_that_exists(script):
    """A deleted script cannot leave a dead ``python <path>`` step behind."""
    assert os.path.isfile(os.path.join(REPO, script)), script


@pytest.mark.parametrize("document", DOCUMENTS)
def test_documents_name_only_files_that_exist(document):
    """Every path a document names under the tree's own directories, and
    every ``bench*.py`` / ``*_smoke.py`` word, is a file or directory of
    the tree.  Placeholders (``<name>``), globs and brace lists are not
    paths; the reference's own files and the outputs a command writes are
    not matched by either pattern."""
    text = _read(document)
    missing = []
    for m in _TREE_PATH.finditer(text):
        rest = text[m.end():m.end() + 1]
        if rest in ("<", "*", "{"):        # benchmark/configs/<name>.json
            continue
        if not os.path.exists(os.path.join(REPO, m.group(1))):
            missing.append(m.group(1))
    for m in _OWN_SCRIPT.finditer(text):
        name = m.group(1)
        if not any(os.path.isfile(os.path.join(REPO, d, name))
                   for d in ("", "scripts", "tools")):
            missing.append(name)
    assert not missing, sorted(set(missing))


def test_package_names_no_dated_record():
    """No file of the package cites a ``*_rNN.json`` record: a constant's
    reason is stated where it stands, and a chip number lives in PERF.md."""
    hits = []
    for root, dirs, files in os.walk(os.path.join(REPO, "raft_stereo_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if name.endswith((".so", ".pyc")):
                continue
            path = os.path.join(root, name)
            with open(path, errors="replace") as f:
                if _DATED_RECORD.search(f.read()):
                    hits.append(os.path.relpath(path, REPO))
    assert not hits, hits
