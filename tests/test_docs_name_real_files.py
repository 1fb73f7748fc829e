"""The documents name files that exist.

A document that points a reader at a deleted file costs every later
reader a search.  One case per document: every backticked token that
looks like a path of this repo names a file (or directory) that is
there.  No waiver list: a document that names a file that went is
corrected.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "designs", "*.md"))
)

_PATH_PREFIXES = (
    "elasticdl_tpu/",
    "perf/",
    "tests/",
    "scripts/",
    "benchmarks/",
    "docs/",
)
# a bare ``name.py`` (a root script such as ``chip_smoke.py``, or a module
# the surrounding sentence places) names a file somewhere in the repo
_BARE_SCRIPT = re.compile(r"^\w+\.py$")
_BACKTICKED = re.compile(r"`([^`\n]+)`")
# ``path/file.py::name``, ``path/file.py:12`` and ``path/file.py:12-40``
_SUFFIX = re.compile(r"(::[\w.]+|:\d+(-\d+)?)+$")


def named_paths(text: str):
    for token in _BACKTICKED.findall(text):
        # a command line names its script: `python perf/run.py --seed 1`
        for word in token.split():
            if any(c in word for c in "*<{"):
                continue
            path = _SUFFIX.sub("", word).rstrip(".,;:)")
            if path.startswith(_PATH_PREFIXES) or _BARE_SCRIPT.match(path):
                yield path


def repo_basenames() -> set:
    names = set(os.listdir(ROOT))
    for prefix in _PATH_PREFIXES:
        for _dir, _subdirs, files in os.walk(os.path.join(ROOT, prefix)):
            names.update(files)
    return names


def exists(path: str, basenames: set) -> bool:
    if "/" in path:
        return os.path.exists(os.path.join(ROOT, path))
    return path in basenames


def test_the_reader_finds_paths():
    text = (
        "`perf/run.py::main` and `python3 chip_smoke.py --size tiny`, "
        "`tests/test_a.py:12-40`, `gone.py`, `tests/<name>.py`, "
        "`worker/worker.py`"
    )
    assert list(named_paths(text)) == [
        "perf/run.py",
        "chip_smoke.py",
        "tests/test_a.py",
        "gone.py",
    ]
    basenames = repo_basenames()
    assert exists("chip_smoke.py", basenames)
    assert exists("worker.py", basenames)  # elasticdl_tpu/worker/worker.py
    assert exists("perf/run.py", basenames)
    assert not exists("gone.py", basenames)
    assert not exists("perf/gone.py", basenames)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_real_files(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    basenames = repo_basenames()
    missing = sorted(
        {path for path in named_paths(text) if not exists(path, basenames)}
    )
    assert not missing, f"{document} names files that do not exist: {missing}"
