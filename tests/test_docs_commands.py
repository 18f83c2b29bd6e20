"""Documents name what is there: every repository path, every ``*.py``
file and every ``python -m horovod_tpu.<module>`` that ``README.md`` or a
``docs/*.md`` names has to exist.  One case per document, so a deletion
that leaves a command behind in a document fails by that document's name.
"""

from __future__ import annotations

import functools
import glob
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md"] + sorted(
    os.path.relpath(p, REPO_ROOT)
    for p in glob.glob(os.path.join(REPO_ROOT, "docs", "*.md")))

# Directories whose paths a document writes from the root of the repo.
TOP = ("scripts", "examples", "benchmark", "ci", "tests", "docs", "cpp",
       "horovod_tpu")
# ... and from the root of the package ("optim/overlap.py").
PACKAGE = "horovod_tpu"

_TOKEN = r"[\w.*-]+"
PATH_RE = re.compile(rf"(?<![\w/.<>-])((?:{_TOKEN}/)+(?:{_TOKEN})?)")
FILE_RE = re.compile(r"(?<![\w/.<>*-])(\w[\w-]*\.(?:py|sh))\b")
MODULE_RE = re.compile(r"python3? -m (horovod_tpu(?:\.\w+)*)")

# What a document may name that is not in this repository: the user's own
# program in an example command (also any ``your_*.py``), and the
# reference implementation's files (Horovod's source tree and its
# ``docs/*.rst``, which the documents cite by file and line).
PLACEHOLDERS = {"train.py", "script.py", "worker.py", "serve_job.py"}
REFERENCE_FILES = {
    "examples/pytorch_synthetic_benchmark.py",
    "mpi_ops.py",
    "gloo_run.py",
}


@functools.lru_cache(maxsize=None)
def _every_python_file():
    names = set()
    for top in TOP + (".",):
        base = os.path.join(REPO_ROOT, top)
        walk = os.walk(base) if top != "." else [(base, [], os.listdir(base))]
        for _, _, files in walk:
            names.update(f for f in files if f.endswith((".py", ".sh")))
    return frozenset(names)


def _exists(path: str) -> bool:
    """``path`` (possibly a glob, possibly ending in ``/``) from the root
    of the repository or of the package."""
    path = path.rstrip(".")
    for base in (REPO_ROOT, os.path.join(REPO_ROOT, PACKAGE)):
        if glob.glob(os.path.join(base, path.rstrip("/"))):
            return True
    return False


def _module_exists(dotted: str) -> bool:
    base = os.path.join(REPO_ROOT, *dotted.split("."))
    return os.path.isfile(base + ".py") or \
        os.path.isfile(os.path.join(base, "__main__.py"))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(REPO_ROOT, document)) as f:
        text = f.read()
    # URLs hold slashes and dots that are no path of this repository
    text = re.sub(r"https?://\S+", " ", text)
    python_files = _every_python_file()
    package_dirs = set(os.listdir(os.path.join(REPO_ROOT, PACKAGE)))
    missing = []
    for path in PATH_RE.findall(text):
        first = path.split("/", 1)[0]
        if path in REFERENCE_FILES or path.endswith(".rst"):
            continue
        if first in package_dirs and first not in TOP:
            # from the root of the package: files only ("serve/req/" is
            # a key of the KV store, "optim/overlap.step" a function)
            if not path.endswith(".py"):
                continue
        elif first not in TOP:
            continue  # not written as a path of this repository
        if not _exists(path):
            missing.append(path)
    for name in FILE_RE.findall(text):
        if name not in python_files | PLACEHOLDERS | REFERENCE_FILES \
                and not name.startswith("your_"):
            missing.append(name)
    for dotted in MODULE_RE.findall(text):
        if not _module_exists(dotted):
            missing.append("python -m " + dotted)
    assert not missing, (
        f"{document} names what the repository does not have: "
        f"{sorted(set(missing))}")
