"""Documents name only files that exist.

The mechanical form of "the documents describe the system as it is": a
back-quoted token that reads as a path into the repo must exist, so a
later deletion that forgets a document fails here. Same style as
``tests/test_obs_docs.py`` and ``tests/test_wire_docs.py``: the document
is the claim, the checkout is the truth.

What reads as a path: a token (its first word, without a ``:line``,
``:function`` or ``::test`` suffix) with no glob, brace, ``<...>`` or
leading ``/``, that either has a ``/`` and a first segment that is an
entry of the checkout's root, of the package directory (documents write
``serving/engine.py`` for ``tensorflowonspark_tpu/serving/engine.py``) or
of the document's own directory, or has no ``/`` and ends in ``.py``: a
bare script name has to be some file's name. A module written without
its suffix (``tools/serve_model``) counts as its file. The reference
project's files are written with its own root
(``tensorflowonspark/TFCluster.py``), which is no entry here.
"""

import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "tensorflowonspark_tpu")

DOCUMENTS = (
    ["README.md"]
    + sorted(
        os.path.join("docs", f)
        for f in os.listdir(os.path.join(ROOT, "docs"))
        if f.endswith(".md")
    )
    + ["examples/README.md", ".claude/skills/verify/SKILL.md"]
)

_TOKEN = re.compile(r"`([^`\n]+)`")
_NOT_A_PATH = re.compile(r"[*?\[\]{}<>$=(),]|\.\.\.|^/|^~|^-")


def _ignored_dirs() -> set[str]:
    """Directory names ``.gitignore`` lists whole: what building, testing
    and running leave behind is not the repo."""
    with open(os.path.join(ROOT, ".gitignore"), encoding="utf-8") as f:
        return {
            line.strip().rstrip("/")
            for line in f
            if line.strip().endswith("/") and "*" not in line
        }


@functools.cache
def _py_basenames() -> frozenset[str]:
    ignored = _ignored_dirs() | {".git"}
    names: set[str] = set()
    for _dirpath, dirnames, files in os.walk(ROOT):
        dirnames[:] = [d for d in dirnames if d not in ignored]
        names.update(f for f in files if f.endswith(".py"))
    return frozenset(names)


def _candidate(token: str) -> str | None:
    """The path a back-quoted token claims, or None."""
    word = (token.split() or [""])[0].split("::")[0]
    word = re.sub(r":[A-Za-z0-9_.,\-]*$", "", word).rstrip(".,;:")
    if not word or _NOT_A_PATH.search(word):
        return None
    return word


def _missing(doc: str) -> list[str]:
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    roots = {
        r: set(os.listdir(r))
        for r in (ROOT, PKG, os.path.dirname(os.path.join(ROOT, doc)))
    }
    basenames = _py_basenames()
    missing = []
    for token in _TOKEN.findall(text):
        path = _candidate(token)
        if path is None:
            continue
        if "/" not in path:
            if path.endswith(".py") and path not in basenames:
                missing.append(path)
            continue
        first = path.split("/")[0]
        under = [r for r, entries in roots.items() if first in entries]
        if under and not any(
            os.path.exists(os.path.join(r, path + suffix))
            for r in under
            for suffix in ("", ".py")
        ):
            missing.append(path)
    return sorted(set(missing))


def test_the_reader_reads_paths():
    """The gate itself: what it takes for a path and what it lets be."""
    assert _candidate("serving/engine.py:2231") == "serving/engine.py"
    assert _candidate("compute/train.py:build_train_step") == "compute/train.py"
    assert _candidate("tests/test_fleet.py -m slow") == "tests/test_fleet.py"
    assert _candidate("tests/test_obs.py::test_x") == "tests/test_obs.py"
    assert _candidate("chip_smoke.py.") == "chip_smoke.py"
    for not_a_path in (
        "logs/flightrec-*.json", "perfbench/workloads/<cell>.json",
        "/root/TESTS_LAST_RUN.json", "--trace", "f(x)",
        "logs/autotune-{feed,serve}.json", "TFOS_TFSAN=1",
    ):
        assert _candidate(not_a_path) is None, not_a_path


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_files_that_exist(doc):
    missing = _missing(doc)
    assert not missing, (
        f"{doc} names paths that do not exist in the checkout: {missing}"
    )
