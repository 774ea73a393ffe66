"""The documents name only files that exist.

Every back-quoted token of a document that reads as a path of this
repository (``*.py``, ``*.md``, ``*.json``, ``tools/...``,
``benchmarks/...``) must be in the tree, so a file cannot be deleted while
the README still sends the reader to it.
"""

import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
#: names the documents use for files a run writes
NOT_IN_THE_TREE = {"MANIFEST.json", "merged.json", "flightrec__process.json"}
#: directories that hold no source: caches, build and run outputs
_PRUNED = {"__pycache__", "chiprun_out", "_export", "scratch_chip", "lib"}

_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_QUOTED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w./-]+$")


def repo_paths(text):
    """Back-quoted tokens of ``text`` that read as paths of this repo."""
    for token in _QUOTED.findall(_FENCE.sub("", text)):
        path = token.split("::")[0].split(" ")[0]
        if not _PATH.match(path) or path.startswith(("/", "-", ".")):
            continue  # a placeholder, a glob, a flag, an absolute path
        if pathlib.PurePath(path).name in NOT_IN_THE_TREE:
            continue
        if path.endswith((".py", ".md", ".json")) or path.startswith(
            ("tools/", "benchmarks/")
        ):
            yield path


@pytest.fixture(scope="module")
def tree():
    """Every file and directory of the tree, as ``/``-led relative paths."""
    found = []
    for here, dirs, files in os.walk(REPO):
        dirs[:] = [
            d for d in dirs
            if d not in _PRUNED and (d == ".claude" or not d.startswith("."))
        ]
        rel = pathlib.Path(here).relative_to(REPO).as_posix()
        found += [f"/{rel}/{name}" for name in dirs + files]
    return found


@pytest.mark.parametrize("doc", ["README.md", ".claude/skills/verify/SKILL.md"])
def test_document_names_only_files_that_exist(doc, tree):
    paths = sorted(set(repo_paths((REPO / doc).read_text())))
    assert paths, f"{doc}: no path found, the extraction is broken"
    # the documents shorten paths (``kv/server.py``, ``pstop.py`` after its
    # directory was named): a path is in the tree if some entry ends with it
    missing = [
        p for p in paths
        if not any(entry.endswith("/" + p.rstrip("/")) for entry in tree)
    ]
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
