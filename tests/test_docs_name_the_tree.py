"""Every file a document names in backticks is in the tree.

A backticked token is taken for a file when it has a `/` and ends in
`.py`, `.md`, `.sh` or `.json`, or is a bare `*.py` (runtime artefacts such
as `MANIFEST.json` have no `/` and are not taken); a `::name` suffix is cut.
It resolves against the root, against `paddle_tpu/`, or as a path suffix of
some file in the tree. Dot-directories are not walked, so a kept copy of
another commit under `.bench_scratch/` resolves nothing. No git, no jax.
"""
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    f"docs/{f}" for f in os.listdir(os.path.join(REPO, "docs"))
    if f.endswith(".md"))
TOKEN = re.compile(r"`([\w./-]+)(?:::[^`\s]*)?`")    # a path, `::name` cut
ENDS = (".py", ".md", ".sh", ".json")


def _tree():
    files = set()
    for dirpath, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "__pycache__"]
        rel = os.path.relpath(dirpath, REPO)
        files.update(os.path.normpath(os.path.join(rel, n)) for n in names)
    return files


def _named_files(text):
    for tok in TOKEN.findall(text):
        if tok.endswith(ENDS) and ("/" in tok or tok.endswith(".py")):
            yield tok


def _resolves(tok, files):
    tok = os.path.normpath(tok)
    return (tok in files or os.path.join("paddle_tpu", tok) in files
            or any(f.endswith(os.sep + tok) for f in files))


@pytest.fixture(scope="module")
def tree():
    return _tree()


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_only_files_in_the_tree(doc, tree):
    with open(os.path.join(REPO, doc)) as f:
        named = sorted(set(_named_files(f.read())))
    dangling = [t for t in named if not _resolves(t, tree)]
    assert not dangling, f"{doc} names files that are not in the tree"
