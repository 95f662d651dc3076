"""``README.md`` and ``docs/*.md`` name only what is in the tree.

Every session starts from these documents, so a path that is gone or an
environment name nothing reads sends the reader to the wrong place.  One
case a file: each path in back quotes or in a fenced block that lies under
the repo's own directories (or is a bare ``*.py``) exists, and each
``KOLIBRIE_*`` name the file mentions is read somewhere under
``kolibrie_tpu/`` or ``benchmark/``.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)
ROOTS = ("kolibrie_tpu/", "benchmark/", "tests/", "examples/", "docs/", "native/")
FENCED = re.compile(r"```.*?```", re.S)
QUOTED = re.compile(r"`([^`]+)`")
ENV_NAME = re.compile(r"KOLIBRIE_[A-Z][A-Z_]*[A-Z]")


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


def _code_words(text):
    """Every word of the document's code: fenced blocks and, in what is
    left, back-quoted spans (``python name.py --flag`` is three words)."""
    spans = FENCED.findall(text) + QUOTED.findall(FENCED.sub(" ", text))
    return {word for span in spans for word in span.strip("`").split()}


def _path_of(word):
    """The file or directory a word of code names, or None if it names
    none: ``a/b.py:12`` and ``a/b.py::test`` name ``a/b.py``; a word with a
    placeholder (``<cell>``, ``*``, ``{...}``, ``…``) names no one path."""
    token = word.split("::")[0].rstrip(",.;")
    token = re.sub(r":[\d,\-:]+$", "", token)
    if re.search(r"[<>*{}…$()|=]", token):
        return None
    if token.startswith(ROOTS):
        return token
    if "/" not in token and token.endswith(".py"):
        return token
    return None


@functools.lru_cache(maxsize=None)
def _env_names_read():
    names = set()
    for root in ("kolibrie_tpu", "benchmark"):
        for path in glob.glob(os.path.join(REPO, root, "**", "*.py"), recursive=True):
            names.update(ENV_NAME.findall(_read(os.path.relpath(path, REPO))))
    return names


def test_there_are_documents_to_check():
    assert "README.md" in DOCS and len(DOCS) > 10


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_is_in_the_tree(doc):
    text = _read(doc)
    named = {_path_of(w) for w in _code_words(text)} - {None}
    # a bare ``name.py`` is a file at the root, or one the sentence around
    # it places (``dist_query.py`` under ``parallel/``, ``01_simple_select.py``
    # under ``examples/``): it has to exist under that name in one of the
    # repo's own directories
    missing = sorted(
        p
        for p in named
        if not os.path.exists(os.path.join(REPO, p))
        and not (
            "/" not in p
            and any(
                glob.glob(os.path.join(REPO, root, "**", p), recursive=True)
                for root in ROOTS
            )
        )
    )
    assert missing == [], f"{doc} names paths that are not in the tree"
    unread = sorted(set(ENV_NAME.findall(text)) - _env_names_read())
    assert unread == [], f"{doc} names environment variables nothing reads"
