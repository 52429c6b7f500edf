"""Every file a document points at exists.

One case a document (README.md and docs/*.md). In code spans and fenced
blocks, a path under one of the checkout's directories (or under a
subpackage of ``deeplearning4j_tpu``, as in ``serving/aio.py``) must
exist, and a bare ``*.py`` / ``*.md`` / ``*.json`` name must be the name
of some file in the checkout (a ``.json`` among a command's other words
is the caller's own file, and is not checked). So a deleted tool, test
or record cannot stay behind as an instruction to run it.
"""
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "deeplearning4j_tpu"
DOCUMENTS = ["README.md"] + sorted(
    "docs/" + f for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))

TOP_DIRS = (PACKAGE, "tests", "tools", "benchmark", "docs", "examples")
SUBPACKAGES = tuple(sorted(
    d for d in os.listdir(os.path.join(ROOT, PACKAGE))
    if os.path.isdir(os.path.join(ROOT, PACKAGE, d))
    and not d.startswith(("_", "."))))

#: globs, placeholders, alternations, elisions: not one path
NOT_A_PATH = re.compile(r"[*<>{}|…$]")
#: the reference project's own tree, named in comparisons with it
REFERENCE = re.compile(r"^docs/deeplearning4j")
SPAN = re.compile(r"```.*?```|`[^`\n]+`", re.S)
BARE = re.compile(r"^[\w\-]+\.(py|md|json)$")


def _checkout_names():
    names = set()
    for folder, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        names.update(files)
    return names


def _candidates(text):
    """(token, whether it is the whole span) of every code span."""
    for span in SPAN.findall(text):
        tokens = span.strip("`").split()
        for token in tokens:
            token = token.strip("`'\"()[],;").rstrip(".:")
            token = token.split("#")[0].split("::")[0]
            token = re.sub(r":\d+([-–]\d+)?$", "", token)
            if token and not NOT_A_PATH.search(token) \
                    and not REFERENCE.match(token):
                yield token, len(tokens) == 1


def _missing(token, alone, names):
    head, _, rest = token.partition("/")
    if rest and head in TOP_DIRS:
        return not os.path.exists(os.path.join(ROOT, token))
    if rest and head in SUBPACKAGES and re.search(r"\.\w+$", token):
        return not os.path.exists(os.path.join(ROOT, PACKAGE, token))
    if BARE.match(token) and (alone or not token.endswith(".json")):
        return token not in names
    return False


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(ROOT, document)) as fh:
        text = fh.read()
    names = _checkout_names()
    missing = sorted({t for t, alone in _candidates(text)
                      if _missing(t, alone, names)})
    assert not missing, f"{document} points at: {missing}"
