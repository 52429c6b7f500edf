"""The arrows between the package's layers point one way.

The served path is ``serving`` -> ``zoo`` -> ``nn`` -> ``kernels``
(PERF.md section 3); a lower layer that imports a higher one cannot be
read, tested or replaced without it. Read from the sources with ``ast``,
imports inside functions included: a lazy import is the same arrow.
"""
import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "deeplearning4j_tpu"

#: what sits beside the package in a checkout and only ever imports it
OUTSIDE = {"benchmark", "tools", "tests", "examples", "chip_smoke",
           "__graft_entry__"}

#: layer -> the subpackages it must not import
RULES = {
    "kernels": {"nn", "zoo", "serving", "parallel"},
    "nn": {"zoo", "serving"},
    "zoo": {"serving"},
    "serving": {"parallel"},
}


def _modules(layer=None):
    top = os.path.join(ROOT, PACKAGE, layer) if layer \
        else os.path.join(ROOT, PACKAGE)
    for folder, _, files in os.walk(top):
        for name in sorted(files):
            if name.endswith(".py"):
                yield os.path.join(folder, name)


def _imported(path):
    """Absolute dotted names of everything ``path`` imports, each with
    its line."""
    with open(path) as fh:
        tree = ast.parse(fh.read(), path)
    here = os.path.relpath(path, ROOT)[:-3].split(os.sep)[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            if not node.level:
                yield node.module, node.lineno
                continue
            base = here[:len(here) - (node.level - 1)]
            if node.module:
                yield ".".join(base + [node.module]), node.lineno
            else:           # ``from .. import serving``
                for alias in node.names:
                    yield ".".join(base + [alias.name]), node.lineno


@pytest.mark.parametrize("layer", sorted(RULES))
def test_a_layer_imports_nothing_above_it(layer):
    forbidden = RULES[layer]
    found = []
    for path in _modules(layer):
        for name, line in _imported(path):
            parts = name.split(".")
            if parts[0] == PACKAGE and len(parts) > 1 \
                    and parts[1] in forbidden:
                found.append(f"{os.path.relpath(path, ROOT)}:{line} "
                             f"imports {name}")
    assert not found, "\n".join(found)


def test_the_package_imports_nothing_beside_it():
    found = []
    for path in _modules():
        for name, line in _imported(path):
            if name.split(".")[0] in OUTSIDE:
                found.append(f"{os.path.relpath(path, ROOT)}:{line} "
                             f"imports {name}")
    assert not found, "\n".join(found)
