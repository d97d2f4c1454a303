"""The tree's own shape: which way the imports point, and whether the
documents name files that exist.

- **Layering**: measurement code sits above the program.  Nothing under
  ``flextree_tpu/`` outside ``flextree_tpu/bench/`` imports
  ``flextree_tpu.bench``, the benchmark (``benchmarks``) or a tool
  (``tools``) — absolute or relative, at module level or inside a function.
- **Paths**: every ``tools/*.py``, ``flextree_tpu/**.py``, ``tests/*.py``
  and root ``*.json`` path a living document names is in the tree.
  ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` carry history and are not
  cases.
"""

from __future__ import annotations

import ast
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "flextree_tpu")

#: top-level names no module of the program may import
ABOVE = ("flextree_tpu.bench", "benchmarks", "tools")

UNITS = sorted(
    name[:-3] if name.endswith(".py") else name
    for name in os.listdir(PKG)
    if name != "bench" and not name.startswith("__")
    and (name.endswith(".py") or os.path.isdir(os.path.join(PKG, name)))
) + ["__init__"]


def _imports(source: str, rel_path: str):
    """Every module ``source`` imports, read as the file at ``rel_path``
    of the repo: relative imports are resolved to absolute names."""
    parts = rel_path[:-3].split("/")
    package = parts[:-1]  # a module's package; an __init__'s is its directory
    for node in ast.walk(ast.parse(source, rel_path)):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - node.level + 1] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            yield node.lineno, mod
            for alias in node.names:  # ``from .. import bench``
                yield node.lineno, f"{mod}.{alias.name}"


def _above(mod: str) -> bool:
    return any(mod == top or mod.startswith(top + ".") for top in ABOVE)


@pytest.mark.parametrize("unit", UNITS)
def test_program_imports_no_measurement_code(unit):
    target = os.path.join(PKG, unit)
    files = (
        glob.glob(os.path.join(target, "**", "*.py"), recursive=True)
        if os.path.isdir(target)
        else [target + ".py"]
    )
    assert files
    found = []
    for path in sorted(files):
        rel = os.path.relpath(path, REPO).replace(os.sep, "/")
        with open(path) as fh:
            found += [
                f"{rel}:{line} imports {mod}"
                for line, mod in _imports(fh.read(), rel)
                if _above(mod)
            ]
    assert found == []


def test_import_walk_resolves_relative_and_nested_imports():
    """The walk itself, on the forms an import of measurement code takes."""
    source = (
        "def f():\n"
        "    from ..bench.harness import timer\n"
        "    from .. import bench\n"
        "    import tools.roofline_reduce\n"
        "    from ..utils.timing import Timer\n"
    )
    mods = [m for _, m in _imports(source, "flextree_tpu/planner/autotune.py")]
    assert [m for m in mods if _above(m)] == [
        "flextree_tpu.bench.harness",
        "flextree_tpu.bench.harness.timer",
        "flextree_tpu.bench",
        "tools.roofline_reduce",
    ]
    # the same line in a package's __init__ is one level shallower
    init = [m for _, m in _imports("from .bench import x\n", "flextree_tpu/__init__.py")]
    assert init[0] == "flextree_tpu.bench"


# ------------------------------------------------------------------ paths

DOCS = sorted(
    ["README.md", "WINS.md", "PARITY.md", ".claude/skills/verify/SKILL.md",
     ".github/workflows/ci.yml"]
    + [os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))]
)

#: a path of the tree, as prose names one; a template (`{tag}.json`) or a
#: scratch path (`/tmp/x.json`) is not one
_PATH = re.compile(
    r"(?<![\w/{}.\-])"
    r"((?:tools|tests|flextree_tpu(?:/\w+)*)/\w+\.py|[A-Z][A-Za-z0-9_]*\.json)"
    r"(?![\w{])"
)


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc):
    with open(os.path.join(REPO, doc)) as fh:
        named = sorted(set(_PATH.findall(fh.read())))
    missing = [p for p in named if not os.path.exists(os.path.join(REPO, p))]
    assert missing == []
