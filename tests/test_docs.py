"""Drift-proofing for the documentation (docs/architecture.md's CI promise).

Docs rot in two ways this repo can actually check: a ``--flag`` a doc tells
the reader to pass stops existing in the parser it names, or a relative
markdown link points at a file that was moved/renamed. Both are pure text
properties — no imports, no jax — so this lane is fast and runs blocking.

Three invariants:

1. every ``--flag`` token in ``docs/*.md``, ``README.md`` and the
   ``examples/*.py`` module docstrings is defined by SOME argparse parser in
   the repo's entry-point sources (train/dryrun/report, the examples, the
   bench runner, ``chip_smoke.py``);
2. every relative markdown link inside ``docs/`` resolves to a git-tracked
   file;
3. every doc under ``docs/`` is reachable from the ``docs/architecture.md``
   hub by following links — a doc the map doesn't reach is a doc nobody
   finds.
"""
from __future__ import annotations

import ast
import re
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = REPO / "docs"

#: sources whose argparse declarations define the legal flag vocabulary
PARSER_SOURCES = [
    REPO / "src" / "repro" / "launch" / "train.py",
    REPO / "src" / "repro" / "launch" / "dryrun.py",
    REPO / "src" / "repro" / "obs" / "report.py",
    REPO / "benchmarks" / "run.py",
    REPO / "chip_smoke.py",
    *sorted((REPO / "examples").glob("*.py")),
]

_ADD_ARGUMENT = re.compile(r"""add_argument\(\s*['"](--[a-z][a-z0-9-]*)['"]""")
#: a flag token in prose/code blocks: ``--word`` with word-ish tail, not
#: preceded by another dash (rules out ``---`` hrules) or a word char
_FLAG_TOKEN = re.compile(r"(?<![\w-])--[a-z][a-z0-9-]*")
_MD_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")


def _defined_flags() -> set:
    flags = {"--help"}  # argparse defines it on every parser
    for src in PARSER_SOURCES:
        flags |= set(_ADD_ARGUMENT.findall(src.read_text()))
    assert "--rounds" in flags, "flag extraction regex rotted"
    return flags


def _unknown_flags(text: str, defined: set) -> list:
    """Flag tokens in ``text`` that no parser defines. A token ending in
    ``-`` is a glob-ish family mention (``--chaos-*``) and passes if any
    defined flag carries that prefix."""
    unknown = []
    for tok in set(_FLAG_TOKEN.findall(text)):
        if tok in defined:
            continue
        if tok.endswith("-") and any(f.startswith(tok) for f in defined):
            continue
        unknown.append(tok)
    return sorted(unknown)


def _tracked_files() -> set:
    out = subprocess.run(
        ["git", "ls-files"], cwd=REPO, capture_output=True, text=True, check=True
    ).stdout
    return {line.strip() for line in out.splitlines() if line.strip()}


def _doc_links(md_path: Path):
    """Relative link targets of one markdown file (external links skipped)."""
    for target in _MD_LINK.findall(md_path.read_text()):
        target = target.split("#", 1)[0]
        if not target or target.startswith(("http://", "https://", "mailto:")):
            continue
        yield target


DOC_FILES = sorted(DOCS.glob("*.md"))
EXAMPLE_FILES = sorted((REPO / "examples").glob("*.py"))


@pytest.mark.parametrize("md", DOC_FILES, ids=lambda p: p.name)
def test_doc_flags_exist(md):
    unknown = _unknown_flags(md.read_text(), _defined_flags())
    assert not unknown, (
        f"{md.name} references flags no entry-point parser defines: {unknown}"
    )


def test_readme_flags_exist():
    unknown = _unknown_flags((REPO / "README.md").read_text(), _defined_flags())
    assert not unknown, f"README.md references undefined flags: {unknown}"


@pytest.mark.parametrize("py", EXAMPLE_FILES, ids=lambda p: p.name)
def test_example_docstring_flags_exist(py):
    doc = ast.get_docstring(ast.parse(py.read_text())) or ""
    unknown = _unknown_flags(doc, _defined_flags())
    assert not unknown, (
        f"{py.name} docstring references undefined flags: {unknown}"
    )


@pytest.mark.parametrize("md", DOC_FILES, ids=lambda p: p.name)
def test_doc_links_resolve(md):
    tracked = _tracked_files()
    broken = []
    for target in _doc_links(md):
        resolved = (md.parent / target).resolve().relative_to(REPO)
        if str(resolved) not in tracked:
            broken.append(target)
    assert not broken, f"{md.name} has broken relative links: {broken}"


def test_all_docs_reachable_from_architecture():
    hub = DOCS / "architecture.md"
    assert hub.exists(), "docs/architecture.md is the documentation hub"
    seen, frontier = set(), [hub]
    while frontier:
        doc = frontier.pop()
        if doc in seen or not doc.exists():
            continue
        seen.add(doc)
        for target in _doc_links(doc):
            resolved = (doc.parent / target).resolve()
            if resolved.suffix == ".md" and resolved.parent == DOCS:
                frontier.append(resolved)
    unreachable = sorted(p.name for p in DOC_FILES if p not in seen)
    assert not unreachable, (
        f"docs not reachable from architecture.md: {unreachable}"
    )
