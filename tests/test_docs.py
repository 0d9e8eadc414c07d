"""The documents that describe the tree as it is cite files that exist.

Every word of a document's code spans and fenced blocks that is a path to a
`.py`, `.md`, `.json`, `.yml`, `.yaml`, `.sh` or `.txt` file, with or
without `:line` or `:line-line`, must resolve to a file of the working tree
(so `python scripts/replay.py --digest` is held as `scripts/replay.py`
is), and a cited line may not lie past the end of the longest file the name
can mean. A deletion that leaves a document pointing at the deleted file
fails here.

Not in the set: `ROADMAP.md` (a plan names files still to come), `PERF.md`
and `CHANGES.md` (a record names files that are gone), and the round's
inputs (`SURVEY.md`, `PAPER*.md`, `SNIPPETS.md`, `BASELINE.md`).
"""

from __future__ import annotations

import os
import re
from functools import lru_cache

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    "README.md",
    "doc/README.md",
    "doc/performance.md",
    "doc/observability.md",
    "doc/concurrency.md",
    "doc/static_analysis.md",
    ".claude/skills/verify/SKILL.md",
)

# what a run leaves behind or a builder unpacks: not the tree
SKIP_DIRS = {
    ".git", "_scratch", "chiprun_out", "_parent", "_clean", "_checkout",
    "_bare", ".bench_work", ".jax_cache", ".jax_cache_cpu_tests",
    ".pytest_cache", ".hypothesis", "__pycache__", "build", "dist",
}
OUTSIDE = ("/opt/", "/root/", "/tmp/")

CITED = (".py", ".md", ".json", ".yml", ".yaml", ".sh", ".txt")
FENCED = re.compile(r"```.*?```", re.S)
INLINE = re.compile(r"`([^`]+)`")
# the WHOLE word is a path: a glob, a `<name>/x.json` or an `a=b.json` is none
CITATION = re.compile(
    r"(?P<path>[\w./-]+\.(?:py|md|json|ya?ml|sh|txt))"
    r"(?:::[\w\[\]-]+)?"
    r"(?::(?P<first>\d+)(?:-(?P<last>\d+))?)?"
)


@lru_cache(maxsize=1)
def tree_files() -> dict[str, int]:
    """Relative path -> number of lines, for every citable file of the tree."""
    out: dict[str, int] = {}
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        for name in files:
            if not name.endswith(CITED):
                continue
            full = os.path.join(root, name)
            with open(full, "rb") as fh:
                out[os.path.relpath(full, REPO)] = fh.read().count(b"\n") + 1
    return out


def lines_of(path: str) -> int | None:
    """Lines of the longest file `path` can mean (the path itself, or any
    file it is the tail of: `engine.py`, `executor/engine.py`); None where
    it means no file."""
    path = path.removeprefix("./")
    tail = "/" + path
    sizes = [
        n for rel, n in tree_files().items()
        if rel == path or rel.endswith(tail)
    ]
    return max(sizes) if sizes else None


def citations(text: str):
    code = FENCED.findall(text) + INLINE.findall(FENCED.sub("", text))
    for word in " ".join(code).replace("`", " ").split():
        m = CITATION.fullmatch(word)
        if m is None or m["path"].startswith(OUTSIDE):
            continue
        yield word, m["path"], int(m["last"] or m["first"] or 0)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_cites_files_that_exist(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as fh:
        text = fh.read()
    wrong = []
    for token, path, line in citations(text):
        n = lines_of(path)
        if n is None:
            wrong.append(f"`{token}`: no such file in the tree")
        elif line > n:
            wrong.append(f"`{token}`: the longest {path} has {n} lines")
    assert not wrong, f"{document} cites\n  " + "\n  ".join(sorted(set(wrong)))
