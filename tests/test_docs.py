"""The documents describe the tree that is there.

Three checks over the living documents (the front page, ``docs/``, the
examples' READMEs and the verify skill; histories such as ``CHANGES.md``,
``PERF.md`` and ``ROADMAP.md`` record what *was* and are not read):

- a path a document names exists;
- no source file names the pre-chip benchmark that PR 30 deleted
  (``benchmarks/``, ``bench.py`` and their records): the yardstick is
  ``BENCHMARK.json`` + ``perf/`` + the driver's ledger, and nothing else;
- an environment variable the documents name is one the package reads.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "saturn_tpu")

DOCS = [
    "README.md",
    "docs/architecture.md",
    "docs/parity.md",
    "docs/analysis.md",
    "examples/README.md",
    "examples/data/README.md",
    "examples/lm_sweep/README.md",
    "examples/multihost/README.md",
    ".claude/skills/verify/SKILL.md",
]

#: First segments that make a token a path of this repo: its top-level
#: directories (``benchmarks`` among them, so that a path into the deleted
#: directory is looked for and not found) and the packages of ``saturn_tpu/``.
TOP_LEVEL = {"saturn_tpu", "tests", "tools", "perf", "docs", "examples",
             "benchmarks", ".claude"}


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as fh:
        return fh.read()


def _code_words(text):
    """Words of the back-ticked spans and of the fenced blocks."""
    parts = text.split("```")
    for i, part in enumerate(parts):
        spans = [part] if i % 2 else re.findall(r"`([^`\n]+)`", part)
        for span in spans:
            yield from span.split()


def _named_paths(text):
    packages = {
        d for d in os.listdir(PACKAGE)
        if os.path.isdir(os.path.join(PACKAGE, d)) and not d.startswith("_")
    }
    for word in _code_words(text):
        word = word.strip("()[],;.'\"")
        if "/" not in word or any(c in word for c in "<*{"):
            continue
        path = word.split(":", 1)[0]  # file.py:line, file.py::name
        if path.split("/", 1)[0] in TOP_LEVEL | packages:
            yield path


@pytest.mark.parametrize("doc", DOCS)
def test_paths_a_document_names_exist(doc):
    here = os.path.dirname(os.path.join(REPO, doc))
    missing = sorted({
        p for p in _named_paths(_read(doc))
        if not any(os.path.exists(os.path.join(base, p))
                   for base in (REPO, PACKAGE, here))
    })
    assert not missing, f"{doc} names paths that do not exist: {missing}"


_DELETED = re.compile(
    r"benchmarks/|bench_guard|bench_baseline|BENCH_r|MULTICHIP_r"
    r"|(?<![/\w])bench\.py"
)


def test_no_source_file_names_the_deleted_benchmark():
    found = []
    for top in ("saturn_tpu", "tests", "tools", "examples"):
        for d, _, names in os.walk(os.path.join(REPO, top)):
            for name in names:
                path = os.path.join(d, name)
                if (name.endswith((".pyc", ".so"))
                        or os.path.abspath(path) == os.path.abspath(__file__)):
                    continue
                with open(path, encoding="utf-8", errors="ignore") as fh:
                    for n, line in enumerate(fh, 1):
                        if _DELETED.search(line):
                            found.append(
                                f"{os.path.relpath(path, REPO)}:{n}")
    assert not found, found


def test_environment_variables_in_the_docs_are_read_by_the_package():
    named = set()
    for doc in ["README.md"] + sorted(
            "docs/" + f for f in os.listdir(os.path.join(REPO, "docs"))
            if f.endswith(".md")):
        named |= set(re.findall(r"\bSATURN_[A-Z0-9_]*[A-Z0-9]", _read(doc)))
    source = []
    for d, _, names in os.walk(PACKAGE):
        source += [_read(os.path.join(d, f)) for f in names
                   if f.endswith((".py", ".cpp"))]
    source = "\n".join(source)
    unread = sorted(v for v in named if v not in source)
    assert named and not unread, unread
