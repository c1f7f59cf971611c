"""The documents a builder is sent to cite files that exist.

A backticked word that reads as a path of this repo — under one of its
top-level directories, a bare ``name.py``, or an upper-case root-level
``*.json|jsonl|md`` (every root file of those types is named in upper case)
— has to be there, a trailing ``:line`` or ``::test`` aside; with a ``*`` it
has to match one file at least. ``CHANGES.md``, ``ROADMAP.md``, ``PERF.md``,
``VERDICT.md`` and ``SURVEY.md`` are history and are not scanned."""

import fnmatch
import glob
import io
import os
import re
import tokenize

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DIRS = ("bin", "tools", "tests", "docs", "benchmark", "deepspeed_tpu",
        "examples")
DOCS = ["README.md", ".claude/skills/verify/SKILL.md",
        *sorted(os.path.relpath(p, REPO)
                for p in glob.glob(os.path.join(REPO, "docs", "*.md")))]


@pytest.fixture(scope="module")
def basenames():
    """Every file name under the scanned directories and at the root."""
    return set(os.listdir(REPO)).union(
        name for d in DIRS for _, _, names in os.walk(os.path.join(REPO, d))
        for name in names)


def _words(text):
    """The words of the inline code spans and of the fenced blocks."""
    for i, chunk in enumerate(text.split("```")):
        spans = [chunk] if i % 2 else re.findall(r"`([^`\n]+)`", chunk)
        for span in spans:
            for word in span.split():
                yield word.strip("()[],;\"'")


def _missing(word, basenames):
    """None where ``word`` is no path of this repo or exists, else why."""
    path = re.sub(r"(::.*|:[\d,-]*)$", "", word).rstrip(".")
    if "<" in path or "{" in path or "$" in path:
        return None                      # a placeholder, not a path
    if path.startswith(tuple(d + "/" for d in DIRS)) \
            or re.fullmatch(r"[A-Z*][\w.*-]*\.(json|jsonl|md)", path):
        found = glob.glob(os.path.join(REPO, path))
    elif "/" not in path and path.endswith(".py"):
        # a module cited by its short name lives somewhere in the tree
        found = fnmatch.filter(basenames, path)
    else:
        return None
    return None if found else f"{word}: not in the tree"


@pytest.mark.parametrize("doc", DOCS)
def test_cited_paths_exist(doc, basenames):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = sorted({m for m in (_missing(w, basenames) for w in _words(text))
                      if m})
    assert not missing, f"{doc} cites what is gone: {missing}"


def test_source_comments_cite_tools_that_exist():
    """Comments and strings of the package, ``bin/`` and ``tools/`` name a
    ``tools/...`` or ``bin/...`` file only where it exists."""
    files = glob.glob(os.path.join(REPO, "deepspeed_tpu", "**", "*.py"),
                      recursive=True)
    files += glob.glob(os.path.join(REPO, "tools", "*.py"))
    files += glob.glob(os.path.join(REPO, "bin", "*"))
    cite = re.compile(r"(?<![\w/.<-])(?:tools|bin)/[\w.*-]+")
    missing = set()
    for path in files:
        with open(path) as f:
            source = f.read()
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type not in (tokenize.COMMENT, tokenize.STRING):
                continue
            for word in cite.findall(tok.string):
                # a string wrapped over two lines ends mid-name, and the
                # reference's ``bin/deepspeed`` is ours with a suffix
                if not glob.glob(os.path.join(REPO, word.rstrip(".") + "*")):
                    missing.add(f"{os.path.relpath(path, REPO)}: {word}")
    assert not missing, sorted(missing)


def test_no_run_records_at_root():
    """A run's numbers live in ``PERF_LEDGER.jsonl`` and ``PERF.md``, not in
    a root-level file named for a round (``<NAME>_r<NN>.json``)."""
    records = [name for name in os.listdir(REPO)
               if re.search(r"_r\d\d", name)
               and os.path.isfile(os.path.join(REPO, name))]
    assert not records, records
