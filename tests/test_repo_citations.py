"""A file the sources and documents name is a file that exists.

Comments and documents cite tests, tools and records by path; a
deletion that leaves such a citation behind sends the next reader to
nothing.  Every `*.py` / `*.md` under `paddle_tpu/`, `tools/`, `tests/`,
`docs/` and `README.md` is read; a token that spells a path from one of
the repository's directories, or a top-level record in capitals
(`PERF.md`, `BASELINE.json`), must name a file in the checkout.
"""
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_READ = ("paddle_tpu", "tools", "tests", "docs")
# made-up trajectory file names are these two files' fixtures
_EXEMPT = {"tools/perf_report.py", "tests/test_perf_report.py"}

_BEFORE = r"(?<![\w/.\-])"
_PATH = re.compile(
    _BEFORE + r"((?:tools|tests|benchmark|benchmarks|docs|paddle_tpu)"
    r"/[\w/.\-]*\.(?:py|md|json))\b")
_RECORD = re.compile(_BEFORE + r"([A-Z][A-Za-z0-9_]*\.(?:md|json))\b")


def _sources():
    yield "README.md"
    for top in _READ:
        for root, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith((".py", ".md")):
                    yield os.path.relpath(os.path.join(root, name), REPO)


def test_every_cited_file_exists():
    stale = []
    for rel in _sources():
        if rel in _EXEMPT:
            continue
        with open(os.path.join(REPO, rel), encoding="utf-8") as f:
            text = f.read()
        cited = set(_PATH.findall(text)) | set(_RECORD.findall(text))
        stale += [f"{rel}: {name}" for name in sorted(cited)
                  if not os.path.exists(os.path.join(REPO, name))]
    assert not stale, "cited, not in the checkout:\n" + "\n".join(stale)
