"""The documents that describe the system as it is name files that exist.

Every path a document writes in backticks that ends in ``.py``, ``.json``,
``.md``, ``.cc`` or ``.sh`` (with or without ``:line``) must exist under the
root, ``paddle_tpu/``, ``benchmarks/`` or ``tests/``. Paths only: no line
numbers, no labels. ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` hold
history and are not read.
"""

import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
DOCS = ["README.md", "DESIGN.md", "COMPONENTS.md", "MIGRATION.md",
        ".claude/skills/verify/SKILL.md"]
BASES = ["", "paddle_tpu", "benchmarks", "tests"]
# files the program writes beside a checkpoint, an artifact or a flight
# dump at run time: named by the documents, never files of the repo
WRITTEN_AT_RUN_TIME = {"manifest.json", ".meta.json", "flight.json"}

_FENCE = re.compile(r"^```.*?^```", re.S | re.M)
_SPAN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w./-]+\.(?:py|json|md|cc|sh)(?::\d+(?:-\d+)?)?$")


def _paths(text):
    """Path-shaped words of the inline code spans (a span may be a command:
    ``python tools/x.py --flag``), less any ``:line`` suffix."""
    for span in _SPAN.findall(_FENCE.sub("", text)):
        for word in span.split():
            if _PATH.match(word):
                yield word.split(":")[0]


@pytest.mark.parametrize("doc", DOCS)
def test_paths_in_backticks_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        named = sorted(set(_paths(f.read())))
    assert named, f"{doc} names no path: the pattern has rotted"
    missing = [p for p in named
               if p not in WRITTEN_AT_RUN_TIME
               and not any(os.path.exists(os.path.join(ROOT, b, p))
                           for b in BASES)]
    assert not missing, f"{doc} names files that do not exist: {missing}"
