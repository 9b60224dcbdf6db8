"""The documents that tell a reader what to run name files that exist.

Every ``python <path>.py`` and every ``tools/<name>.py`` (or ``.sh``)
named in ``README.md``, ``tools/run_checks.sh`` and the verify skill is
a file of this checkout: a deleted harness leaves no instruction
behind. A script of the reader's own is written ``your_<name>.py``. One
case a document.
"""
import os
import re

import pytest

from helpers import ROOT

DOCS = ("README.md", "tools/run_checks.sh",
        ".claude/skills/verify/SKILL.md")
# `python [-flags] path.py` (also python3, and after an env assignment)
# and bare mentions of a file under tools/
_NAMED = (re.compile(r"python3?\s+(?:-\w+\s+)*([\w./-]+\.py)\b"),
          re.compile(r"(?<![\w/])(tools/[\w./-]+\.(?:py|sh))\b"))


def _named_files(text):
    return sorted({m for pat in _NAMED for m in pat.findall(text)
                   if not os.path.basename(m).startswith("your_")})


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_only_files_that_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        named = _named_files(f.read())
    assert named, "%s names no file: the patterns above have rotted" % doc
    missing = [p for p in named
               if not os.path.exists(os.path.join(ROOT, p))]
    assert missing == [], "%s names files that do not exist" % doc
