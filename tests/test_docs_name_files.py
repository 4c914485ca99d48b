"""A document that tells a reader to open or run a file names one that is in
the tree: every backticked path of the README and of the verify skill that
ends in a source or record suffix resolves from the repo's root or from
``vnsum_tpu/``. What a run writes under ``chiprun_out/`` is exempt."""
from __future__ import annotations

import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PATH_TOKEN = re.compile(r"`([\w./-]+\.(?:py|json|md|sh|cpp))`")


@pytest.mark.parametrize(
    "doc", ["README.md", ".claude/skills/verify/SKILL.md"])
def test_every_file_a_document_names_is_in_the_tree(doc):
    text = (REPO / doc).read_text(encoding="utf-8")
    named = {t for t in PATH_TOKEN.findall(text)
             if not t.startswith("chiprun_out/")}
    assert named, f"{doc} names no file: the pattern no longer matches"
    missing = sorted(
        t for t in named
        if not (REPO / t).is_file() and not (REPO / "vnsum_tpu" / t).is_file())
    assert not missing, f"{doc} names files not in the tree: {missing}"
