"""The documents say what the tree holds: text reads, no jax, well under a
second each.

Two things rot in silence: a `ROADMAP <item>` citation whose item was
renumbered or never existed (a reader follows it to nothing), and a document
that sends its reader to a tool the tree no longer has (the root-level bench
and its regression gate went at PR 44, the pre-chip narrative with them).
`PERF.md` and `CHANGES.md` keep history and are held to the first only.
"""

import json
import re
from pathlib import Path

import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]

# "ROADMAP S13", "ROADMAP R4/R7", "ROADMAP R7, R9", "ROADMAP D1 d", "ROADMAP's S6"
_ITEM = r"[SRD]\d+[a-z]?"
_CITATION = re.compile(rf"ROADMAP(?:\.md)?(?:'s)?,? ({_ITEM}(?:(?:/|, | and ){_ITEM})*)")


def _roadmap_items() -> set[str]:
    """Every item `ROADMAP.md` has: the open ones by their headings
    (`S13. **...`), the done and closed ones by the paragraph under "Open
    items" that lists the numbers not to be reused."""
    text = (REPO_ROOT / "ROADMAP.md").read_text()
    open_items = set(re.findall(rf"^({_ITEM})\. \*\*", text, flags=re.MULTILINE))
    preamble = text.split("## Open items", 1)[1].split("\n###", 1)[0]
    return open_items | set(re.findall(rf"\b{_ITEM}\b", preamble))


def _texts(where: str) -> dict[str, str]:
    """{path from the root: text} of a file, or of a directory's `.py` and `.md` files."""
    path = REPO_ROOT / where
    paths = [path] if path.is_file() else sorted(
        p for p in path.rglob("*") if p.suffix in (".py", ".md") and "__pycache__" not in p.parts
    )
    return {str(p.relative_to(REPO_ROOT)): p.read_text() for p in paths}


@pytest.mark.parametrize("where", ["llm_training_tpu", "docs", "README.md", "PERF.md"])
def test_cited_roadmap_items_exist(where):
    items = _roadmap_items()
    assert {"S13", "R11", "D1"} <= items and "D2" in items  # open by heading, closed by the list
    texts = _texts(where)
    stale = [
        f"{name}: ROADMAP {cited}"
        for name, text in texts.items()
        for group in _CITATION.findall(text)
        for cited in re.findall(_ITEM, group)
        if cited not in items
    ]
    assert stale == []
    # a numbering two rounds gone ("ROADMAP item 2", "ROADMAP-5") names nothing at all
    assert [name for name, text in texts.items() if re.search(r"ROADMAP(?: item |-)\d", text)] == []


_GONE = re.compile(r"bench\.py|--check-regression|--bench-dir|\bBENCH_[A-Z]\w*|BASELINE\.md|ADVICE\.md")
_CELL = re.compile(r"`([a-z0-9]+-(?:serve|train)-[a-z0-9-]+)`")


@pytest.mark.parametrize("document", ["README.md", "docs/performance.md", "docs/observability.md"])
def test_documents_name_only_what_exists(document):
    text = (REPO_ROOT / document).read_text()
    assert sorted(set(_GONE.findall(text))) == []
    # the one measurement is named as `BENCHMARK.json` names it (read, not edited)
    cells = {w["name"] for w in json.loads((REPO_ROOT / "BENCHMARK.json").read_text())["workloads"]}
    named = set(_CELL.findall(text))
    assert named <= cells, sorted(named - cells)
    if document == "README.md":
        assert named == cells, sorted(cells - named)
        assert "benchmarks/run.py --workload" in text and "PERF_LEDGER.jsonl" in text
