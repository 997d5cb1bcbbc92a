"""Rule `env-doc-drift`: every repo env var the code reads is documented.

The repo owns three env namespaces — `LLMT_*` (chaos/supervisor/elastic),
`FLASH_*` (kernel tiles), `PAGED_*` (serving tiles) — and the docs carry
env tables for them (docs/performance.md, docs/resilience.md,
docs/serving.md). A knob added in code but not in the tables is effectively
unshipped: nobody sweeping tiles or debugging a resume can find it.

The rule collects every string literal matching the env-name pattern from
non-docstring positions in the scan set (literals, dict values feeding
`os.environ` lookups — intentionally broader than call-site analysis, so
tables like `tuning.ENV_PAGED` count) and requires each name to appear
somewhere in the docs corpus. Docstring mentions don't count as reads.
"""

from __future__ import annotations

import ast
import re

from llm_training_tpu.analysis import contracts
from llm_training_tpu.analysis.engine import Finding, RepoContext, RuleSpec

_ENV_RE = re.compile(contracts.ENV_VAR_PATTERN)


def _docstring_ids(tree: ast.Module) -> set[int]:
    ids: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
            body = node.body
            if (
                body
                and isinstance(body[0], ast.Expr)
                and isinstance(body[0].value, ast.Constant)
                and isinstance(body[0].value.value, str)
            ):
                ids.add(id(body[0].value))
    return ids


def _docs_corpus(ctx: RepoContext) -> str:
    chunks = []
    for rel in contracts.ENV_DOC_FILES:
        path = ctx.root / rel
        if path.is_file():
            chunks.append(path.read_text())
    return "\n".join(chunks)


def _run(ctx: RepoContext) -> list[Finding]:
    corpus = _docs_corpus(ctx)
    first_seen: dict[str, tuple[str, int]] = {}
    for parsed in ctx.files:
        doc_ids = _docstring_ids(parsed.tree)
        for node in ast.walk(parsed.tree):
            if (
                isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and id(node) not in doc_ids
                and _ENV_RE.match(node.value)
            ):
                first_seen.setdefault(node.value, (parsed.path, node.lineno))
    findings: list[Finding] = []
    for name in sorted(first_seen):
        if re.search(rf"\b{re.escape(name)}\b", corpus):
            continue
        path, line = first_seen[name]
        findings.append(
            Finding(
                rule=RULE.name,
                path=path,
                line=line,
                message=(
                    f"env var `{name}` is read in code but appears in none of "
                    "the docs env tables "
                    f"({', '.join(contracts.ENV_DOC_FILES[:3])}, ...); add a "
                    "row where its subsystem is documented"
                ),
            )
        )
    return findings


RULE = RuleSpec(
    name="env-doc-drift",
    description=(
        "every LLMT_*/FLASH_*/PAGED_* env var read in code must "
        "appear in the docs env tables"
    ),
    run=_run,
)
