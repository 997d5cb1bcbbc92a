"""Rule `jax-free-import`: declared jax-free modules stay jax-free.

The supervisor/elastic/serve-surface/loadgen modules each carry a
hand-maintained "never imports jax at module level" invariant (a supervisor
that owns a backend dies with the child it must restart; the serve package
surface must be importable host-only; a parent that runs its phases as
children must leave them the chip). Until now only scattered subprocess
tests enforced it.

This rule walks the *transitive module-level* import graph from each
contracted module in `contracts.JAX_FREE_CONTRACTS`: importing
`llm_training_tpu.resilience.elastic` also executes every package
`__init__` on its dotted path, so those are edges too. Imports inside
function bodies (the sanctioned lazy pattern) and `if TYPE_CHECKING:`
blocks are ignored. Any path that reaches a `jax`/`jaxlib` import is
reported with the full chain, so the fix target is obvious.
"""

from __future__ import annotations

import ast
from pathlib import Path

from llm_training_tpu.analysis import contracts
from llm_training_tpu.analysis.engine import Finding, RepoContext, RuleSpec

# every statement type whose body executes inline at module import time;
# TryStar exists only on 3.11+
_TRY_OR_WITH = (ast.Try, ast.With, ast.AsyncWith) + (
    (ast.TryStar,) if hasattr(ast, "TryStar") else ()
)


def _module_name(ctx: RepoContext, abs_path: Path) -> str:
    rel = ctx.rel(abs_path)
    parts = rel[:-3].split("/")  # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _is_type_checking_guard(test: ast.AST) -> bool:
    name = test.attr if isinstance(test, ast.Attribute) else getattr(test, "id", None)
    return name == "TYPE_CHECKING"


def _module_level_imports(
    tree: ast.Module, current_module: str, is_package: bool
) -> list[tuple[str, int]]:
    """(target dotted module, line) for every import executed at module
    import time — class bodies run, function bodies don't."""
    edges: list[tuple[str, int]] = []

    def visit(statements) -> None:
        for stmt in statements:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            if isinstance(stmt, ast.Import):
                for alias in stmt.names:
                    parts = alias.name.split(".")
                    for depth in range(1, len(parts) + 1):
                        edges.append((".".join(parts[:depth]), stmt.lineno))
            elif isinstance(stmt, ast.ImportFrom):
                if stmt.level:
                    base_parts = current_module.split(".")
                    if not is_package:
                        base_parts = base_parts[:-1]
                    base_parts = base_parts[: len(base_parts) - (stmt.level - 1)]
                    base = ".".join(base_parts)
                    module = f"{base}.{stmt.module}" if stmt.module else base
                else:
                    module = stmt.module or ""
                if module:
                    parts = module.split(".")
                    for depth in range(1, len(parts) + 1):
                        edges.append((".".join(parts[:depth]), stmt.lineno))
                    # `from pkg import sub` may import the submodule pkg.sub
                    for alias in stmt.names:
                        if alias.name != "*":
                            edges.append((f"{module}.{alias.name}", stmt.lineno))
            elif isinstance(stmt, ast.If):
                if not _is_type_checking_guard(stmt.test):
                    visit(stmt.body)
                visit(stmt.orelse)
            elif isinstance(stmt, _TRY_OR_WITH):
                visit(stmt.body)
                for handler in getattr(stmt, "handlers", []):
                    visit(handler.body)
                visit(getattr(stmt, "orelse", []))
                visit(getattr(stmt, "finalbody", []))
            elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                visit(stmt.body)
                visit(stmt.orelse)
            elif isinstance(stmt, ast.ClassDef):
                visit(stmt.body)
            elif isinstance(stmt, ast.Match):
                for case in stmt.cases:
                    visit(case.body)

    visit(tree.body)
    return edges


def _edges_for(ctx: RepoContext, abs_path: Path, cache: dict) -> list[tuple[str, int]]:
    if abs_path not in cache:
        parsed = ctx.parsed(abs_path)
        if parsed is None:
            cache[abs_path] = []
        else:
            cache[abs_path] = _module_level_imports(
                parsed.tree,
                _module_name(ctx, abs_path),
                abs_path.name == "__init__.py",
            )
    return cache[abs_path]


def _run(ctx: RepoContext) -> list[Finding]:
    findings: list[Finding] = []
    edge_cache: dict = {}
    for contract_rel, reason in contracts.JAX_FREE_CONTRACTS.items():
        contract_abs = ctx.root / contract_rel
        if not contract_abs.is_file():
            findings.append(
                Finding(
                    rule=RULE.name,
                    path=contract_rel,
                    line=1,
                    message=(
                        "jax-free contract names a file that no longer exists; "
                        "update analysis/contracts.py"
                    ),
                )
            )
            continue
        # BFS over repo-internal module-level imports; chain = [(file, line,
        # target), ...] so the violation message can show the whole path.
        # Seeded with the contract file AND every package __init__ on its
        # own dotted path — importing the contract module executes those
        # first, so a jax import there breaks the contract just the same.
        queue: list[tuple[Path, tuple]] = [(contract_abs.resolve(), ())]
        visited = {contract_abs.resolve()}
        parts = Path(contract_rel).parts[:-1]
        for depth in range(1, len(parts) + 1):
            init = (ctx.root.joinpath(*parts[:depth]) / "__init__.py").resolve()
            if init.is_file() and init not in visited:
                visited.add(init)
                queue.append((init, ((init, 1, ".".join(parts[:depth])),)))
        reported: set[str] = set()
        while queue:
            file_abs, chain = queue.pop(0)
            for target, lineno in _edges_for(ctx, file_abs, edge_cache):
                if target.split(".")[0] in contracts.BANNED_IMPORT_ROOTS:
                    offender = ctx.rel(file_abs)
                    if offender in reported:
                        continue
                    reported.add(offender)
                    # no line numbers in the message: Finding.key must stay
                    # stable across unrelated edits in intermediate files
                    hops = " -> ".join(t for _f, _ln, t in chain)
                    via = f" via {hops}" if hops else ""
                    findings.append(
                        Finding(
                            rule=RULE.name,
                            path=contract_rel,
                            line=chain[0][1] if chain else lineno,
                            message=(
                                f"module-level import of '{target}' in "
                                f"{offender} breaks the jax-free contract"
                                f"{via} — {reason}; make the import lazy "
                                "(function body) or drop it"
                            ),
                        )
                    )
                    continue
                internal = ctx.file_for_module(target)
                if internal is not None:
                    internal = internal.resolve()
                    if internal not in visited:
                        visited.add(internal)
                        queue.append(
                            (internal, chain + ((file_abs, lineno, target),))
                        )
    return findings


RULE = RuleSpec(
    name="jax-free-import",
    description=(
        "declared jax-free modules (supervisor, elastic, serve surface, "
        "chip_smoke.py, serve_loadgen) must not reach jax through module-level "
        "imports, transitively"
    ),
    run=_run,
)
