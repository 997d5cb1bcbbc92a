"""graftlint core: file discovery, suppressions, baseline, CLI.

Rules are pure functions over parsed ASTs (`RuleSpec.run(ctx)`); this module
owns everything around them — which files to scan, `# lint: allow(...)`
suppression comments, the committed baseline for grandfathered findings,
human/JSON output, and exit codes. No jax anywhere in this package: the
whole point is a correctness signal that costs milliseconds, before any
backend exists (docs/static-analysis.md).
"""

from __future__ import annotations

import argparse
import ast
import io
import json
import re
import sys
import time
import tokenize
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Iterable

# the default scan set, relative to the repo root: library + entry scripts.
# tests/ are deliberately excluded (they import jax freely and construct
# intentionally-broken fixtures); point the CLI at extra paths to widen.
DEFAULT_SCAN = ("llm_training_tpu", "scripts", "chip_smoke.py")
DEFAULT_BASELINE = "config/lint_baseline.json"
DEFAULT_RACE_BASELINE = "config/race_baseline.json"
# meta-findings that must never be grandfathered: a baselined reasonless
# suppression would permanently void the mandatory-reason rule, and a
# baselined parse error hides every finding in the broken file
NON_BASELINABLE_RULES = ("suppression-reason", "parse-error")
_EXCLUDED_DIRS = {"__pycache__", ".git"}

# `# lint: allow(rule)` or `# lint: allow(rule-a, rule-b): why it is fine`
_SUPPRESS_RE = re.compile(r"#\s*lint:\s*allow\(([\w*,\s-]+)\)(?::\s*(\S.*))?")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative posix path
    line: int
    message: str

    @property
    def key(self) -> str:
        # line numbers drift with unrelated edits; baseline entries key on
        # the stable (rule, file, message) triple instead
        return f"{self.rule}::{self.path}::{self.message}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclass(frozen=True)
class RuleSpec:
    name: str
    description: str
    run: Callable[["RepoContext"], list[Finding]]


@dataclass
class ParsedFile:
    path: str  # repo-relative posix
    abs_path: Path
    source: str
    tree: ast.Module
    # line -> (rule names allowed, reason or None); reasons are REQUIRED —
    # a reasonless allow is itself a finding
    suppressions: dict[int, tuple[set[str], str | None]]


def _parse_suppressions(source: str) -> dict[int, tuple[set[str], str | None]]:
    # only real COMMENT tokens register suppressions — the syntax quoted in
    # a docstring or string literal must never silently suppress findings
    out: dict[int, tuple[set[str], str | None]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            match = _SUPPRESS_RE.search(tok.string)
            if match:
                rules = {
                    part.strip() for part in match.group(1).split(",") if part.strip()
                }
                out[tok.start[0]] = (rules, match.group(2))
    except tokenize.TokenError:
        pass  # unparseable tail: the ast parse error is the real finding
    return out


class RepoContext:
    """Parsed view of the scan set plus an on-demand parse cache (the import
    graph walks files outside the selected paths)."""

    def __init__(self, root: Path, paths: Iterable[str] | None = None):
        self.root = Path(root).resolve()
        self.parse_errors: list[Finding] = []
        self._cache: dict[Path, ParsedFile | None] = {}
        self.files: list[ParsedFile] = []
        for file_path in self._discover(paths or DEFAULT_SCAN):
            parsed = self.parsed(file_path)
            if parsed is not None:
                self.files.append(parsed)

    def _discover(self, paths: Iterable[str]) -> list[Path]:
        found: list[Path] = []
        for entry in paths:
            target = (self.root / entry).resolve()
            if target.is_file() and target.suffix == ".py":
                found.append(target)
            elif target.is_dir():
                found.extend(
                    p
                    for p in sorted(target.rglob("*.py"))
                    if not (_EXCLUDED_DIRS & set(p.relative_to(self.root).parts))
                )
        return found

    def rel(self, abs_path: Path) -> str:
        try:
            return abs_path.relative_to(self.root).as_posix()
        except ValueError:
            return abs_path.as_posix()

    def parsed(self, abs_path: Path) -> ParsedFile | None:
        abs_path = abs_path.resolve()
        if abs_path in self._cache:
            return self._cache[abs_path]
        parsed: ParsedFile | None = None
        try:
            source = abs_path.read_text()
            tree = ast.parse(source, filename=str(abs_path))
            parsed = ParsedFile(
                path=self.rel(abs_path),
                abs_path=abs_path,
                source=source,
                tree=tree,
                suppressions=_parse_suppressions(source),
            )
        except (OSError, SyntaxError, ValueError) as exc:
            self.parse_errors.append(
                Finding(
                    rule="parse-error",
                    path=self.rel(abs_path),
                    line=getattr(exc, "lineno", None) or 1,
                    message=f"could not parse: {exc.__class__.__name__}: {exc}",
                )
            )
            self._cache[abs_path] = None
            return None
        self._cache[abs_path] = parsed
        return parsed

    def file(self, rel_path: str) -> ParsedFile | None:
        return self.parsed(self.root / rel_path)

    def file_for_module(self, module: str) -> Path | None:
        """Repo file implementing dotted `module`, or None for third-party."""
        parts = module.split(".")
        as_module = self.root.joinpath(*parts).with_suffix(".py")
        if as_module.is_file():
            return as_module
        as_package = self.root.joinpath(*parts, "__init__.py")
        if as_package.is_file():
            return as_package
        return None


def all_rules() -> list[RuleSpec]:
    from llm_training_tpu.analysis import (
        env_docs,
        host_sync,
        import_contracts,
        logical_axes,
        pallas_arity,
        telemetry_prefixes,
        thread_jax_free,
    )

    return [
        pallas_arity.RULE,
        import_contracts.RULE,
        host_sync.RULE,
        telemetry_prefixes.RULE,
        env_docs.RULE,
        logical_axes.RULE,
        thread_jax_free.RULE,
    ]


@dataclass
class AnalysisResult:
    findings: list[Finding]  # active: fail the gate
    suppressed: list[Finding]
    baselined: list[Finding]
    elapsed_s: float


def run_analysis(
    root: Path,
    paths: Iterable[str] | None = None,
    rules: Iterable[str] | None = None,
    baseline_keys: set[str] | None = None,
    rule_specs: list[RuleSpec] | None = None,
) -> AnalysisResult:
    """Run `rule_specs` (default: the graftlint rule table) over the scan
    set; the racecheck mode passes its own rule list through here so the
    suppression/baseline machinery is shared verbatim."""
    t0 = time.monotonic()
    ctx = RepoContext(root, paths)
    selected = rule_specs if rule_specs is not None else all_rules()
    if rules is not None:
        wanted = set(rules)
        known = {rule.name for rule in selected}
        unknown = wanted - known
        if unknown:
            raise ValueError(
                f"unknown rule(s) {sorted(unknown)}; known: {sorted(known)}"
            )
        selected = [rule for rule in selected if rule.name in wanted]

    raw: list[Finding] = []
    for rule in selected:
        raw.extend(rule.run(ctx))
    # AFTER the rules: on-demand parses (the import-graph walk reaches files
    # outside the selected paths) append parse errors during rule execution
    raw.extend(ctx.parse_errors)

    active: list[Finding] = []
    suppressed: list[Finding] = []
    baselined: list[Finding] = []
    suppression_files = {pf.path: pf.suppressions for pf in ctx.files}
    for finding in sorted(raw, key=lambda f: (f.path, f.line, f.rule)):
        if finding.path not in suppression_files:
            # findings can land on files outside the selected scan paths
            # (the import-graph walk); their inline suppressions still count
            parsed = ctx.file(finding.path)
            suppression_files[finding.path] = (
                parsed.suppressions if parsed is not None else {}
            )
        table = suppression_files.get(finding.path, {})
        hit = None
        for line in (finding.line, finding.line - 1):
            entry = table.get(line)
            if entry and (finding.rule in entry[0] or "*" in entry[0]):
                hit = (line, entry)
                break
        if hit is not None:
            line, (_, reason) = hit
            if reason is None:
                active.append(
                    Finding(
                        rule="suppression-reason",
                        path=finding.path,
                        line=line,
                        message=(
                            f"suppression of [{finding.rule}] has no reason; write "
                            "`# lint: allow(" + finding.rule + "): <why this is fine>`"
                        ),
                    )
                )
            else:
                suppressed.append(finding)
        elif (
            finding.rule not in NON_BASELINABLE_RULES
            and baseline_keys
            and finding.key in baseline_keys
        ):
            baselined.append(finding)
        else:
            active.append(finding)
    return AnalysisResult(active, suppressed, baselined, time.monotonic() - t0)


def load_baseline(path: Path) -> set[str]:
    try:
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        return set()
    if not isinstance(data, dict):
        return set()
    return {key for key in data.get("findings", []) if isinstance(key, str)}


def write_baseline(path: Path, findings: Iterable[Finding | str]) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    payload = {
        "version": 1,
        "comment": (
            "grandfathered graftlint findings (docs/static-analysis.md); "
            "the goal is for this list to stay empty — fix or suppress "
            "inline with a reason instead of adding entries"
        ),
        "findings": sorted(
            {f if isinstance(f, str) else f.key for f in findings}
        ),
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")


def _default_root() -> Path:
    cwd = Path.cwd()
    if (cwd / "llm_training_tpu").is_dir():
        return cwd
    # fall back to the checkout this package was imported from
    return Path(__file__).resolve().parents[2]


def _changed_scan_paths(root: Path) -> list[str] | None:
    """Repo-relative .py files changed vs HEAD (worktree + staged +
    untracked), restricted to the default scan set. None when git is
    unavailable or errors — the caller then falls back to the full tree
    (scanning MORE than asked is the safe degradation)."""
    import subprocess

    changed: set[str] = set()
    for argv in (
        ["git", "-C", str(root), "diff", "--name-only", "HEAD", "--"],
        ["git", "-C", str(root), "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            proc = subprocess.run(
                argv, capture_output=True, text=True, timeout=30
            )
        except (OSError, subprocess.TimeoutExpired):
            return None
        if proc.returncode != 0:
            return None
        changed.update(line.strip() for line in proc.stdout.splitlines())
    scan_roots = tuple(
        entry + "/" for entry in DEFAULT_SCAN if not entry.endswith(".py")
    )
    scan_files = tuple(entry for entry in DEFAULT_SCAN if entry.endswith(".py"))
    return sorted(
        rel for rel in changed
        if rel.endswith(".py")
        and (rel.startswith(scan_roots) or rel in scan_files)
        and (root / rel).is_file()  # deletions need no scan
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m llm_training_tpu.analysis",
        description=(
            "graftlint: repo-native static analysis (the AST rules never "
            "import jax; `--races` runs the racecheck thread-model audit, "
            "also jax-free; `--audit` runs the shardcheck abstract-eval "
            "audit, which does import jax — CPU-only, zero FLOPs). "
            "Exit 0 = clean, 1 = findings, 2 = usage error."
        ),
        epilog=(
            "Suppress a finding with `# lint: allow(<rule>): <reason>` on the "
            "flagged line or the line above (the reason is mandatory). "
            "Grandfather existing debt with --update-baseline. "
            "Full rule docs: docs/static-analysis.md"
        ),
    )
    parser.add_argument(
        "paths",
        nargs="*",
        help=f"files/dirs to scan, relative to --root (default: {', '.join(DEFAULT_SCAN)})",
    )
    parser.add_argument(
        "--rules",
        help="comma-separated rule subset (see --list-rules); default: all",
    )
    parser.add_argument("--list-rules", action="store_true", help="print the rule table and exit")
    parser.add_argument("--json", action="store_true", help="machine-readable output")
    audit = parser.add_argument_group(
        "shardcheck audit",
        "`--audit` switches from AST lint to the abstract-eval sharding/"
        "layout/HBM audit (shard_audit.py): jax.eval_shape over every "
        "registered family's init, resolved against the mesh matrix. This "
        "mode DOES import jax (CPU, zero FLOPs) and uses its own baseline "
        "(config/audit_baseline.json).",
    )
    audit.add_argument(
        "--audit", action="store_true",
        help="run the family x mesh sharding/layout/HBM audit instead of the AST rules",
    )
    audit.add_argument(
        "--families", default=None,
        help="comma-separated family subset (default: all registered)",
    )
    audit.add_argument(
        "--meshes", default=None,
        help="comma-separated mesh-matrix subset (default: the full matrix)",
    )
    audit.add_argument(
        "--hbm-budget-gib", type=float, default=None,
        help="per-chip HBM budget the estimate is checked against (default 32)",
    )
    audit.add_argument(
        "--replicated-threshold-mib", type=float, default=None,
        help="tensors above this size may not resolve fully-replicated on "
        "param-capable meshes (default 4)",
    )
    races = parser.add_argument_group(
        "racecheck",
        "`--races` switches to the thread-model audit (racecheck.py): "
        "shared-state guarded-by contracts, lock-order cycles, and "
        "signal-handler safety, built from the AST's thread-entry graph. "
        "Jax-free like the lint, with its own baseline "
        f"(config/race_baseline.json). docs/static-analysis.md#racecheck.",
    )
    races.add_argument(
        "--races", action="store_true",
        help="run the thread-model race audit instead of the lint rules",
    )
    parser.add_argument(
        "--changed-only", action="store_true",
        help="scan only .py files changed vs git HEAD (plus untracked) — "
        "the fast local-commit mode; cross-file contract walks still parse "
        "the rest of the tree on demand, and CI/precommit keep the "
        "full-tree default",
    )
    parser.add_argument(
        "--root", type=Path, default=None, help="repo root (default: cwd if it holds llm_training_tpu/)"
    )
    parser.add_argument(
        "--baseline",
        type=Path,
        default=None,
        help=f"baseline file (default: <root>/{DEFAULT_BASELINE})",
    )
    parser.add_argument("--no-baseline", action="store_true", help="ignore the baseline file")
    parser.add_argument(
        "--update-baseline",
        action="store_true",
        help="write the current findings to the baseline and exit 0",
    )
    args = parser.parse_args(argv)

    rule_specs: list[RuleSpec] | None = None
    if args.races:
        from llm_training_tpu.analysis.racecheck import race_rules

        rule_specs = race_rules()

    if args.list_rules:
        for rule in rule_specs or all_rules():
            print(f"{rule.name:24s} {rule.description}")
        return 0

    root = (args.root or _default_root()).resolve()
    if not (root / "llm_training_tpu").is_dir():
        print(f"graftlint: {root} does not look like the repo root", file=sys.stderr)
        return 2
    if args.races and args.audit:
        print(
            "graftlint: --races and --audit are separate gates; run them "
            "separately",
            file=sys.stderr,
        )
        return 2
    if args.changed_only and args.audit:
        print(
            "graftlint: --changed-only scopes the AST scan set; the "
            "audit has no path scoping",
            file=sys.stderr,
        )
        return 2
    if args.changed_only and args.paths:
        print(
            "graftlint: --changed-only and explicit paths are "
            "mutually exclusive",
            file=sys.stderr,
        )
        return 2
    audit_only_flags = (
        args.families is not None
        or args.meshes is not None
        or args.hbm_budget_gib is not None
        or args.replicated_threshold_mib is not None
    )
    if args.audit:
        if args.paths or args.rules:
            # lint-only scoping must not be silently ignored: a user who
            # typed `--audit --rules ... path/` believes the run was scoped
            print(
                "graftlint: --audit takes --families/--meshes, not lint "
                "paths or --rules",
                file=sys.stderr,
            )
            return 2
        # the shardcheck audit imports jax (lazily, here only) — the plain
        # lint gate below stays jax-free
        from llm_training_tpu.analysis.shard_audit import audit_main

        return audit_main(args, root)
    if audit_only_flags:
        # the mirror mistake: audit scoping without --audit would silently
        # run the full AST lint and look like a passing scoped audit
        print(
            "graftlint: --families/--meshes/--hbm-budget-gib/"
            "--replicated-threshold-mib require --audit",
            file=sys.stderr,
        )
        return 2
    if args.changed_only:
        # AFTER every usage-flag validation: an invalid invocation must
        # exit 2 regardless of git diff state, never a state-dependent 0
        changed = _changed_scan_paths(root)
        if changed is None:
            print(
                "graftlint: git unavailable for --changed-only — falling "
                "back to the full tree",
                file=sys.stderr,
            )
        elif not changed:
            if args.json:
                # precommit tees this into audit/race record files the
                # report renders — an empty diff must still be valid JSON
                print(json.dumps({
                    "version": 1,
                    "mode": "races" if args.races else "lint",
                    "findings": [],
                    "suppressed": 0,
                    "baselined": 0,
                    "elapsed_s": 0.0,
                    "changed_only": "empty diff — nothing scanned",
                }))
            else:
                print(
                    "graftlint: OK — no changed .py files in the scan set "
                    "(--changed-only)"
                )
            return 0
        else:
            args.paths = changed
    gate = "racecheck" if args.races else "graftlint"
    default_baseline = DEFAULT_RACE_BASELINE if args.races else DEFAULT_BASELINE
    baseline_path = args.baseline or (root / default_baseline)
    baseline_keys = set() if args.no_baseline else load_baseline(baseline_path)

    try:
        result = run_analysis(
            root,
            paths=args.paths or None,
            rules=args.rules.split(",") if args.rules else None,
            baseline_keys=baseline_keys,
            rule_specs=rule_specs,
        )
    except ValueError as exc:
        print(f"{gate}: {exc}", file=sys.stderr)
        return 2

    if args.update_baseline:
        # still-firing grandfathered findings stay in the baseline — updating
        # must never un-grandfather debt the update didn't fix
        keep_keys = {
            f.key
            for f in result.findings + result.baselined
            if f.rule not in NON_BASELINABLE_RULES
        }
        if args.paths or args.rules:
            # a narrowed run (subset of paths OR rules) can't see findings
            # elsewhere; their grandfathered entries must survive untouched
            keep_keys |= baseline_keys
        write_baseline(baseline_path, keep_keys)
        print(
            f"{gate}: baseline updated with {len(keep_keys)} finding(s) "
            f"({len(result.baselined)} still firing, carried over) at {baseline_path}"
        )
        return 0

    if args.json:
        print(
            json.dumps(
                {
                    "version": 1,
                    "mode": "races" if args.races else "lint",
                    "findings": [
                        {
                            "rule": f.rule,
                            "path": f.path,
                            "line": f.line,
                            "message": f.message,
                            "key": f.key,
                        }
                        for f in result.findings
                    ],
                    "suppressed": len(result.suppressed),
                    "baselined": len(result.baselined),
                    "elapsed_s": round(result.elapsed_s, 3),
                }
            )
        )
        return 1 if result.findings else 0

    for finding in result.findings:
        print(finding.render())
    status = "FAIL" if result.findings else "OK"
    print(
        f"{gate}: {status} — {len(result.findings)} finding(s) "
        f"({len(result.suppressed)} suppressed, {len(result.baselined)} baselined) "
        f"in {result.elapsed_s:.2f}s"
    )
    if result.findings:
        print(
            "hint: fix the invariant, or suppress with "
            "`# lint: allow(<rule>): <reason>` on the flagged line (or the line "
            "above); docs/static-analysis.md documents every rule and the "
            "baseline workflow."
        )
    return 1 if result.findings else 0
