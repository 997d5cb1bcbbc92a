"""racecheck (--races) + thread-jax-free tests — docs/static-analysis.md#racecheck.

Same shape as test_analysis.py: minimal positive/negative AST fixtures per
rule, the whole-repo capstone (clean against an EMPTY committed baseline),
and a copied-tree acceptance test proving that seeding an unguarded
shared-mutation AND a lock-order inversion makes the gate exit 1 naming
the attribute, both entry threads, and the missing/violated lock. Nothing
here builds a jax program.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import textwrap
import time
from pathlib import Path

import pytest

from llm_training_tpu.analysis.engine import (
    DEFAULT_RACE_BASELINE,
    load_baseline,
    main,
    run_analysis,
)
from llm_training_tpu.analysis.racecheck import race_rules

REPO_ROOT = Path(__file__).resolve().parents[1]


def make_repo(tmp_path: Path, files: dict[str, str]) -> Path:
    base = {"llm_training_tpu/__init__.py": ""}
    base.update(files)
    for rel, content in base.items():
        target = tmp_path / rel
        target.parent.mkdir(parents=True, exist_ok=True)
        target.write_text(textwrap.dedent(content))
    return tmp_path


def race_findings(root: Path, rule: str | None = None):
    return run_analysis(
        root,
        rules=[rule] if rule else None,
        rule_specs=race_rules(),
    ).findings


# ---------------------------------------------------------------- rule table


def test_race_rule_table():
    names = [rule.name for rule in race_rules()]
    assert names == [
        "race-unguarded-shared",
        "race-lock-order",
        "race-signal-unsafe",
    ]


def test_whole_repo_races_clean_and_baseline_empty():
    """The acceptance bar: `--races` exits 0 at HEAD with an EMPTY
    committed baseline, in seconds."""
    t0 = time.monotonic()
    baseline = load_baseline(REPO_ROOT / DEFAULT_RACE_BASELINE)
    result = run_analysis(
        REPO_ROOT, baseline_keys=baseline, rule_specs=race_rules()
    )
    elapsed = time.monotonic() - t0
    assert result.findings == [], [f.render() for f in result.findings]
    assert baseline == set(), "race baseline must stay empty"
    assert elapsed < 15.0, f"race gate took {elapsed:.1f}s (budget 15s)"


def test_races_mode_never_imports_jax():
    code = (
        "import sys\n"
        "from llm_training_tpu.analysis.engine import main\n"
        "rc = main(['--races', '--list-rules'])\n"
        "leaked = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'jaxlib'))]\n"
        "assert rc == 0 and not leaked, (rc, leaked)\n"
        "print('RACES-JAXFREE-OK')\n"
    )
    proc = subprocess.run(
        ["python", "-c", code], cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "RACES-JAXFREE-OK" in proc.stdout


# ------------------------------------------------- race-unguarded-shared


_UNGUARDED = """
    import threading


    class Pump:
        def __init__(self):
            self._lock = threading.Lock()
            self._boxes = []

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            self._boxes.append(1)

        def add(self, item):
            self._boxes.append(item)
"""


def test_unguarded_shared_names_attr_and_both_entries(tmp_path):
    root = make_repo(tmp_path, {"llm_training_tpu/pump.py": _UNGUARDED})
    found = race_findings(root, "race-unguarded-shared")
    assert len(found) == 1, [f.render() for f in found]
    message = found[0].message
    assert "Pump._boxes" in message
    assert "thread:_run" in message and "main" in message
    assert "guarded by" in message


def test_declared_and_held_guard_passes(tmp_path):
    guarded = _UNGUARDED.replace(
        "            self._boxes = []",
        "            self._boxes = []  # guarded by: _lock",
    ).replace(
        "            self._boxes.append(1)",
        "            with self._lock:\n                self._boxes.append(1)",
    ).replace(
        "            self._boxes.append(item)",
        "            with self._lock:\n                self._boxes.append(item)",
    )
    root = make_repo(tmp_path, {"llm_training_tpu/pump.py": guarded})
    assert race_findings(root, "race-unguarded-shared") == []


def test_declared_guard_violated_names_the_lock_and_method(tmp_path):
    # declared, held in _run, but add() mutates outside the lock
    partially = _UNGUARDED.replace(
        "            self._boxes = []",
        "            self._boxes = []  # guarded by: _lock",
    ).replace(
        "            self._boxes.append(1)",
        "            with self._lock:\n                self._boxes.append(1)",
    )
    root = make_repo(tmp_path, {"llm_training_tpu/pump.py": partially})
    found = race_findings(root, "race-unguarded-shared")
    assert len(found) == 1, [f.render() for f in found]
    message = found[0].message
    assert "`Pump._boxes`" in message and "`add`" in message
    assert "`_lock`" in message


def test_declared_guard_must_be_a_real_lock(tmp_path):
    bogus = _UNGUARDED.replace(
        "            self._boxes = []",
        "            self._boxes = []  # guarded by: _no_such_lock",
    )
    root = make_repo(tmp_path, {"llm_training_tpu/pump.py": bogus})
    found = race_findings(root, "race-unguarded-shared")
    assert len(found) == 1
    assert "_no_such_lock" in found[0].message
    assert "not a Lock/RLock" in found[0].message


def test_caller_holds_contract_on_def_line(tmp_path):
    # the RequestJournal._append pattern: a private helper documented as
    # "caller holds the lock" — the def-line declaration grants it
    src = """
    import threading


    class Sink:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []  # guarded by: _lock

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._lock:
                self._push(1)

        def push(self, item):
            with self._lock:
                self._push(item)

        def _push(self, item):  # guarded by: _lock
            self._items.append(item)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/sink.py": src})
    assert race_findings(root, "race-unguarded-shared") == []


def test_lock_name_heuristic_is_word_boundary_only(tmp_path):
    # `_blocks`/`_clock` must never classify as locks via substring match
    # — that would silently drop BlockAllocator-style state from the
    # shared-mutation analysis (found by review, pinned here)
    src = """
    import threading


    class Pool:
        def __init__(self, blocks, clock):
            self._blocks = blocks
            self._clock = clock

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            self._blocks.append(1)

        def put(self, item):
            self._blocks.append(item)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/pool.py": src})
    found = race_findings(root, "race-unguarded-shared")
    assert len(found) == 1, [f.render() for f in found]
    assert "Pool._blocks" in found[0].message
    # the sanctioned injected-lock pattern (`self._lock = lock`) still
    # counts as a lock and guards its attrs
    injected = """
    import threading


    class Shared:
        def __init__(self, lock):
            self._lock = lock
            self._items = []  # guarded by: _lock

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._lock:
                self._items.append(1)

        def put(self, item):
            with self._lock:
                self._items.append(item)
    """
    root2 = make_repo(tmp_path / "ok", {"llm_training_tpu/shared.py": injected})
    assert race_findings(root2, "race-unguarded-shared") == []


def test_threadsafe_containers_are_exempt(tmp_path):
    src = """
    import queue
    import threading


    class Feeder:
        def __init__(self):
            self._queue = queue.Queue()
            self._stop = threading.Event()

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            self._queue.put(1)

        def stop(self):
            self._stop.set()
            self._queue.put(None)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/feeder.py": src})
    assert race_findings(root, "race-unguarded-shared") == []


def test_module_global_shared_requires_declaration(tmp_path):
    src = """
    import threading

    _active = None
    _active_lock = threading.Lock()


    def install(value):
        global _active
        with _active_lock:
            _active = value


    def reader_loop():
        return _active


    def start():
        threading.Thread(target=reader_loop, daemon=True).start()
    """
    root = make_repo(tmp_path, {"llm_training_tpu/hooks.py": src})
    found = race_findings(root, "race-unguarded-shared")
    assert len(found) == 1, [f.render() for f in found]
    assert "module global `_active`" in found[0].message
    declared = src.replace(
        "    _active = None",
        "    _active = None  # guarded by: _active_lock",
    )
    root2 = make_repo(tmp_path / "ok", {"llm_training_tpu/hooks.py": declared})
    assert race_findings(root2, "race-unguarded-shared") == []


def test_closure_shared_with_nested_thread_target(tmp_path):
    # the PR 12 shape: a nested reader thread mutating a plain list the
    # enclosing serve loop also drains
    src = """
    import threading


    def serve_loop(stream):
        pending = []

        def reader():
            for line in stream:
                pending.append(line)

        threading.Thread(target=reader, daemon=True).start()
        while pending:
            pending.pop()
    """
    root = make_repo(tmp_path, {"llm_training_tpu/loop.py": src})
    found = race_findings(root, "race-unguarded-shared")
    assert len(found) == 1, [f.render() for f in found]
    assert "closure variable `pending`" in found[0].message
    assert "thread:reader" in found[0].message
    # the sanctioned queue handoff is silent
    fixed = src.replace("pending = []", "import queue\n        pending = queue.Queue()").replace(
        "pending.append(line)", "pending.put(line)"
    ).replace("while pending:\n            pending.pop()", "pending.get()")
    root2 = make_repo(tmp_path / "ok", {"llm_training_tpu/loop.py": fixed})
    assert race_findings(root2, "race-unguarded-shared") == []


def test_signal_entries_do_not_demand_locks(tmp_path):
    # a handler setting a flag the main loop polls is THE sanctioned
    # pattern — locks are the wrong tool in a handler (reentrancy)
    src = """
    import os
    import signal


    class Shutdown:
        def __init__(self):
            self._requested = False

        def install(self):
            signal.signal(signal.SIGTERM, self._handler)

        def _handler(self, signum, frame):
            self._requested = True
            os.write(2, b"shutting down\\n")

        @property
        def requested(self):
            return self._requested
    """
    root = make_repo(tmp_path, {"llm_training_tpu/sd.py": src})
    assert race_findings(root) == []


# ----------------------------------------------------- race-lock-order


_INVERSION = """
    import threading


    class Twisty:
        def __init__(self):
            self._a = threading.Lock()
            self._b = threading.Lock()

        def start(self):
            threading.Thread(target=self._run, daemon=True).start()

        def _run(self):
            with self._a:
                with self._b:
                    pass

        def poke(self):
            with self._b:
                with self._a:
                    pass
"""


def test_lock_order_inversion_is_flagged(tmp_path):
    root = make_repo(tmp_path, {"llm_training_tpu/twisty.py": _INVERSION})
    found = race_findings(root, "race-lock-order")
    assert len(found) == 1, [f.render() for f in found]
    message = found[0].message
    assert "Twisty._a" in message and "Twisty._b" in message
    assert "deadlock" in message


def test_consistent_lock_order_passes(tmp_path):
    consistent = _INVERSION.replace(
        "            with self._b:\n                with self._a:",
        "            with self._a:\n                with self._b:",
    )
    root = make_repo(tmp_path, {"llm_training_tpu/twisty.py": consistent})
    assert race_findings(root, "race-lock-order") == []


def test_lock_order_through_method_calls(tmp_path):
    # one hop of call propagation: _run holds _a and calls helper() which
    # acquires _b; poke nests them the other way
    src = _INVERSION.replace(
        "            with self._a:\n                with self._b:\n                    pass",
        "            with self._a:\n                self.helper()\n\n"
        "    def helper(self):\n            with self._b:\n                pass",
    )
    root = make_repo(tmp_path, {"llm_training_tpu/twisty.py": src})
    found = race_findings(root, "race-lock-order")
    assert len(found) == 1, [f.render() for f in found]


def test_single_threaded_modules_never_report_lock_order(tmp_path):
    solo = _INVERSION.replace(
        "        def start(self):\n"
        "            threading.Thread(target=self._run, daemon=True).start()\n\n",
        "",
    )
    root = make_repo(tmp_path, {"llm_training_tpu/twisty.py": solo})
    assert race_findings(root, "race-lock-order") == []


# --------------------------------------------------- race-signal-unsafe


def test_signal_handler_unsafe_work_is_flagged(tmp_path):
    src = """
    import logging
    import signal
    import threading

    logger = logging.getLogger(__name__)


    class Bad:
        def __init__(self):
            self._lock = threading.Lock()

        def install(self):
            signal.signal(signal.SIGTERM, self._handler)

        def _handler(self, signum, frame):
            print("dying")
            with self._lock:
                pass
            self._log_it()

        def _log_it(self):
            logger.warning("handled")
    """
    root = make_repo(tmp_path, {"llm_training_tpu/bad.py": src})
    found = race_findings(root, "race-signal-unsafe")
    whats = "\n".join(f.message for f in found)
    assert "print()" in whats
    assert "lock `_lock`" in whats
    assert "logging" in whats
    assert all("Bad._handler" in f.message for f in found)


def test_signal_handler_os_write_pattern_is_clean(tmp_path):
    src = """
    import os
    import signal


    def _handler(signum, frame):
        os.write(2, b"caught\\n")
        signal.raise_signal(signum)


    def install():
        signal.signal(signal.SIGTERM, _handler)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/ok.py": src})
    assert race_findings(root, "race-signal-unsafe") == []


# ---------------------------------------------------------------- CLI modes


def test_races_cli_json_baseline_and_exit_codes(tmp_path, capsys):
    root = make_repo(tmp_path, {"llm_training_tpu/pump.py": _UNGUARDED})
    rc = main(["--root", str(root), "--races", "--no-baseline", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["mode"] == "races"
    assert payload["findings"][0]["rule"] == "race-unguarded-shared"
    # baseline workflow (config/race_baseline.json, kept separate from lint)
    assert main(["--root", str(root), "--races", "--update-baseline"]) == 0
    assert load_baseline(root / DEFAULT_RACE_BASELINE)
    assert not (root / "config/lint_baseline.json").exists()
    assert main(["--root", str(root), "--races"]) == 0  # grandfathered
    assert main(["--root", str(root), "--races", "--no-baseline"]) == 1
    # the two audits stay separate gates
    assert main(["--root", str(root), "--races", "--audit"]) == 2
    capsys.readouterr()


def test_races_suppression_with_reason(tmp_path):
    suppressed = _UNGUARDED.replace(
        "            self._boxes = []",
        "            # lint: allow(race-unguarded-shared): fixture proves the suppression path\n"
        "            self._boxes = []",
    )
    root = make_repo(tmp_path, {"llm_training_tpu/pump.py": suppressed})
    result = run_analysis(root, rule_specs=race_rules())
    assert result.findings == [], [f.render() for f in result.findings]
    assert len(result.suppressed) == 1


def test_copied_tree_acceptance_seeded_races_exit_1(tmp_path, capsys):
    """Acceptance: seeding an unguarded shared mutation AND a lock-order
    inversion into a copy of the real tree makes `--races` exit 1, naming
    the attribute, both entry threads, and the lock."""
    root = tmp_path / "copy"
    for rel in ("llm_training_tpu", "scripts", "chip_smoke.py", "config"):
        src = REPO_ROOT / rel
        if src.is_dir():
            shutil.copytree(
                src, root / rel, ignore=shutil.ignore_patterns("__pycache__")
            )
        else:
            root.mkdir(parents=True, exist_ok=True)
            shutil.copy(src, root / rel)
    target = root / "llm_training_tpu/resilience/watchdog.py"
    target.write_text(target.read_text() + textwrap.dedent(_UNGUARDED) + textwrap.dedent(_INVERSION))
    rc = main([
        "--root", str(root), "--races",
        "llm_training_tpu/resilience",  # narrowed scan keeps the test fast
    ])
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "Pump._boxes" in out  # the attribute
    assert "thread:_run" in out and "main" in out  # both entry threads
    assert "guarded by" in out  # the missing lock
    assert "Twisty._a" in out and "Twisty._b" in out  # the inversion


# ------------------------------------------------------------ --changed-only


def _git(root: Path, *argv: str) -> None:
    subprocess.run(
        ["git", "-C", str(root), "-c", "user.email=t@t", "-c", "user.name=t",
         *argv],
        check=True, capture_output=True, timeout=30,
    )


def test_changed_only_scopes_the_scan_to_the_diff(tmp_path, capsys):
    root = make_repo(tmp_path, {
        "llm_training_tpu/pump.py": _UNGUARDED,
        "llm_training_tpu/other.py": "X = 1\n",
    })
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    # the committed violation is invisible when only other.py changed
    (root / "llm_training_tpu/other.py").write_text("X = 2\n")
    assert main(["--root", str(root), "--races", "--changed-only"]) == 0
    # ...and visible again once pump.py itself is in the diff
    (root / "llm_training_tpu/pump.py").write_text(
        (root / "llm_training_tpu/pump.py").read_text() + "\n"
    )
    assert main(["--root", str(root), "--races", "--changed-only"]) == 1
    # a clean tree short-circuits with exit 0
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "wip")
    rc = main(["--root", str(root), "--changed-only"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no changed .py files" in out


def test_changed_only_rejects_explicit_paths(tmp_path, capsys):
    root = make_repo(tmp_path, {})
    rc = main(["--root", str(root), "--changed-only", "llm_training_tpu"])
    assert rc == 2
    assert "mutually exclusive" in capsys.readouterr().err


def test_changed_only_usage_errors_beat_the_empty_diff_short_circuit(
    tmp_path, capsys
):
    """Invalid flag combinations must exit 2 regardless of git diff state
    — a clean worktree must never turn a usage error into a silent 0
    (review finding, pinned)."""
    root = make_repo(tmp_path, {"llm_training_tpu/clean.py": "X = 1\n"})
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    rc = main(["--root", str(root), "--changed-only", "--families", "llama"])
    assert rc == 2
    assert "require --audit" in capsys.readouterr().err


def test_changed_only_keeps_cross_module_reachability(tmp_path):
    """A changed file spawning a thread whose target lives in an UNCHANGED
    jax-importing module must still fail under the narrowed scan — the
    call graph resolves out-of-scan modules on demand (review finding,
    pinned)."""
    root = make_repo(tmp_path, {
        "llm_training_tpu/worker.py": (
            "import jax\n\n\ndef worker():\n    jax.device_put(1)\n"
        ),
        "llm_training_tpu/spawner.py": (
            "import threading\n\n"
            "from llm_training_tpu.worker import worker\n\n\n"
            "def start():\n"
            "    threading.Thread(target=worker, daemon=True).start()\n"
        ),
    })
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    # only the spawner is in the diff; the violation is in worker.py
    (root / "llm_training_tpu/spawner.py").write_text(
        (root / "llm_training_tpu/spawner.py").read_text() + "\n"
    )
    assert main([
        "--root", str(root), "--changed-only", "--no-baseline",
        "--rules", "thread-jax-free",
    ]) == 1


def test_changed_only_untracked_files_are_scanned(tmp_path):
    root = make_repo(tmp_path, {"llm_training_tpu/clean.py": "X = 1\n"})
    _git(root, "init", "-q")
    _git(root, "add", "-A")
    _git(root, "commit", "-qm", "seed")
    (root / "llm_training_tpu/pump.py").write_text(textwrap.dedent(_UNGUARDED))
    assert main(["--root", str(root), "--races", "--changed-only"]) == 1


# ------------------------------------------------------- thread-jax-free


def test_thread_jax_free_flags_thread_targets(tmp_path):
    src = """
    import threading

    import jax


    def worker():
        jax.device_put(1)


    def start():
        threading.Thread(target=worker, daemon=True).start()
    """
    root = make_repo(tmp_path, {"llm_training_tpu/w.py": src})
    found = run_analysis(root, rules=["thread-jax-free"]).findings
    assert len(found) == 1, [f.render() for f in found]
    assert "thread:worker" in found[0].message
    assert "jax" in found[0].message


def test_thread_jax_free_flags_lazy_imports_and_transitive_calls(tmp_path):
    src = """
    import threading


    def helper():
        import jax

        return jax.devices()


    def worker():
        helper()


    def start():
        threading.Thread(target=worker, daemon=True).start()
    """
    root = make_repo(tmp_path, {"llm_training_tpu/w.py": src})
    found = run_analysis(root, rules=["thread-jax-free"]).findings
    # both the lazy `import jax` and the call through its alias land in
    # the transitively-reached helper
    assert found, [f.render() for f in found]
    assert all("`helper`" in f.message for f in found)
    assert any("import jax" in f.message for f in found)


def test_thread_jax_free_ignores_main_thread_jax(tmp_path):
    src = """
    import threading

    import jax


    def step():
        return jax.jit(lambda x: x)(1)


    def worker():
        pass


    def start():
        threading.Thread(target=worker, daemon=True).start()
    """
    root = make_repo(tmp_path, {"llm_training_tpu/w.py": src})
    assert run_analysis(root, rules=["thread-jax-free"]).findings == []


def test_thread_jax_free_real_tree_only_sanctioned_suppression():
    """The whole-tree rule run: the only jax-on-a-thread site is the
    prefetcher's suppressed device_put."""
    result = run_analysis(REPO_ROOT, rules=["thread-jax-free"])
    assert result.findings == [], [f.render() for f in result.findings]
    assert any(
        "prefetch" in f.path for f in result.suppressed
    ), "expected the sanctioned prefetcher suppression to be exercised"


# ------------------------------------------------------- report race line


def test_report_audit_section_renders_race_gate(tmp_path):
    from llm_training_tpu.telemetry.report import _audit_section

    races = ({
        "version": 1, "mode": "races", "findings": [], "suppressed": 1,
        "baselined": 0, "elapsed_s": 1.0,
    }, "race.json")
    lines = _audit_section(None, races, {})
    text = "\n".join(lines)
    assert "== Audit ==" in text
    assert "racecheck: OK — 0 finding(s)" in text

    failing = ({
        "version": 1, "mode": "races",
        "findings": [{"rule": "race-unguarded-shared", "path": "x.py",
                      "line": 1, "message": "m", "key": "k"}],
        "suppressed": 0, "baselined": 2, "elapsed_s": 1.0,
    }, "race.json")
    text = "\n".join(_audit_section(None, failing, {}))
    assert "racecheck: FAIL — 1 finding(s)" in text
    assert "race-unguarded-shared x1" in text

    # honest degrade on malformed record
    text = "\n".join(_audit_section(None, ({"findings": "junk"}, "race.json"), {}))
    assert "racecheck" in text and "unreadable" in text

    # absent record: no racecheck line, and no crash
    assert _audit_section(None, None, {}) == []


def test_report_run_dir_race_json_end_to_end(tmp_path):
    from llm_training_tpu.telemetry.report import render_report

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    (run_dir / "metrics.jsonl").write_text(
        json.dumps({"step": 1, "loss": 2.0}) + "\n"
    )
    (run_dir / "race.json").write_text(json.dumps({
        "version": 1, "mode": "races", "findings": [], "suppressed": 0,
        "baselined": 0, "elapsed_s": 0.5,
    }))
    rendered = render_report(run_dir)
    assert "== Audit ==" in rendered
    assert "racecheck: OK" in rendered
