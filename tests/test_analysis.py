"""graftlint (llm_training_tpu.analysis) tests — docs/static-analysis.md.

Pure-AST fixtures: each rule gets a minimal positive (a violation the rule
must flag — including a reconstruction of the exact BENCH_r04 `_dq_kernel`
two-missing-refs arity bug) and a negative (the sanctioned pattern passes).
The capstone is the whole-repo run: the real tree must produce ZERO
unbaselined findings, in under 10 seconds, without the analysis package
ever importing jax. None of these tests build a jax program, so the whole
module adds ~nothing to the tier-1 time budget.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

from llm_training_tpu.analysis import contracts
from llm_training_tpu.analysis.engine import (
    DEFAULT_BASELINE,
    DEFAULT_SCAN,
    RepoContext,
    all_rules,
    load_baseline,
    main,
    run_analysis,
    write_baseline,
)

REPO_ROOT = Path(__file__).resolve().parents[1]

_DEFAULT_LOGGERS = """
TELEMETRY_PREFIXES = ("goodput/", "decode/", "flash/")
TELEMETRY_KEYS = ("compile_time_s",)
"""

# the logical-axis registry the `logical-axis-literal` rule parses out of
# the sharding file's AST (fixture trees get a tiny stand-in)
_DEFAULT_SHARDING = """
KNOWN_LOGICAL_AXES: tuple[str, ...] = (
    "batch", "embed", "mlp", "norm", "layers", "stages",
)
"""


def make_repo(tmp_path: Path, files: dict[str, str]) -> Path:
    """A minimal tree the engine accepts as a repo root: package inits, the
    telemetry routing file, and empty stubs for every declared jax-free
    contract file (so fixture trees don't trip the missing-contract check),
    overlaid with the test's own files."""
    base = {
        "llm_training_tpu/__init__.py": "",
        "llm_training_tpu/callbacks/__init__.py": "",
        "llm_training_tpu/callbacks/loggers.py": _DEFAULT_LOGGERS,
        "llm_training_tpu/parallel/__init__.py": "",
        "llm_training_tpu/parallel/sharding.py": _DEFAULT_SHARDING,
        "docs/performance.md": "env table: LLMT_DOCUMENTED, FLASH_DOCUMENTED\n",
    }
    for contract_rel in contracts.JAX_FREE_CONTRACTS:
        base.setdefault(contract_rel, "")
        init = Path(contract_rel).parent / "__init__.py"
        if str(init) != ".":
            base.setdefault(init.as_posix(), "")
    base.update(files)
    for rel, content in base.items():
        target = tmp_path / rel
        target.parent.mkdir(parents=True, exist_ok=True)
        target.write_text(textwrap.dedent(content))
    return tmp_path


def findings_for(root: Path, rule: str | None = None, **kwargs):
    rules = [rule] if rule else None
    return run_analysis(root, rules=rules, **kwargs).findings


# --------------------------------------------------------------- engine


def test_rule_table_has_the_seven_rules():
    names = [rule.name for rule in all_rules()]
    assert names == [
        "pallas-kernel-arity",
        "jax-free-import",
        "host-sync",
        "telemetry-prefix",
        "env-doc-drift",
        "logical-axis-literal",
        "thread-jax-free",
    ]


def test_whole_repo_is_clean_and_fast():
    """The committed tree lints clean against the committed baseline (which
    must stay empty — debt goes through inline suppressions with reasons)."""
    t0 = time.monotonic()
    baseline = load_baseline(REPO_ROOT / DEFAULT_BASELINE)
    result = run_analysis(REPO_ROOT, baseline_keys=baseline)
    elapsed = time.monotonic() - t0
    assert result.findings == [], [f.render() for f in result.findings]
    assert baseline == set(), "baseline must stay empty; fix or suppress inline"
    assert elapsed < 10.0, f"lint gate took {elapsed:.1f}s (budget 10s)"


def test_default_scan_names_only_paths_that_exist():
    """`RepoContext._discover` passes over a path that is not there, so a
    deleted entry script would leave the scan set in silence: every entry
    of `DEFAULT_SCAN` is a file or a directory of this tree."""
    assert [entry for entry in DEFAULT_SCAN if not (REPO_ROOT / entry).exists()] == []


def test_env_doc_prefixes_are_the_ones_the_tree_reads():
    """The env-doc rule demands documents for the namespaces the tree reads
    and for no other: each prefix of `ENV_VAR_PATTERN` names at least one
    variable some scanned file reads, and no scanned file reads a variable
    of the deleted bench's namespace."""
    import ast
    import re

    from llm_training_tpu.analysis.env_docs import _docstring_ids

    prefixes = re.match(r"\^\(([A-Z|]+)\)_", contracts.ENV_VAR_PATTERN).group(1).split("|")
    read: set[str] = set()
    for parsed in RepoContext(REPO_ROOT).files:
        docstrings = _docstring_ids(parsed.tree)
        read.update(
            node.value for node in ast.walk(parsed.tree)
            if isinstance(node, ast.Constant) and isinstance(node.value, str)
            and id(node) not in docstrings and re.fullmatch(r"[A-Z]+(?:_[A-Z0-9]+)+", node.value)
        )
    assert sorted(name for name in read if name.startswith("BENCH_")) == []
    for prefix in prefixes:
        assert any(name.startswith(prefix + "_") for name in read), prefix


def test_analysis_package_never_imports_jax():
    """The acceptance bar: the gate runs before any backend exists."""
    code = (
        "import sys\n"
        "from llm_training_tpu.analysis.engine import main\n"
        "rc = main(['--list-rules'])\n"
        "leaked = [m for m in sys.modules if m == 'jax' or m.startswith(('jax.', 'jaxlib'))]\n"
        "assert rc == 0 and not leaked, (rc, leaked)\n"
        "print('JAXFREE-OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "JAXFREE-OK" in proc.stdout


# ------------------------------------------------- rule: pallas-kernel-arity

# the exact BENCH_r04 shape: `_dq_kernel() missing 2 required positional
# arguments: 'dq_ref' and 'dq_scr'` — the kernel binds 12 refs, the call's
# specs imply 10 (2 prefetch + 6 in_specs + 1 out + 1 scratch)
_R04_FIXTURE = """
    import functools
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu


    def _dq_kernel(seg_lo_ref, seg_hi_ref, q_seg_ref, kv_seg_ref, q_ref,
                   k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
                   *, scale, causal):
        pass


    def flash_bwd(q, k, v, do, lse, delta, seg_lo, seg_hi, seg_q, seg_kv):
        return pl.pallas_call(
            functools.partial(_dq_kernel, scale=1.0, causal=True),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(8, 4, 4),
                in_specs=[
                    pl.BlockSpec((1, 1, 128), lambda b, i, j, lo, hi: (b, 0, i)),
                    pl.BlockSpec((1, 1, 128), lambda b, i, j, lo, hi: (b, 0, j)),
                    pl.BlockSpec((1, 128, 64), lambda b, i, j, lo, hi: (b, i, 0)),
                    pl.BlockSpec((1, 128, 64), lambda b, i, j, lo, hi: (b, j, 0)),
                    pl.BlockSpec((1, 128, 64), lambda b, i, j, lo, hi: (b, j, 0)),
                    pl.BlockSpec((1, 128, 64), lambda b, i, j, lo, hi: (b, i, 0)),
                ],
                out_specs=pl.BlockSpec((1, 128, 64), lambda b, i, j, lo, hi: (b, i, 0)),
                scratch_shapes=[pltpu.VMEM((128, 64), jax.numpy.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        )(seg_lo, seg_hi, seg_q, seg_kv, q, k, v, do)
"""


def test_arity_flags_the_r04_two_missing_refs_bug(tmp_path):
    root = make_repo(tmp_path, {"llm_training_tpu/kern.py": _R04_FIXTURE})
    found = findings_for(root, "pallas-kernel-arity")
    assert len(found) == 1, [f.render() for f in found]
    message = found[0].message
    assert "_dq_kernel" in message
    assert "2 ref(s) missing" in message
    assert "BENCH_r04" in message


def test_arity_passes_once_the_two_refs_are_restored(tmp_path):
    # the shipped fix: two more in_specs (lse/delta rows) make 12 == 12
    fixed = _R04_FIXTURE.replace(
        "                ],\n                out_specs=",
        "                    pl.BlockSpec((1, 1, 128), lambda b, i, j, lo, hi: (b, 0, i)),\n"
        "                    pl.BlockSpec((1, 1, 128), lambda b, i, j, lo, hi: (b, 0, i)),\n"
        "                ],\n                out_specs=",
        1,
    )
    assert fixed != _R04_FIXTURE
    root = make_repo(tmp_path, {"llm_training_tpu/kern.py": fixed})
    assert findings_for(root, "pallas-kernel-arity") == []


def test_arity_flags_extra_refs(tmp_path):
    src = """
    from jax.experimental import pallas as pl
    import jax


    def k(a_ref, o_ref):
        pass


    def call(x):
        return pl.pallas_call(
            k,
            in_specs=[pl.BlockSpec((8,), lambda i: (i,)),
                      pl.BlockSpec((8,), lambda i: (i,))],
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        )(x, x)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/kern.py": src})
    found = findings_for(root, "pallas-kernel-arity")
    assert len(found) == 1 and "extra ref(s)" in found[0].message


def test_arity_tolerates_vararg_kernels_and_conditional_appends(tmp_path):
    # the flash forward pattern: specs built as a local with a conditional
    # append, kernel absorbing the tail in *rest — provably consistent
    src = """
    from jax.experimental import pallas as pl
    import jax


    def k(a_ref, b_ref, *rest, flag=False):
        pass


    def call(x, extra):
        in_specs = [pl.BlockSpec((8,), lambda i: (i,)),
                    pl.BlockSpec((8,), lambda i: (i,))]
        args = [x, x]
        if extra is not None:
            in_specs.append(pl.BlockSpec((8,), lambda i: (i,)))
            args.append(extra)
        return pl.pallas_call(
            k,
            in_specs=in_specs,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        )(*args)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/kern.py": src})
    assert findings_for(root, "pallas-kernel-arity") == []


def test_arity_degrades_to_silence_on_extend_and_augassign(tmp_path):
    # only single-element .append widens the count; .extend/+= make it
    # unknowable and must NEVER produce a false "refs missing" alarm
    src = """
    from jax.experimental import pallas as pl
    import jax


    def k(a_ref, b_ref, c_ref, o_ref):
        pass


    def call(x):
        in_specs = [pl.BlockSpec((8,), lambda i: (i,))]
        in_specs.extend([pl.BlockSpec((8,), lambda i: (i,)),
                         pl.BlockSpec((8,), lambda i: (i,))])
        return pl.pallas_call(
            k,
            in_specs=in_specs,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        )(x, x, x)


    def call2(x):
        in_specs = [pl.BlockSpec((8,), lambda i: (i,))]
        in_specs += [pl.BlockSpec((8,), lambda i: (i,)),
                     pl.BlockSpec((8,), lambda i: (i,))]
        return pl.pallas_call(
            k,
            in_specs=in_specs,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        )(x, x, x)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/kern.py": src})
    assert findings_for(root, "pallas-kernel-arity") == []


def test_arity_negative_on_the_real_kernels():
    """The current (fixed) flash + paged kernels pass the rule."""
    found = run_analysis(
        REPO_ROOT, paths=["llm_training_tpu/ops/pallas"], rules=["pallas-kernel-arity"]
    ).findings
    assert found == [], [f.render() for f in found]


# ------------------------------------------------- rule: jax-free-import


def test_contract_flags_module_level_jax_import(tmp_path):
    root = make_repo(
        tmp_path,
        {"llm_training_tpu/resilience/supervisor.py": "import jax\n"},
    )
    found = findings_for(root, "jax-free-import")
    assert any(
        f.path == "llm_training_tpu/resilience/supervisor.py"
        and "module-level import of 'jax'" in f.message
        for f in found
    ), [f.render() for f in found]


def test_contract_allows_lazy_and_type_checking_imports(tmp_path):
    root = make_repo(
        tmp_path,
        {
            "llm_training_tpu/resilience/supervisor.py": """
            from typing import TYPE_CHECKING

            if TYPE_CHECKING:
                import jax

            def probe():
                import jax  # lazy: the sanctioned pattern

                return jax.devices()
            """
        },
    )
    assert findings_for(root, "jax-free-import") == []


def test_contract_walks_transitive_chains_through_package_inits(tmp_path):
    # supervisor -> (package __init__ of .helpers executes) -> helper pulls jax
    root = make_repo(
        tmp_path,
        {
            "llm_training_tpu/resilience/supervisor.py": (
                "from llm_training_tpu.helpers.util import f\n"
            ),
            "llm_training_tpu/helpers/__init__.py": (
                "from llm_training_tpu.helpers.heavy import g\n"
            ),
            "llm_training_tpu/helpers/util.py": "def f():\n    return 1\n",
            "llm_training_tpu/helpers/heavy.py": "import jax\n\ndef g():\n    pass\n",
        },
    )
    found = [
        f
        for f in findings_for(root, "jax-free-import")
        if f.path == "llm_training_tpu/resilience/supervisor.py"
    ]
    assert len(found) == 1
    assert "llm_training_tpu/helpers/heavy.py" in found[0].message
    assert found[0].line == 1  # the import in the contract module that starts the chain


def test_contract_checks_the_modules_own_package_init_chain(tmp_path):
    # importing the contract module executes its ancestor __init__s first;
    # a jax import there breaks the contract even when the contract file
    # itself imports nothing from the repo
    root = make_repo(
        tmp_path,
        {
            "llm_training_tpu/resilience/supervisor.py": (
                "def probe():\n    import jax\n    return jax.devices()\n"
            ),
            "llm_training_tpu/resilience/__init__.py": "import jax\n",
        },
    )
    found = [
        f
        for f in findings_for(root, "jax-free-import")
        if f.path == "llm_training_tpu/resilience/supervisor.py"
    ]
    assert len(found) == 1, [f.render() for f in found]
    assert "llm_training_tpu/resilience/__init__.py" in found[0].message


def test_arity_handles_module_scope_spec_lists(tmp_path):
    # specs assigned AND mutated at module scope, used inside a function:
    # the append is in the owning scope, so the count stays provable (3)
    src = """
    from jax.experimental import pallas as pl
    import jax

    IN_SPECS = [pl.BlockSpec((8,), lambda i: (i,)),
                pl.BlockSpec((8,), lambda i: (i,))]
    IN_SPECS.append(pl.BlockSpec((8,), lambda i: (i,)))


    def k(a_ref, b_ref, c_ref, o_ref):
        pass


    def call(x):
        return pl.pallas_call(
            k,
            in_specs=IN_SPECS,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        )(x, x, x)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/kern.py": src})
    assert findings_for(root, "pallas-kernel-arity") == []


def test_update_baseline_with_narrow_paths_keeps_outside_entries(tmp_path, capsys):
    root = make_repo(
        tmp_path,
        {
            "chip_smoke.py": "import jax\n",
            "llm_training_tpu/other/__init__.py": "",
        },
    )
    baseline = root / "config/lint_baseline.json"
    assert main(["--root", str(root), "--update-baseline"]) == 0  # full scan
    assert main(["--root", str(root)]) == 0  # grandfathered
    # a narrow-path update must not drop the chip_smoke.py entry it cannot see.
    # (scanning a path with no contract files would still WALK chip_smoke.py via
    # the contract table, so also restrict to a rule that never leaves the
    # scan set — the hostile case for entry preservation)
    assert main(
        [
            "--root",
            str(root),
            "--update-baseline",
            "--rules",
            "telemetry-prefix",
            "llm_training_tpu/other",
        ]
    ) == 0
    assert load_baseline(baseline), "narrow update dropped the outside entry"
    assert main(["--root", str(root)]) == 0  # still grandfathered
    capsys.readouterr()


def test_contract_sees_imports_inside_match_statements(tmp_path):
    root = make_repo(
        tmp_path,
        {
            "llm_training_tpu/resilience/supervisor.py": (
                "import os\n"
                "match os.environ.get('X'):\n"
                "    case '1':\n"
                "        import jax\n"
                "    case _:\n"
                "        pass\n"
            )
        },
    )
    found = findings_for(root, "jax-free-import")
    assert any("module-level import of 'jax'" in f.message for f in found), [
        f.render() for f in found
    ]


def test_update_baseline_with_narrow_rules_keeps_other_rules_entries(tmp_path, capsys):
    root = make_repo(
        tmp_path,
        {
            "chip_smoke.py": "import jax\n",
        },
    )
    baseline = root / "config/lint_baseline.json"
    assert main(["--root", str(root), "--update-baseline"]) == 0  # full
    assert main(["--root", str(root)]) == 0
    # updating under a single rule must not drop the other rules' entries
    assert main(
        ["--root", str(root), "--update-baseline", "--rules", "telemetry-prefix"]
    ) == 0
    assert load_baseline(baseline), "rule-narrowed update dropped entries"
    assert main(["--root", str(root)]) == 0
    capsys.readouterr()


def test_real_supervisor_contract_holds_and_breaks_when_jax_is_added(tmp_path):
    """Acceptance: adding `import jax` to resilience/supervisor.py makes the
    gate exit nonzero naming the rule and location. Run on a copied tree so
    the real one stays untouched."""
    import shutil

    root = tmp_path / "copy"
    for rel in ("llm_training_tpu", "scripts", "chip_smoke.py", "docs", "README.md"):
        src = REPO_ROOT / rel
        if src.is_dir():
            shutil.copytree(src, root / rel, ignore=shutil.ignore_patterns("__pycache__"))
        else:
            root.mkdir(parents=True, exist_ok=True)
            shutil.copy(src, root / rel)
    sup = root / "llm_training_tpu/resilience/supervisor.py"
    sup.write_text("import jax\n" + sup.read_text())
    # narrow scan paths keep the test fast; the contract walk parses the
    # rest of the tree on demand regardless
    rc = main(
        [
            "--root",
            str(root),
            "--no-baseline",
            "--rules",
            "jax-free-import",
            "llm_training_tpu/resilience",
        ]
    )
    assert rc == 1


# ------------------------------------------------- rule: host-sync

_HOST_SYNC_FIXTURE = """
    import jax
    import jax.numpy as jnp


    def helper(x):
        return x.item()


    def step(params, batch):
        loss = helper(params)
        denom = float(jnp.sum(batch))
        static = float(1e-6)  # plain python float() stays legal
        return loss, denom, static


    stepped = jax.jit(step)


    def unreached(x):
        return x.item()  # not reachable from any jitted entry: not flagged
"""


def test_host_sync_flags_item_and_jax_float_in_reachable_code(tmp_path):
    root = make_repo(tmp_path, {"llm_training_tpu/step.py": _HOST_SYNC_FIXTURE})
    found = findings_for(root, "host-sync")
    rendered = [f.render() for f in found]
    assert len(found) == 2, rendered
    assert any(".item()" in f.message and "`helper`" in f.message for f in found)
    assert any("float(<jax expression>)" in f.message for f in found)
    # the unreached function's .item() stays silent
    assert not any("`unreached`" in f.message for f in found), rendered


def test_host_sync_suppression_requires_a_reason(tmp_path):
    suppressed = _HOST_SYNC_FIXTURE.replace(
        "return x.item()\n",
        "return x.item()  # lint: allow(host-sync): eval-only helper, never jitted hot\n",
        1,
    ).replace(
        "denom = float(jnp.sum(batch))",
        "denom = float(jnp.sum(batch))  # lint: allow(host-sync)",
    )
    root = make_repo(tmp_path, {"llm_training_tpu/step.py": suppressed})
    result = run_analysis(root, rules=["host-sync"])
    # the reasoned suppression silences its finding; the reasonless one
    # converts into a suppression-reason finding
    assert len(result.suppressed) == 1
    assert [f.rule for f in result.findings] == ["suppression-reason"]
    assert "no reason" in result.findings[0].message


def test_host_sync_bare_names_skip_class_scope(tmp_path):
    # Python scoping: a method's bare `helper(x)` resolves to the module
    # function, never to an unrelated sibling method of the same name
    src = """
    import jax


    def helper(x):
        return x + 1


    class T:
        def helper(self):
            print("never reached via bare-name call")

        def step(self, x):
            return helper(x)

        def compile(self):
            return jax.jit(self.step)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/cls.py": src})
    assert findings_for(root, "host-sync") == []


def test_host_sync_follows_factory_built_steps(tmp_path):
    # the trainer pattern: jax.jit(self._build_step(...)) where the builder
    # returns a closure
    src = """
    import jax


    class Trainer:
        def _build_step(self):
            def train_step(state, batch):
                print("step!", state)
                return state

            return train_step

        def compile(self):
            return jax.jit(self._build_step(), donate_argnums=0)
    """
    root = make_repo(tmp_path, {"llm_training_tpu/tr.py": src})
    found = findings_for(root, "host-sync")
    assert len(found) == 1 and "print(...)" in found[0].message


# ------------------------------------------------- rule: telemetry-prefix


def test_telemetry_prefix_flags_unregistered_names(tmp_path):
    src = """
    def publish(registry, kind):
        registry.gauge("mystery/thing").set(1.0)          # unregistered
        registry.counter(f"mystery/{kind}/hits").inc()    # unregistered f-string
        registry.gauge("decode/ok").set(1.0)              # registered prefix
        registry.gauge("compile_time_s").set(1.0)         # registered key
        registry.gauge(f"flash/{kind}/block_q").set(1.0)  # registered f-head
        registry.timer(kind)                              # dynamic: skipped
    """
    root = make_repo(tmp_path, {"llm_training_tpu/pub.py": src})
    found = findings_for(root, "telemetry-prefix")
    assert sorted(f.line for f in found) == [3, 4], [f.render() for f in found]
    assert all("telemetry.jsonl" in f.message for f in found)


def test_telemetry_prefix_ignores_non_registry_receivers(tmp_path):
    src = """
    def other(widget):
        widget.gauge("whatever/name")  # not a telemetry receiver
    """
    root = make_repo(tmp_path, {"llm_training_tpu/pub.py": src})
    assert findings_for(root, "telemetry-prefix") == []


# ------------------------------------------------- rule: env-doc-drift


def test_env_doc_drift_flags_undocumented_reads(tmp_path):
    src = '''
    import os

    """LLMT_DOCSTRING_ONLY is prose, not a read."""

    KNOB = os.environ.get("LLMT_SECRET_KNOB")
    OK = os.environ.get("LLMT_DOCUMENTED")
    TABLE = {"block_q": "FLASH_SECRET_TILE"}  # dict values count as reads
    '''
    root = make_repo(tmp_path, {"llm_training_tpu/env.py": src})
    found = findings_for(root, "env-doc-drift")
    names = sorted(f.message.split("`")[1] for f in found)
    assert names == ["FLASH_SECRET_TILE", "LLMT_SECRET_KNOB"], [
        f.render() for f in found
    ]


def test_env_doc_drift_ignores_docstring_mentions(tmp_path):
    src = '''
    def f():
        """Reads LLMT_PROSE_ONLY from the environment (doc prose)."""
        return None
    '''
    root = make_repo(tmp_path, {"llm_training_tpu/env.py": src})
    assert findings_for(root, "env-doc-drift") == []


# ------------------------------------------------- logical-axis-literal


_AXIS_FIXTURE = """
    import flax.linen as nn


    def _dense(features, logical_axes, name):
        return nn.Dense(
            features,
            kernel_init=nn.with_logical_partitioning(init, logical_axes),
            name=name,
        )


    class Block(nn.Module):
        def __call__(self, x):
            w = self.param(
                "w",
                nn.with_logical_partitioning(init, ("embd", "mlp")),  # typo
                (4, 4),
            )
            x = nn.with_logical_constraint(x, ("batch", None, "norm"))
            up = _dense(8, ("embed", "mpl"), "up")  # typo via the helper
            scanned = nn.scan(
                Block, metadata_params={nn.PARTITION_NAME: "layrs"},  # typo
            )
            shaped = (None,) * 2 + ("norm",)  # concatenated tuple: known
            return x
"""


def test_logical_axis_literal_flags_typos_in_models(tmp_path):
    root = make_repo(
        tmp_path, {"llm_training_tpu/models/fake/model.py": _AXIS_FIXTURE}
    )
    found = findings_for(root, "logical-axis-literal")
    bad = sorted(f.message.split("'")[1] for f in found)
    assert bad == ["embd", "layrs", "mpl"], [f.render() for f in found]
    for finding in found:
        assert "KNOWN_LOGICAL_AXES" in finding.message


def test_logical_axis_literal_only_scans_models(tmp_path):
    # the same typo outside models/ (e.g. an infer helper building specs
    # dynamically) is the audit's job, not this rule's
    root = make_repo(
        tmp_path, {"llm_training_tpu/infer/helper.py": _AXIS_FIXTURE}
    )
    assert findings_for(root, "logical-axis-literal") == []


def test_logical_axis_literal_unparseable_registry_is_loud(tmp_path):
    root = make_repo(
        tmp_path,
        {
            "llm_training_tpu/parallel/sharding.py": "KNOWN_LOGICAL_AXES = build()\n",
            "llm_training_tpu/models/fake/model.py": _AXIS_FIXTURE,
        },
    )
    found = findings_for(root, "logical-axis-literal")
    assert len(found) == 1 and "unverifiable" in found[0].message


def test_logical_axis_literal_real_models_clean():
    """Every axis literal in the real models/ tree is registered (the
    whole-repo capstone also proves this; this narrow run localizes a
    failure to the rule)."""
    found = findings_for(REPO_ROOT, "logical-axis-literal")
    assert found == [], [f.render() for f in found]


# --------------------------------------------------------------- CLI


def test_cli_json_output_and_exit_codes(tmp_path, capsys):
    root = make_repo(
        tmp_path,
        {"llm_training_tpu/resilience/supervisor.py": "import jax\n"},
    )
    rc = main(["--root", str(root), "--no-baseline", "--json"])
    payload = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert payload["findings"][0]["rule"] == "jax-free-import"
    assert "key" in payload["findings"][0]

    rc = main(["--root", str(root), "--no-baseline", "--rules", "telemetry-prefix"])
    capsys.readouterr()
    assert rc == 0  # the jax import is invisible to the selected rule

    rc = main(["--root", str(root), "--rules", "no-such-rule"])
    assert rc == 2


def test_cli_baseline_workflow(tmp_path, capsys):
    root = make_repo(
        tmp_path,
        {"llm_training_tpu/resilience/supervisor.py": "import jax\n"},
    )
    baseline = root / "config/lint_baseline.json"
    assert main(["--root", str(root)]) == 1  # missing baseline == empty
    assert main(["--root", str(root), "--update-baseline"]) == 0
    assert load_baseline(baseline)  # the finding was recorded
    assert main(["--root", str(root)]) == 0  # grandfathered
    assert main(["--root", str(root), "--no-baseline"]) == 1
    capsys.readouterr()


def test_cli_audit_rejects_lint_scoping(tmp_path, capsys):
    # `--audit` must not silently ignore lint-only scoping — a user who
    # typed `--audit --rules x path/` believes the run was scoped. Returns
    # 2 BEFORE the audit module (and jax) would load.
    root = make_repo(tmp_path, {})
    assert main(["--root", str(root), "--audit", "--rules", "host-sync"]) == 2
    assert main(["--root", str(root), "--audit", "llm_training_tpu"]) == 2
    assert "--families/--meshes" in capsys.readouterr().err


def test_cli_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in all_rules():
        assert rule.name in out


def test_write_baseline_roundtrip(tmp_path):
    from llm_training_tpu.analysis.engine import Finding

    target = tmp_path / "b.json"
    finding = Finding(rule="r", path="p.py", line=3, message="m")
    write_baseline(target, [finding])
    assert load_baseline(target) == {finding.key}


def test_update_baseline_carries_over_still_firing_entries(tmp_path, capsys):
    """--update-baseline must never un-grandfather debt it didn't fix."""
    root = make_repo(
        tmp_path,
        {"llm_training_tpu/resilience/supervisor.py": "import jax\n"},
    )
    baseline = root / "config/lint_baseline.json"
    assert main(["--root", str(root), "--update-baseline"]) == 0
    old_keys = load_baseline(baseline)
    # add a SECOND violation, then update again: both must be recorded
    (root / "llm_training_tpu/resilience/elastic.py").write_text("import jax\n")
    assert main(["--root", str(root), "--update-baseline"]) == 0
    assert load_baseline(baseline) > old_keys  # superset: old entry kept
    assert main(["--root", str(root)]) == 0
    capsys.readouterr()


def test_parse_errors_from_contract_walk_surface_on_narrow_scans(tmp_path):
    """A syntax-broken jax-free contract file must fail the gate even when
    the scan paths don't include it (the import walk parses on demand)."""
    root = make_repo(
        tmp_path,
        {
            "chip_smoke.py": "import jax\ndef broken(:\n",
            "llm_training_tpu/other/__init__.py": "",
        },
    )
    result = run_analysis(root, paths=["llm_training_tpu/other"])
    assert any(f.rule == "parse-error" and f.path == "chip_smoke.py" for f in result.findings), [
        f.render() for f in result.findings
    ]


def test_baseline_never_grandfathers_reasonless_suppressions(tmp_path, capsys):
    root = make_repo(
        tmp_path,
        {
            "llm_training_tpu/resilience/supervisor.py": (
                "# lint: allow(jax-free-import)\nimport jax\n"
            )
        },
    )
    assert main(["--root", str(root), "--update-baseline"]) == 0
    # the suppression-reason finding was NOT recorded: the gate still fails
    assert main(["--root", str(root)]) == 1
    capsys.readouterr()


def test_contract_suppressions_work_outside_narrow_scan_paths(tmp_path):
    root = make_repo(
        tmp_path,
        {
            "chip_smoke.py": (
                "# lint: allow(jax-free-import): proving suppressions reach "
                "walked-not-scanned files\nimport jax\n"
            ),
            "llm_training_tpu/other/__init__.py": "",
        },
    )
    result = run_analysis(root, paths=["llm_training_tpu/other"], rules=["jax-free-import"])
    assert result.findings == [], [f.render() for f in result.findings]
    assert len(result.suppressed) == 1


def test_suppression_syntax_in_docstrings_is_inert(tmp_path):
    """Only real comments register suppressions — prose quoting the syntax
    (like the rule modules' own docstrings) must not suppress findings."""
    src = '''
    """Suppress with `# lint: allow(jax-free-import): reason` if needed."""
    import jax
    '''
    root = make_repo(tmp_path, {"llm_training_tpu/resilience/supervisor.py": src})
    found = findings_for(root, "jax-free-import")
    assert len(found) == 1, [f.render() for f in found]


def test_suppression_star_and_multi_rule(tmp_path):
    root = make_repo(
        tmp_path,
        {
            "llm_training_tpu/resilience/supervisor.py": (
                "# lint: allow(*): fixture keeps jax on purpose\n"
                "import jax\n"
            )
        },
    )
    result = run_analysis(root, rules=["jax-free-import"])
    assert result.findings == []
    assert len(result.suppressed) == 1
