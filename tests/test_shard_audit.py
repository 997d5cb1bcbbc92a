"""shardcheck audit + strict sharding resolution (docs/static-analysis.md#audit).

Three layers, cheapest first: pure-math hbm_budget units, the strict-mode /
structured-drop regression pins on `parallel/sharding.py`, then the real
family × mesh audit matrix — `jax.eval_shape` only, zero FLOPs, so the full
14-family × 6-mesh sweep costs single-digit seconds on CPU. The capstone is
the copied-tree acceptance test: a seeded one-character typo in a family's
logical-axis metadata must fail `--audit` with a finding naming the leaf
path, the bad axis, and the affected mesh configs.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from llm_training_tpu.analysis import hbm_budget
from llm_training_tpu.analysis.shard_audit import (
    AuditConfig,
    FAMILY_REGISTRY,
    FamilySpec,
    MESH_MATRIX,
    run_audit,
    worst_estimate,
)
from llm_training_tpu.parallel.sharding import (
    DEFAULT_LOGICAL_AXIS_RULES,
    KNOWN_LOGICAL_AXES,
    UnknownLogicalAxisError,
    logical_to_spec,
    resolve_spec,
)

REPO_ROOT = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------ hbm_budget


def test_entry_ways_and_shard_ways():
    sizes = {"fsdp": 4, "tensor": 2}
    assert hbm_budget.entry_ways(None, sizes) == 1
    assert hbm_budget.entry_ways("fsdp", sizes) == 4
    assert hbm_budget.entry_ways(("fsdp", "tensor"), sizes) == 8
    assert hbm_budget.entry_ways("sequence", sizes) == 1  # unlisted axis = 1
    # spec shorter than rank pads with unsharded dims
    assert hbm_budget.shard_ways(("fsdp",), (8, 6, 4), sizes) == (4, 1, 1)


def test_per_chip_bytes_ceils_ragged_shards():
    # 10 rows over 4 ways -> ceil(10/4)=3 rows per chip, like GSPMD padding
    assert hbm_budget.per_chip_bytes((10, 2), 4, (4, 1)) == 3 * 2 * 4
    assert hbm_budget.global_bytes((10, 2), 4) == 80


def test_hbm_estimate_totals_and_fits():
    est = hbm_budget.HbmEstimate(
        params_bytes=100, opt_state_bytes=200, kv_cache_bytes=50,
        activation_bytes=25,
    )
    assert est.total_bytes == 375
    assert est.fits(375) and not est.fits(374)
    assert est.to_json()["total_gib"] == pytest.approx(
        375 / hbm_budget.GIB, abs=1e-9
    )


def test_activation_proxy_shards_by_batch_and_seq():
    dense = hbm_budget.activation_proxy_bytes(8, 64, 32, 2, 2, 1, 1)
    sharded = hbm_budget.activation_proxy_bytes(8, 64, 32, 2, 2, 4, 2)
    assert dense == 8 * sharded


# ------------------------------------------- strict resolution regressions


def test_known_axes_registry_matches_rule_table():
    """The registry and the rule table must not drift (the lint rule and
    the audit both treat KNOWN_LOGICAL_AXES as the source of truth)."""
    rule_names = {name for name, _ in DEFAULT_LOGICAL_AXIS_RULES}
    assert set(KNOWN_LOGICAL_AXES) == rule_names | {"layers"}


def test_strict_mode_raises_on_unknown_axis_with_leaf_path():
    with pytest.raises(UnknownLogicalAxisError) as err:
        logical_to_spec(("embd", "mlp"), strict=True, path="mlp/up_proj/kernel")
    message = str(err.value)
    assert "'embd'" in message
    assert "mlp/up_proj/kernel" in message
    assert "replicates" in message.lower()
    assert err.value.axis == "embd"


def test_legacy_mode_still_replicates_unknown_axes():
    """Pinned on purpose: non-strict callers (serving paths resolving with
    partial rule sets) keep the permissive behavior."""
    spec = logical_to_spec(("embd", "mlp"))
    assert tuple(spec) == (None, "tensor")


def test_duplicate_axis_drop_is_structured_not_silent():
    # 'batch' consumes data+fsdp+expert; a later 'embed' dim loses fsdp
    spec, drops = resolve_spec(("batch", "embed"), path="x")
    assert tuple(spec) == (("data", "fsdp", "expert"), None)
    assert len(drops) == 1
    drop = drops[0]
    assert drop.axis == "embed"
    assert drop.mesh_axes == ("fsdp",)
    assert drop.position == 1
    assert drop.path == "x"


def test_clean_resolution_reports_no_drops():
    spec, drops = resolve_spec(("embed", "mlp"))
    assert tuple(spec) == ("fsdp", "tensor") and drops == ()


def test_trainer_state_shardings_are_strict(devices):
    """The Trainer's resolution path must raise (naming the leaf) on an
    unknown axis instead of silently replicating, and surface duplicate
    drops as warnings instead of swallowing them."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    trainer = Trainer(TrainerConfig())
    trainer.mesh = build_mesh(MeshConfig(), devices)

    bad = {
        "params": {
            "up_proj": {
                "kernel": nn.Partitioned(
                    jax.ShapeDtypeStruct((8, 8), jnp.float32),
                    names=("embd", "mlp"),
                )
            }
        }
    }
    with pytest.raises(UnknownLogicalAxisError) as err:
        trainer._state_shardings(bad)
    assert "up_proj" in str(err.value)

    good = {
        "params": {
            "kernel": nn.Partitioned(
                jax.ShapeDtypeStruct((8, 8), jnp.float32),
                names=("embed", "mlp"),
            )
        }
    }
    shardings = trainer._state_shardings(good)
    assert tuple(shardings["params"]["kernel"].spec) == ("fsdp", "tensor")


# ------------------------------------------------------- the audit matrix


def test_audit_matrix_all_families_all_meshes_clean():
    """THE regression gate for a change of the sharding rule table: every
    registered family × every matrix mesh resolves with zero findings at
    HEAD, well inside the acceptance budget."""
    result = run_audit(REPO_ROOT)
    assert result.findings == [], [f.render() for f in result.findings]
    assert len(result.families_run) == 19
    assert set(result.meshes_run) == set(MESH_MATRIX)
    assert result.elapsed_s < 60.0
    # every cell produced an estimate and fits the default budget
    for family in result.families_run:
        cells = result.estimates[family]["meshes"]
        assert set(cells) == set(MESH_MATRIX)
        for cell in cells.values():
            assert cell["fits"] and cell["total_gib"] > 0
    worst = worst_estimate(result.estimates)
    assert worst is not None and worst[2] < 1.0  # tiny registry families


def test_audit_unknown_family_or_mesh_raises():
    with pytest.raises(ValueError, match="unknown family"):
        run_audit(REPO_ROOT, AuditConfig(families=("nope",)))
    with pytest.raises(ValueError, match="unknown mesh"):
        run_audit(REPO_ROOT, AuditConfig(meshes=("nope",)))


def test_audit_hbm_budget_finding_fires():
    """An absurdly small chip budget must flag every (family, mesh) cell
    with the budget + mesh named in the message."""
    result = run_audit(
        REPO_ROOT,
        AuditConfig(families=("llama",), hbm_budget_gib=1e-9),
    )
    rules = {f.rule for f in result.findings}
    assert rules == {"shard-hbm-budget"}
    assert len(result.findings) == len(MESH_MATRIX)
    message = result.findings[0].message
    assert "exceeds" in message and "budget" in message
    assert any(mesh in message for mesh in MESH_MATRIX)
    # the baseline key is mesh- and estimate-independent: all six per-mesh
    # findings for the family collapse to ONE grandfatherable key
    from llm_training_tpu.analysis.shard_audit import _baseline_key

    assert len({_baseline_key(f) for f in result.findings}) == 1


def test_audit_replicated_threshold_finding_fires():
    """With a ~zero size threshold, intentionally-replicated tensors (norm
    weights) trip the large-replicated check on param-capable meshes — and
    the pure-DP mesh (data8) must NOT appear in the mesh list."""
    result = run_audit(
        REPO_ROOT,
        AuditConfig(families=("llama",), replicated_threshold_mib=0.0),
    )
    replicated = [f for f in result.findings if f.rule == "shard-replicated"]
    assert replicated, [f.render() for f in result.findings]
    for finding in replicated:
        assert "data8" not in finding.message.split("mesh(es)")[-1]


def test_audit_indivisible_finding_fires(monkeypatch):
    """A family whose embed dim cannot divide the 8-way fsdp axis is
    flagged with the offending mesh named."""
    import llm_training_tpu.analysis.shard_audit as shard_audit

    ragged = FamilySpec(
        "ragged_llama", "llm_training_tpu.models.llama", "Llama",
        "llm_training_tpu/models/llama/model.py",
        dict(vocab_size=128, hidden_size=36, intermediate_size=64,
             num_hidden_layers=2, num_attention_heads=2,
             num_key_value_heads=2, max_position_embeddings=64),
    )
    monkeypatch.setattr(shard_audit, "FAMILY_REGISTRY", (ragged,))
    result = run_audit(REPO_ROOT, AuditConfig(meshes=("fsdp8", "data8")))
    indivisible = [f for f in result.findings if f.rule == "shard-indivisible"]
    assert indivisible, [f.render() for f in result.findings]
    assert any(
        "36" in f.message and "fsdp8" in f.message for f in indivisible
    )
    # the pure-DP mesh shards nothing, so it can never be the offender
    assert all("data8" not in f.message for f in indivisible)


@pytest.mark.slow
def test_audit_seeded_typo_acceptance(tmp_path):
    """ISSUE 10 acceptance: on a copied tree with a one-character typo in
    llama's q_proj logical axes, `--audit` exits nonzero and the finding
    names the leaf path, the bad axis, and the affected mesh configs.

    Slow-marked: it respawns a full jax interpreter over a copied tree
    (~5s), and the tier-1 suite sits within noise of its 870s timeout
    (1132s measured on a loaded container, 2026-08-04); the in-process
    matrix + strict-mode tests carry the tier-1 signal, and the same
    seeded-typo path is what `test_logical_axis_literal_flags_typos_in_models`
    pins at AST level in every tier-1 run."""
    tree = tmp_path / "tree"
    tree.mkdir()
    shutil.copytree(
        REPO_ROOT / "llm_training_tpu", tree / "llm_training_tpu",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    shutil.copytree(REPO_ROOT / "config", tree / "config")
    target = tree / "llm_training_tpu/models/llama/model.py"
    source = target.read_text()
    assert '("embed", "heads")' in source
    target.write_text(source.replace('("embed", "heads")', '("embd", "heads")', 1))

    proc = subprocess.run(
        [
            sys.executable, "-m", "llm_training_tpu.analysis", "--audit",
            "--families", "llama", "--meshes", "fsdp8,dryrun_fsdp2_tp2_sp2",
            "--json",
        ],
        cwd=tree,
        capture_output=True,
        text=True,
        env={
            **__import__("os").environ,
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": str(tree),
        },
        timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    record = json.loads(proc.stdout)
    findings = record["findings"]
    assert findings and all(f["rule"] == "shard-unknown-axis" for f in findings)
    message = findings[0]["message"]
    assert "q_proj" in message  # the leaf path
    assert "'embd'" in message  # the bad axis
    assert "fsdp8" in message and "dryrun_fsdp2_tp2_sp2" in message  # meshes


# ------------------------------------------------------ report rendering


def test_report_audit_section_renders_and_degrades(tmp_path):
    from llm_training_tpu.telemetry.report import (
        _audit_section,
        _newest_audit_record,
        render_report,
    )

    good = {
        "version": 1, "mode": "audit", "findings": [], "baselined": 0,
        "families": ["llama"], "meshes": ["fsdp8"], "hbm_budget_gib": 32.0,
        "estimates": {"llama": {"meshes": {"fsdp8": {
            "params_gib": 0.001, "opt_state_gib": 0.002,
            "kv_cache_gib": 0.0005, "activation_gib": 0.0005,
            "total_gib": 0.004, "fits": True,
        }}}},
    }
    lines = _audit_section(
        (good, "audit.json"), None, {"hbm/peak_bytes_in_use": 2 * 1024**3}
    )
    text = "\n".join(lines)
    assert "== Audit ==" in text
    assert "shardcheck: OK" in text
    assert "0.004 GiB (llama @ fsdp8" in text
    assert "measured hbm/peak_bytes_in_use: 2.000" in text

    failing = dict(good, findings=[{"rule": "shard-unknown-axis"}] * 2)
    text = "\n".join(_audit_section((failing, "a.json"), None, {}))
    assert "shardcheck: FAIL — 2 finding(s)" in text
    assert "shard-unknown-axis x2" in text

    # malformed record: one honest line, never a crash
    text = "\n".join(_audit_section(({"findings": "what"}, "a.json"), None, {}))
    assert "unreadable audit record" in text

    # absent: the section is omitted entirely
    assert _audit_section(None, None, {}) == []

    # end-to-end: render_report picks audit.json out of the run dir
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    (run_dir / "metrics.jsonl").write_text(
        json.dumps({"step": 1, "loss": 2.0, "steps_per_sec": 1.0}) + "\n"
    )
    (run_dir / "audit.json").write_text(json.dumps(good))
    report = render_report(run_dir)
    assert "== Audit ==" in report and "shardcheck: OK" in report
    # a run dir with no audit json renders no section
    (run_dir / "audit.json").unlink()
    assert "== Audit ==" not in render_report(run_dir)


def test_baseline_keys_are_mesh_selection_stable():
    """A `--meshes`-narrowed `--update-baseline` run and the full precommit
    run must agree on baseline keys: the mesh-list suffix is stripped, and
    unknown-axis messages always name the full matrix."""
    from llm_training_tpu.analysis.engine import Finding
    from llm_training_tpu.analysis.shard_audit import _baseline_key

    # the per-mesh shard counts differ (8-way vs 8-way + 4-way) — the
    # stable prefix must not mention them, only the suffix does
    narrow = Finding(
        rule="shard-indivisible", path="p", line=1,
        message="fam: leaf x dim of size 36 does not divide its sharding "
                "(spec entry 'fsdp') on mesh(es) fsdp8 (8-way); the shard "
                "goes ragged and pads on every chip",
    )
    full = Finding(
        rule="shard-indivisible", path="p", line=1,
        message="fam: leaf x dim of size 36 does not divide its sharding "
                "(spec entry 'fsdp') on mesh(es) fsdp8 (8-way), "
                "data2_fsdp4 (4-way); the shard goes ragged and pads on "
                "every chip",
    )
    assert _baseline_key(narrow) == _baseline_key(full)
    # unknown-axis findings name every matrix mesh regardless of --meshes
    result = run_audit(
        REPO_ROOT, AuditConfig(families=("llama",), meshes=("fsdp8",))
    )
    assert result.meshes_run == ("fsdp8",)


def test_registry_covers_nineteen_families():
    names = [f.name for f in FAMILY_REGISTRY]
    assert len(names) == len(set(names)) == 19
    # the registry must exercise scan stacks, MoE, and pipeline layouts
    assert {"llama", "llama_moe", "llama_pp"} <= set(names)
