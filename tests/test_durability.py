"""Checkpoint durability plane: hashed manifests, verify-before-restore,
mirror healing, retention GC, byte-level corruption chaos, and the `ckpt`
CLI (docs/resilience.md#durability)."""

import json
import os
import signal
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.resilience import (
    ChaosConfig,
    MirrorDaemon,
    config_from_env,
    install_chaos,
    uninstall_chaos,
)
from llm_training_tpu.resilience import durability
from llm_training_tpu.telemetry import TelemetryRegistry, set_registry
from llm_training_tpu.trainer.state import TrainState


@pytest.fixture(autouse=True)
def _no_leaked_chaos():
    yield
    uninstall_chaos()


@pytest.fixture()
def registry():
    registry = TelemetryRegistry()
    previous = set_registry(registry)
    yield registry
    set_registry(previous)


def _fake_step(root: Path, step: int, payload: bytes = b"x" * 256,
               manifest: bool = True) -> Path:
    """A committed orbax-shaped step dir with two payload files."""
    sdir = root / str(step)
    (sdir / "state").mkdir(parents=True)
    (sdir / "state" / "array.bin").write_bytes(payload)
    (sdir / "meta.json").write_text(json.dumps({"step": step}))
    (sdir / "_CHECKPOINT_METADATA").write_text("{}")
    if manifest:
        durability.write_manifest(
            root, step, durability.build_manifest(sdir, step)
        )
    return sdir


# ------------------------------------------------------------- manifests


def test_manifest_round_trip_and_atomic_write(tmp_path):
    _fake_step(tmp_path, 3)
    manifest = durability.load_manifest(tmp_path, 3)
    assert manifest["step"] == 3
    assert set(manifest["files"]) == {
        "_CHECKPOINT_METADATA", "meta.json", "state/array.bin"
    }
    assert manifest["total_bytes"] == sum(
        entry["bytes"] for entry in manifest["files"].values()
    )
    # tmp-then-rename left no torn intermediate behind
    assert not list(tmp_path.glob("*.tmp"))
    assert durability.verify_step(tmp_path, 3, mode="full").ok


def test_load_manifest_absent_vs_torn(tmp_path):
    assert durability.load_manifest(tmp_path, 9) is None
    durability.manifest_path(tmp_path, 9).write_text("{not json")
    with pytest.raises(ValueError):
        durability.load_manifest(tmp_path, 9)


# -------------------------------------------------- corruption matrix


@pytest.mark.parametrize("corrupt_mode", ["flip", "truncate", "delete"])
@pytest.mark.parametrize("target", ["state/array.bin", "meta.json"])
def test_verify_full_names_step_and_file(tmp_path, corrupt_mode, target):
    _fake_step(tmp_path, 5)
    victim = durability.corrupt_step(tmp_path, 5, corrupt_mode, target=target)
    assert victim == target
    result = durability.verify_step(tmp_path, 5, mode="full")
    assert result.verifiable and result.findings
    # every finding names the step and the damaged file
    assert all(f.startswith("step 5: ") for f in result.findings)
    assert any(target in f for f in result.findings)


@pytest.mark.parametrize("corrupt_mode,fast_catches", [
    ("flip", False),      # same size, same file set — needs the hash pass
    ("truncate", True),   # size mismatch
    ("delete", True),     # file-set mismatch
])
def test_verify_fast_catches_shape_not_content(tmp_path, corrupt_mode,
                                               fast_catches):
    _fake_step(tmp_path, 1)
    durability.corrupt_step(tmp_path, 1, corrupt_mode)
    fast = durability.verify_step(tmp_path, 1, mode="fast")
    assert bool(fast.findings) == fast_catches
    assert not durability.verify_step(tmp_path, 1, mode="full").ok


def test_verify_catches_manifest_corruption_itself(tmp_path):
    """The manifest is part of the verified surface: a torn manifest is a
    named finding, not a crash and not a silent pass."""
    _fake_step(tmp_path, 2)
    mpath = durability.manifest_path(tmp_path, 2)
    mpath.write_text(mpath.read_text()[: len(mpath.read_text()) // 2])
    result = durability.verify_step(tmp_path, 2, mode="fast")
    assert result.verifiable and result.findings
    assert any("manifest-2.json" in f for f in result.findings)


def test_verify_catches_unexpected_file(tmp_path):
    _fake_step(tmp_path, 4)
    (tmp_path / "4" / "state" / "stray.bin").write_bytes(b"stray")
    result = durability.verify_step(tmp_path, 4, mode="fast")
    assert any("state/stray.bin" in f and "not in manifest" in f
               for f in result.findings)


def test_verify_legacy_step_is_unverifiable_not_a_finding(tmp_path):
    _fake_step(tmp_path, 7, manifest=False)
    result = durability.verify_step(tmp_path, 7, mode="full")
    assert not result.verifiable and not result.findings and not result.ok


def test_corrupt_step_picks_largest_payload(tmp_path):
    sdir = _fake_step(tmp_path, 1, payload=b"y" * 4096)
    victim = durability.corrupt_step(tmp_path, 1, "flip")
    assert victim == "state/array.bin"  # the largest file, not a marker
    assert (sdir / victim).stat().st_size == 4096  # flip preserves size


# ------------------------------------------------------------ retention


def test_retention_victims_policy():
    steps = [10, 20, 30, 40, 50, 60]
    # keep-last-2 → newest two survive
    assert durability.retention_victims(steps, 2) == [10, 20, 30, 40]
    # keep_every pins the long-tail archive
    assert durability.retention_victims(steps, 1, keep_every=30) == [10, 20, 40, 50]
    # protected (mirror-only intact copies) are never victims
    assert durability.retention_victims(steps, 1, protected={20}) == [10, 30, 40, 50]
    with pytest.raises(ValueError):
        durability.retention_victims(steps, 0)


def test_retention_never_deletes_newest():
    """Property: for any step set and policy, the newest step survives."""
    for steps in ([1], [1, 2], [3, 7, 9, 12], list(range(1, 30, 3))):
        for keep_last in (1, 2, 5):
            for keep_every in (None, 2, 10):
                victims = durability.retention_victims(
                    steps, keep_last, keep_every
                )
                assert max(steps) not in victims
                assert len(set(steps) - set(victims)) >= min(len(steps), keep_last)


def test_apply_retention_and_orphan_manifests(tmp_path):
    for step in (1, 2, 3, 4):
        _fake_step(tmp_path, step)
    victims = durability.apply_retention(tmp_path, keep_last=2)
    assert victims == [1, 2]
    assert durability.committed_steps(tmp_path) == [3, 4]
    assert not durability.manifest_path(tmp_path, 1).exists()
    # an orbax-side delete leaves a manifest orphan; the sweep drops it
    import shutil

    shutil.rmtree(tmp_path / "3")
    assert durability.gc_orphan_manifests(tmp_path) == [3]
    assert not durability.manifest_path(tmp_path, 3).exists()


# ------------------------------------------------------------ mirroring


def test_mirror_step_publishes_verified_copy(tmp_path):
    primary, mirror = tmp_path / "p", tmp_path / "m"
    _fake_step(primary, 1)
    assert durability.mirror_step(primary, mirror, 1) == []
    assert durability.verify_step(mirror, 1, mode="full").ok
    # idempotent: an intact mirror copy is not re-copied or disturbed
    assert durability.mirror_step(primary, mirror, 1) == []
    # a mirror copy is real bytes, not a hardlink back to the primary —
    # otherwise in-place corruption would damage both copies at once
    src = primary / "1" / "state" / "array.bin"
    dst = mirror / "1" / "state" / "array.bin"
    assert os.stat(src).st_ino != os.stat(dst).st_ino


def test_mirror_step_rejects_post_manifest_rot(tmp_path):
    """A source that decayed after its manifest landed must never publish:
    the mirror-side re-hash rejects the copy and tears it down."""
    primary, mirror = tmp_path / "p", tmp_path / "m"
    _fake_step(primary, 2)
    durability.corrupt_step(primary, 2, "flip")
    findings = durability.mirror_step(primary, mirror, 2)
    assert findings and any("sha256 mismatch" in f for f in findings)
    assert not (mirror / "2").exists()
    assert not list(mirror.glob(".tmp-*"))


def test_last_intact_on_mirror_protects_broken_primaries(tmp_path):
    primary, mirror = tmp_path / "p", tmp_path / "m"
    for step in (1, 2):
        _fake_step(primary, step)
        assert durability.mirror_step(primary, mirror, step) == []
    durability.corrupt_step(primary, 2, "truncate")
    assert durability.last_intact_on_mirror(primary, mirror) == {2}
    # and retention on the mirror honors the protection
    victims = durability.apply_retention(
        mirror, keep_last=1,
        protected=durability.last_intact_on_mirror(primary, mirror),
    )
    assert victims == [1]
    assert durability.committed_steps(mirror) == [2]


def test_mirror_daemon_mirrors_gcs_and_scrubs(tmp_path, registry):
    primary, mirror = tmp_path / "p", tmp_path / "m"
    primary.mkdir()
    for step in (1, 2, 3):
        _fake_step(primary, step)
    daemon = MirrorDaemon(
        primary, mirror, interval_s=0.05, keep_last=2,
        scrub_interval_s=0.0,  # exercised separately below
        registry=registry,
    )
    # the mirror-and-GC pass, driven from here: no thread, no wall clock
    daemon._pass()
    stats = daemon.stats()
    assert stats["mirrored"] and not stats["failed"]
    # retention keeps the newest keep_last on the mirror side
    assert durability.committed_steps(mirror) == [2, 3]
    snap, _ = registry.snapshot_with_kinds()
    assert snap["ckpt/mirrored_steps"] == 2
    assert snap["ckpt/mirror_lag_steps"] == 1  # step 1 GC'd mirror-side
    assert snap["ckpt/gc_deleted"] >= 1
    # the thread itself: every later pass finds nothing to do (a step the
    # mirror's retention dropped is not mirrored again), so the barrier
    # returns on the state the direct pass left
    daemon.start()
    try:
        assert daemon.drain(timeout_s=30.0)
        assert daemon.stats()["mirrored"] == [1, 2, 3]
    finally:
        thread = daemon._thread
        daemon.stop()
    assert not thread.is_alive()
    assert durability.committed_steps(mirror) == [2, 3]
    snap, _ = registry.snapshot_with_kinds()
    assert snap["ckpt/gc_deleted"] == 1  # and step 1 was not deleted twice
    # scrubber: drive _maybe_scrub directly with a fake clock
    clock = iter([100.0, 200.0]).__next__
    scrubber = MirrorDaemon(
        primary, mirror, scrub_interval_s=1.0, registry=registry, clock=clock
    )
    scrubber._maybe_scrub(registry)
    snap, _ = registry.snapshot_with_kinds()
    assert snap["ckpt/scrub_ok"] == 1 and snap["ckpt/scrub_last_ok"] == 1.0
    durability.corrupt_step(primary, 1, "flip")
    scrubber._scrub_cursor = 0
    scrubber._maybe_scrub(registry)
    snap, _ = registry.snapshot_with_kinds()
    assert snap["ckpt/scrub_failures"] == 1 and snap["ckpt/scrub_last_ok"] == 0.0


# -------------------------------------------------------- staged swaps


def test_stale_stage_promote_round_trip(tmp_path):
    _fake_step(tmp_path, 1)
    staged = durability.stage_stale_step(tmp_path, 1)
    assert staged is not None and staged.is_dir()
    # the SIGKILL-mid-swap signature: old step deleted, replacement absent
    import shutil

    shutil.rmtree(tmp_path / "1")
    durability.manifest_path(tmp_path, 1).unlink()
    assert durability.promote_stale_steps(tmp_path) == [1]
    assert durability.verify_step(tmp_path, 1, mode="full").ok
    assert not (tmp_path / durability.STALE_DIR).exists()


def test_promote_skips_committed_replacement(tmp_path):
    _fake_step(tmp_path, 1)
    durability.stage_stale_step(tmp_path, 1)
    # replacement committed fine — the staged copy is just swap trash
    assert durability.promote_stale_steps(tmp_path) == []
    assert not (tmp_path / durability.STALE_DIR).exists()


# ----------------------------------------------------------- chaos env


def test_chaos_ckpt_env_parsing(monkeypatch):
    monkeypatch.setenv("LLMT_CHAOS_CKPT_CORRUPT", "flip:3")
    monkeypatch.setenv("LLMT_CHAOS_CKPT_KILL_IN_SWAP", "2")
    config = config_from_env(ChaosConfig())
    assert config.ckpt_corrupt == "flip:3"
    assert config.ckpt_kill_in_swap == 2
    assert config.any_active()


def test_chaos_corrupts_targeted_step_once(tmp_path, registry):
    _fake_step(tmp_path, 3)
    _fake_step(tmp_path, 4)
    chaos = install_chaos(ChaosConfig(ckpt_corrupt="truncate:3"),
                          registry=registry)
    assert chaos.maybe_corrupt_checkpoint(tmp_path, 4) is None  # wrong step
    victim = chaos.maybe_corrupt_checkpoint(tmp_path, 3)
    assert victim is not None
    assert not durability.verify_step(tmp_path, 3, mode="fast").ok
    # fire-once: the second matching call is a no-op
    assert chaos.maybe_corrupt_checkpoint(tmp_path, 3) is None


def test_chaos_untargeted_waits_for_final_barrier(tmp_path, registry):
    _fake_step(tmp_path, 1)
    chaos = install_chaos(ChaosConfig(ckpt_corrupt="flip"), registry=registry)
    assert chaos.maybe_corrupt_checkpoint(tmp_path, 1) is None  # mid-run: no
    assert chaos.maybe_corrupt_checkpoint(
        tmp_path, 1, at_final_barrier=True
    ) is not None


# ------------------------------------------------------------ ckpt CLI


def _run_ckpt(*argv):
    from llm_training_tpu.cli.main import main

    return main(["ckpt", *[str(a) for a in argv]])


def test_ckpt_cli_exit_codes(tmp_path, capsys):
    primary = tmp_path / "p"
    # 2 = unusable: nothing to examine, every searched path named
    assert _run_ckpt("verify", primary) == 2
    assert str(primary) in capsys.readouterr().out
    _fake_step(primary, 1)
    _fake_step(primary, 2)
    assert _run_ckpt("verify", primary, "--mode", "full") == 0
    assert _run_ckpt("ls", primary) == 0
    assert "step 1" in capsys.readouterr().out
    # 1 = findings, naming step and file
    durability.corrupt_step(primary, 2, "flip", target="state/array.bin")
    assert _run_ckpt("verify", primary, "--mode", "full") == 1
    out = capsys.readouterr().out
    assert "FINDING" in out and "step 2" in out and "state/array.bin" in out
    # fast mode cannot see a same-size flip — that asymmetry is the point
    assert _run_ckpt("verify", primary, "--mode", "fast") == 0


def test_ckpt_cli_mirror_and_gc(tmp_path, capsys):
    primary, mirror = tmp_path / "p", tmp_path / "m"
    for step in (1, 2, 3):
        _fake_step(primary, step)
    assert _run_ckpt("mirror", primary, "--mirror-dir", mirror) == 0
    assert durability.committed_steps(mirror) == [1, 2, 3]
    # dry-run reports victims without deleting
    assert _run_ckpt("gc", primary, "--mirror-dir", mirror,
                     "--keep-last", "1", "--dry-run") == 0
    assert durability.committed_steps(mirror) == [1, 2, 3]
    assert _run_ckpt("gc", primary, "--mirror-dir", mirror,
                     "--keep-last", "1") == 0
    assert durability.committed_steps(mirror) == [3]
    # mirror with no mirror dir configured = unusable
    capsys.readouterr()
    assert _run_ckpt("mirror", primary) == 2


# ----------------------------------------- Checkpointer integration


def _tiny_state(value: float) -> TrainState:
    return TrainState.create(
        params={"w": jnp.full((4,), value, jnp.float32)},
        opt_state={"m": jnp.zeros((4,), jnp.float32)},
        rng=jax.random.key(0),
    )


def _restore_args(state: TrainState):
    abstract = jax.eval_shape(lambda: state)
    shardings = jax.tree.map(lambda leaf: None, abstract)
    return abstract, shardings


def _checkpointer(dirpath, **overrides):
    from llm_training_tpu.trainer.checkpoint import CheckpointConfig, Checkpointer

    kwargs = dict(dirpath=str(dirpath), async_save=False, retry_backoff_s=0.0,
                  mirror_interval_s=0.05)
    kwargs.update(overrides)
    return Checkpointer(CheckpointConfig(**kwargs))


def test_save_writes_manifest_at_commit(tmp_path, registry):
    ckpt = _checkpointer(tmp_path / "p")
    ckpt.save(1, _tiny_state(1.0))
    assert durability.verify_step(tmp_path / "p", 1, mode="full").ok
    snap, _ = registry.snapshot_with_kinds()
    assert snap.get("checkpoint/manifest_n", 0) >= 1  # timer fired
    ckpt.close()


def test_restore_heals_corrupt_primary_from_mirror(tmp_path, registry):
    """The heal leg: flip a byte in the newest primary step; verify-before-
    restore detects it, the restore lands on the mirror's copy in place,
    and no fallback to an older step happens."""
    primary, mirror = tmp_path / "p", tmp_path / "m"
    ckpt = _checkpointer(primary, mirror_dir=str(mirror), verify="full")
    ckpt.save(1, _tiny_state(1.0))
    ckpt.save(2, _tiny_state(2.0))
    ckpt.wait()  # manifest flush + mirror drain
    assert durability.committed_steps(mirror) == [1, 2]
    durability.corrupt_step(primary, 2, "flip")
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    assert meta["step"] == 2  # healed in place, NOT a fallback
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), 2.0)
    snap, _ = registry.snapshot_with_kinds()
    assert snap["checkpoint/verify_failures"] == 1
    assert snap["checkpoint/mirror_restores"] == 1
    assert snap.get("resilience/restore_fallbacks", 0) == 0
    # the primary copy is whole again
    assert durability.verify_step(primary, 2, mode="full").ok
    ckpt.close()


def test_restore_falls_back_when_mirror_also_rotten(tmp_path, registry):
    """Both copies of the newest step are bad → exactly one fallback leg to
    the older step, and the verified-corrupt step is repaired away."""
    primary, mirror = tmp_path / "p", tmp_path / "m"
    ckpt = _checkpointer(primary, mirror_dir=str(mirror), verify="full")
    ckpt.save(1, _tiny_state(1.0))
    ckpt.save(2, _tiny_state(2.0))
    ckpt.wait()
    durability.corrupt_step(primary, 2, "flip")
    durability.corrupt_step(mirror, 2, "flip")
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    assert meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), 1.0)
    snap, _ = registry.snapshot_with_kinds()
    assert snap["resilience/restore_fallbacks"] == 1  # exactly one leg
    assert snap["checkpoint/verify_failures"] >= 1
    assert 2 not in ckpt.manager.all_steps()  # verified corrupt → repaired
    assert not durability.manifest_path(primary, 2).exists()
    ckpt.close()


def test_environmental_error_preserves_checkpoint(tmp_path, registry,
                                                  monkeypatch):
    """A restore failure whose bytes verify clean is environmental (perms,
    mounts): fall back, but do NOT delete the good checkpoint."""
    ckpt = _checkpointer(tmp_path / "p", save_retries=0)
    ckpt.save(1, _tiny_state(1.0))
    ckpt.save(2, _tiny_state(2.0))
    ckpt.wait()
    real_restore = ckpt.manager.restore

    def broken_env(step, *args, **kwargs):
        if step == 2:
            raise PermissionError("mount went read-only")
        return real_restore(step, *args, **kwargs)

    monkeypatch.setattr(ckpt.manager, "restore", broken_env)
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    assert meta["step"] == 1
    assert 2 in ckpt.manager.all_steps()  # NOT deleted
    assert durability.manifest_path(tmp_path / "p", 2).exists()
    snap, _ = registry.snapshot_with_kinds()
    assert snap["resilience/restore_fallbacks"] >= 1
    assert snap.get("checkpoint/verify_failures", 0) == 0
    ckpt.close()


def test_legacy_step_without_manifest_keeps_repair_delete(tmp_path, registry):
    """Pre-manifest checkpoints keep today's behavior: an unrestorable
    legacy step is dropped so the resumed run can re-save it."""
    import shutil

    ckpt = _checkpointer(tmp_path / "p")
    ckpt.save(1, _tiny_state(1.0))
    ckpt.save(2, _tiny_state(2.0))
    durability.manifest_path(tmp_path / "p", 2).unlink()  # make it legacy
    shutil.rmtree(next((tmp_path / "p" / "2").glob("state*")))
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    assert meta["step"] == 1
    assert 2 not in ckpt.manager.all_steps()  # legacy path still repairs
    ckpt.close()


def test_force_save_leaves_no_stale_residue_on_success(tmp_path):
    ckpt = _checkpointer(tmp_path / "p")
    ckpt.save(1, _tiny_state(1.0))
    ckpt.save(1, _tiny_state(3.0), force=True)
    assert not (tmp_path / "p" / durability.STALE_DIR).exists()
    assert durability.verify_step(tmp_path / "p", 1, mode="full").ok
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), 3.0)
    ckpt.close()


def test_startup_promotes_interrupted_force_save(tmp_path):
    """Simulated SIGKILL inside the swap window: the staged copy is
    promoted by the next Checkpointer before orbax scans the dir."""
    import shutil

    ckpt = _checkpointer(tmp_path / "p")
    ckpt.save(1, _tiny_state(1.0))
    ckpt.close()
    durability.stage_stale_step(tmp_path / "p", 1)
    shutil.rmtree(tmp_path / "p" / "1")  # the delete the kill interrupts
    ckpt = _checkpointer(tmp_path / "p")
    assert ckpt.manager.all_steps() == [1]
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    assert meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), 1.0)
    ckpt.close()


@pytest.mark.slow
def test_force_save_survives_sigkill_in_swap(tmp_path):
    """The chaos-kill pin for the force-save data-loss window: a SIGKILL
    between the old step's delete and the replacement's commit must leave
    at least one restorable durable copy."""
    child = textwrap.dedent(
        """
        import jax, jax.numpy as jnp
        from llm_training_tpu.trainer.state import TrainState
        from llm_training_tpu.trainer.checkpoint import CheckpointConfig, Checkpointer
        from llm_training_tpu.resilience import ChaosConfig, config_from_env, install_chaos

        install_chaos(config_from_env(ChaosConfig()))

        def tiny(v):
            return TrainState.create(
                params={"w": jnp.full((4,), v, jnp.float32)},
                opt_state={"m": jnp.zeros((4,), jnp.float32)},
                rng=jax.random.key(0),
            )

        ckpt = Checkpointer(CheckpointConfig(
            dirpath=%r, async_save=False, retry_backoff_s=0.0))
        ckpt.save(1, tiny(1.0))
        ckpt.save(1, tiny(9.0), force=True)  # chaos SIGKILLs mid-swap
        raise SystemExit("survived the kill window")
        """ % str(tmp_path / "p")
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               LLMT_CHAOS_CKPT_KILL_IN_SWAP="1")
    proc = subprocess.run([sys.executable, "-c", child], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == -signal.SIGKILL, proc.stdout + proc.stderr
    # relaunch: promotion restores the pre-force copy
    ckpt = _checkpointer(tmp_path / "p")
    assert ckpt.manager.all_steps() == [1]
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    assert meta["step"] == 1
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), 1.0)
    ckpt.close()


def test_targeted_chaos_exercises_mirror_reject_then_fallback(tmp_path,
                                                              registry):
    """`LLMT_CHAOS_CKPT_CORRUPT=truncate:2` fires post-manifest, pre-
    mirror: the mirror must reject the rotten copy, and the restore must
    fall back primary(2 corrupt) -> mirror(2 absent) -> older step 1."""
    primary, mirror = tmp_path / "p", tmp_path / "m"
    install_chaos(ChaosConfig(ckpt_corrupt="truncate:2"), registry=registry)
    # the mirror wakes on the checkpointer's notify() alone, which comes after
    # the chaos hook: at the helper's 0.05 s poll it now and then copied step
    # 2 between its manifest and its corruption (1 of 30 runs on a busy machine)
    ckpt = _checkpointer(primary, mirror_dir=str(mirror), verify="fast",
                         mirror_interval_s=3600.0)
    ckpt.save(1, _tiny_state(1.0))
    ckpt.save(2, _tiny_state(2.0))  # corrupted right after its manifest
    ckpt.wait()
    assert durability.committed_steps(mirror) == [1]  # 2 was rejected
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    assert meta["step"] == 1
    snap, _ = registry.snapshot_with_kinds()
    assert snap["ckpt/mirror_verify_rejects"] >= 1
    assert snap["resilience/restore_fallbacks"] == 1
    assert snap["checkpoint/verify_failures"] >= 1
    ckpt.close()


def test_untargeted_chaos_flip_heals_at_restore(tmp_path, registry):
    """`LLMT_CHAOS_CKPT_CORRUPT=flip` (no step) fires at the final barrier
    AFTER the mirror drained — the restore must land on the mirror copy."""
    primary, mirror = tmp_path / "p", tmp_path / "m"
    install_chaos(ChaosConfig(ckpt_corrupt="flip"), registry=registry)
    ckpt = _checkpointer(primary, mirror_dir=str(mirror), verify="full")
    ckpt.save(2, _tiny_state(7.0))
    ckpt.wait()  # drain, then the flip lands on the newest primary step
    assert not durability.verify_step(primary, 2, mode="full").ok
    state, shardings = _restore_args(_tiny_state(0.0))
    restored, meta = ckpt.maybe_restore(state, shardings)
    assert meta["step"] == 2
    np.testing.assert_array_equal(np.asarray(restored.params["w"]), 7.0)
    snap, _ = registry.snapshot_with_kinds()
    assert snap["checkpoint/mirror_restores"] == 1
    ckpt.close()


# ------------------------------------------------------- report surface


def test_report_renders_durability_section(tmp_path):
    from llm_training_tpu.telemetry.report import render_report, render_report_data

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    (run_dir / "telemetry.jsonl").write_text(json.dumps({
        "step": 10,
        "checkpoint/verify_failures": 1.0,
        "checkpoint/mirror_restores": 1.0,
        "ckpt/mirror_verify_rejects": 0.0,
        "ckpt/mirrored_steps": 3.0,
        "ckpt/mirror_lag_steps": 0.0,
        "ckpt/scrub_ok": 5.0,
    }) + "\n")
    text = render_report(run_dir)
    assert "== Durability ==" in text
    assert "restores healed from the mirror: 1" in text
    assert "mirrored steps: 3" in text
    data = render_report_data(run_dir)
    assert data["durability"]["checkpoint/verify_failures"] == 1.0
    assert data["durability"]["ckpt/mirrored_steps"] == 3.0


def test_statusz_health_line_flags_durability(tmp_path, registry):
    from llm_training_tpu.telemetry.exporter import MetricsExporter

    registry.counter("checkpoint/verify_failures").inc()
    registry.gauge("ckpt/mirror_lag_steps").set(2)
    registry.gauge("ckpt/mirrored_steps").set(1)
    text = MetricsExporter(0, registry=registry).render_statusz()
    assert "durability:" in text
    assert "verify failures 1" in text
    # the problem surfaces on the health line itself, not just the detail
    health_line = next(l for l in text.splitlines() if l.startswith("health:"))
    assert "durability" in health_line
