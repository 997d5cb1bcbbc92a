#!/usr/bin/env python
"""Precommit RL-smoke gate (docs/post-training.md).

Proves the on-policy GRPO loop end to end on CPU, on every commit:

1. **learning leg** — `rl-fit` on the tiny committed recipe
   (`config/examples/smoke/rl-smoke.yaml`: 16-vocab 2-layer Llama,
   `copy_digit` reward over repeated-digit prompts) must *strictly
   improve* mean reward: the mean of the last two rounds' rewards above
   the mean of the first two. The task is deliberately a bigram pattern
   ("emit the prompt digit") so a few policy-gradient rounds suffice;
   the seeded run is deterministic on CPU. Zero rollouts may be
   stale-dropped here — nothing races the weight sync in-process.
2. **chaos leg** — `LLMT_CHAOS_SERVE_SIGTERM_STEP` delivers SIGTERM
   inside an engine step mid-rollout; rl-fit must drain in-flight
   rollouts to `rl-journal.jsonl`, checkpoint the round cursor, and
   exit 75. The relaunch (attempt 2, chaos self-gated off) must replay
   and ADOPT the journaled rollouts and run to completion — with
   `--sync-mode host`, so the oracle sync path is exercised in CI too.
3. **report leg** — the learning run's dir must render an `== RL ==`
   section and an `"rl"` block in `--format json` (additive,
   no schema_version bump).

This parent is jax-free by contract (analysis/contracts.py) — the
rl-fit children own the backend.

Usage: python scripts/rl_smoke.py <scratch_dir>
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_CONFIG = "config/examples/smoke/rl-smoke.yaml"
# run dirs resolve as <run_root>/<project>/<name> (the JsonlLogger layout
# pinned in the config)
_RUN_SUFFIX = Path("smoke") / "rl-smoke"
RESUMABLE_EXIT_CODE = 75

# the recipe validated to learn at this scale: repeated-digit prompts,
# 2 reuse epochs per round (PPO clipping keeps reuse sound), temperature
# 1.0 so behavior logprobs are the plain softmax, eos disabled so every
# completion has full length
_FIT_FLAGS = [
    "--prompts-per-round", "8", "--prompt-len", "4",
    "--max-new-tokens", "8", "--updates-per-round", "2",
    "--prompt-style", "repeat", "--reward", "copy_digit",
    "--temperature", "1.0", "--eos-token-id", "-1",
    "--max-batch", "4", "--max-model-len", "64", "--prefill-chunk", "8",
]


def _rl_fit(scratch: Path, leg: str, env: dict, rounds: int,
            extra: list[str], expect_rc: int = 0) -> tuple[list[dict], dict | None, str]:
    """One rl-fit invocation under <scratch>/<leg>; returns (rl_round
    records, final stats or None, combined output text)."""
    run = subprocess.run(
        [
            sys.executable, "-m", "llm_training_tpu", "rl-fit",
            "--config", _CONFIG, "--rounds", str(rounds),
            *_FIT_FLAGS, *extra, f"run_root={scratch / leg}",
        ],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if run.returncode != expect_rc:
        print(run.stdout[-3000:], file=sys.stderr)
        print(run.stderr[-3000:], file=sys.stderr)
        raise SystemExit(
            f"rl smoke: {leg} rl-fit exited {run.returncode},"
            f" expected {expect_rc}"
        )
    rounds_out, stats = [], None
    for line in run.stdout.splitlines():
        try:
            record = json.loads(line)
        except json.JSONDecodeError:
            continue
        if record.get("type") == "rl_round":
            rounds_out.append(record)
        elif record.get("type") == "stats":
            stats = record["stats"]
    return rounds_out, stats, run.stdout + run.stderr


def main() -> int:
    if len(sys.argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    scratch = Path(sys.argv[1])
    shutil.rmtree(scratch, ignore_errors=True)
    scratch.mkdir(parents=True, exist_ok=True)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for stale in ("LLMT_CHAOS_SERVE_SIGTERM_STEP", "LLMT_SUPERVISOR_ATTEMPT",
                  "LLMT_RL_REWARD"):
        env.pop(stale, None)

    # --- 1. learning: mean reward over 10 rounds must strictly improve
    print("rl smoke: learning leg (10 rounds, fused sync)...", flush=True)
    records, stats, _ = _rl_fit(scratch, "learn", env, rounds=10, extra=[])
    assert len(records) == 10, [r.get("round") for r in records]
    rewards = [r["mean_reward"] for r in records]
    first, last = sum(rewards[:2]) / 2, sum(rewards[-2:]) / 2
    assert last > first, (
        f"mean reward did not improve: first-2 {first:.4f} vs"
        f" last-2 {last:.4f} ({[round(r, 3) for r in rewards]})"
    )
    assert stats is not None
    assert stats["rl/rollouts_stale_dropped"] == 0.0, stats
    assert stats["rl/rollouts_collected"] == 10 * 8 * 4, stats
    # 10 syncs -> the engine's weights generation reached 10 (init is 0)
    assert stats["rl/weight_syncs"] == 10.0, stats
    print(
        "rl smoke: learning OK —"
        f" reward {first:.3f} -> {last:.3f},"
        f" {int(stats['rl/rollouts_collected'])} rollouts,"
        f" generation {int(stats['rl/weight_syncs'])}", flush=True,
    )

    # --- 2. chaos: SIGTERM mid-rollout -> exit 75 -> replay/adopt -> done
    print("rl smoke: chaos leg (SIGTERM mid-rollout, host sync)...",
          flush=True)
    chaos_extra = ["--sync-mode", "host"]
    _, _, _ = _rl_fit(
        scratch, "chaos",
        {**env, "LLMT_CHAOS_SERVE_SIGTERM_STEP": "5"},
        rounds=3, extra=chaos_extra, expect_rc=RESUMABLE_EXIT_CODE,
    )
    run_dir = scratch / "chaos" / _RUN_SUFFIX
    journal = run_dir / "rl-journal.jsonl"
    assert journal.is_file() and journal.stat().st_size > 0, (
        f"no journaled rollouts after mid-rollout SIGTERM: {journal}"
    )
    records, stats, output = _rl_fit(
        scratch, "chaos",
        {**env, "LLMT_SUPERVISOR_ATTEMPT": "2"},
        rounds=3, extra=chaos_extra,
    )
    assert "replaying" in output, (
        f"relaunch never replayed the journal: {output[-2000:]}"
    )
    assert records and records[-1]["round"] == 2, records
    assert stats is not None and stats["rl/rounds"] == 3.0, stats
    assert not journal.exists(), "journal not retired after clean finish"
    print(
        "rl smoke: chaos OK — exit 75, journal replayed+adopted,"
        f" {int(stats['rl/rollouts_collected'])} rollouts across the"
        " restart", flush=True,
    )

    # --- 3. report renders the RL section, text and JSON
    learn_dir = scratch / "learn" / _RUN_SUFFIX
    report = subprocess.run(
        [sys.executable, "-m", "llm_training_tpu", "report", str(learn_dir)],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert report.returncode == 0, report.stderr
    assert "== RL ==" in report.stdout, report.stdout
    report_json = subprocess.run(
        [
            sys.executable, "-m", "llm_training_tpu", "report",
            str(learn_dir), "--format", "json",
        ],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert report_json.returncode == 0, report_json.stderr
    data = json.loads(report_json.stdout)
    assert data["schema_version"] == 2, data["schema_version"]
    assert data["rl"] and data["rl"]["rl/rounds"] == 10.0, data.get("rl")

    print("rl smoke: OK — reward improved, SIGTERM survived, report renders")
    return 0


if __name__ == "__main__":
    sys.exit(main())
