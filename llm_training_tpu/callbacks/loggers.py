"""Metric loggers.

Capability parity: reference `lightning/loggers/wandb.py:10` (W&B logger
with project/name-scoped save dirs) and `SaveConfigCallback`'s resolved-
config upload (`save_config_callback.py:15-41`). W&B is optional at runtime
(this image has no wandb and zero egress), so the always-available default
is a JSONL metrics file per run — machine-readable like a W&B history file.
"""

from __future__ import annotations

import json
import logging
import time
from pathlib import Path

from pydantic import BaseModel, ConfigDict

logger = logging.getLogger(__name__)

# metric keys routed (additionally) to telemetry.jsonl — the observability
# record a `report` invocation reads (docs/observability.md)
TELEMETRY_PREFIXES = (
    "goodput/", "hbm/", "xla/", "data/", "checkpoint/", "perf/",
    "health/", "nan_guard/", "resilience/", "decode/", "eval/", "serve/",
    "elastic/", "flash/", "trace/", "slo/", "exporter/", "attr/",
    "profile/", "hbm_timeline/", "router/", "rl/", "ckpt/", "setup/",
    "compile/",
)
TELEMETRY_KEYS = ("compile_time_s",)


def _is_telemetry_key(key: str) -> bool:
    return key in TELEMETRY_KEYS or key.startswith(TELEMETRY_PREFIXES)


def _primary_host() -> bool:
    """Run-dir artifacts are written by process 0 only: in multi-host SPMD
    every host runs the same program, and N hosts appending to one
    metrics.jsonl (or racing W&B inits) corrupts the run record."""
    try:
        import jax

        return jax.process_index() == 0
    except Exception:
        return True


class JsonlLoggerConfig(BaseModel):
    model_config = ConfigDict(extra="forbid")

    # save_dir/project defaults are mirrored by cli.main's
    # _jsonl_run_dir_jaxfree (the supervisor path cannot import this
    # package — its __init__ pulls jax); keep them in sync
    save_dir: str = "runs"
    project: str = "llm-training-tpu"
    name: str | None = None  # default: timestamp


class JsonlLogger:
    """Appends one JSON object per logged step to
    `<save_dir>/<project>/<name>/metrics.jsonl` (all metrics) and
    `telemetry.jsonl` (the goodput/device/registry subset `report` reads),
    and writes the resolved run config next to them (the reference embeds it
    in W&B + checkpoints). All writes happen on process 0 only."""

    def __init__(self, config: JsonlLoggerConfig | None = None):
        self.config = config or JsonlLoggerConfig()
        name = self.config.name or time.strftime("%Y%m%d-%H%M%S")
        self.run_dir = Path(self.config.save_dir) / self.config.project / name
        self._files: dict[str, object] = {}

    def _ensure_open(self, filename: str):
        if filename not in self._files:
            self.run_dir.mkdir(parents=True, exist_ok=True)
            self._files[filename] = open(self.run_dir / filename, "a")
        return self._files[filename]

    def _write(self, filename: str, record: dict) -> None:
        f = self._ensure_open(filename)
        f.write(json.dumps(record) + "\n")
        f.flush()

    def on_fit_start(self, trainer, objective, datamodule, start_step) -> None:
        if not _primary_host():
            return
        self.run_dir.mkdir(parents=True, exist_ok=True)
        # one metadata snapshot per run: reuse the checkpointer's (collected
        # at construction) so the checkpoint meta and the run dir record the
        # SAME world/env/rev; collect only when no checkpointer exists
        ckpt = getattr(trainer, "checkpointer", None)
        run_metadata = getattr(ckpt, "run_metadata", None)
        if run_metadata is None:
            from llm_training_tpu.run_metadata import collect_run_metadata

            run_metadata = collect_run_metadata()
        (self.run_dir / "run_metadata.json").write_text(
            json.dumps(run_metadata, indent=2, default=str)
        )
        run_config = getattr(ckpt, "run_config", None)
        if run_config:
            (self.run_dir / "config.json").write_text(json.dumps(run_config, indent=2, default=str))

    def on_step_end(self, trainer, step, metrics) -> None:
        if not _primary_host():
            return
        record = {"step": step}
        for key, value in metrics.items():
            try:
                record[key] = float(value)
            except (TypeError, ValueError):
                record[key] = str(value)
        self._write("metrics.jsonl", record)
        telemetry = {k: v for k, v in record.items() if _is_telemetry_key(k)}
        if telemetry:
            self._write("telemetry.jsonl", {"step": step, **telemetry})

    def on_validation_end(self, trainer, step, metrics) -> None:
        self.on_step_end(trainer, step, metrics)

    def on_telemetry(self, trainer, step, record) -> None:
        """End-of-fit telemetry flush (trainer epilogue): the post-loop
        checkpoint save/wait lands after the last log step — without this,
        `report` would render totals missing that tail."""
        if not _primary_host():
            return
        telemetry = {}
        for key, value in record.items():
            if not _is_telemetry_key(key):
                continue
            try:
                telemetry[key] = float(value)
            except (TypeError, ValueError):
                telemetry[key] = str(value)
        if telemetry:
            self._write("telemetry.jsonl", {"step": step, **telemetry})

    def on_fit_end(self, trainer, state) -> None:
        for f in self._files.values():
            f.close()
        self._files = {}


class WandbLoggerConfig(BaseModel):
    model_config = ConfigDict(extra="forbid")

    save_dir: str = "runs"
    project: str = "llm-training-tpu"
    name: str | None = None
    entity: str | None = None
    mode: str = "offline"  # zero-egress default; 'online' where permitted
    # upload the resolved run config YAML + a snapshot of the framework's
    # .py sources to the run (reference save_config_callback.py:15-41)
    log_code: bool = True


class WandbLogger:
    """W&B metrics logging, import-gated: constructing it without wandb
    installed raises immediately (no silent no-op), matching the reference's
    hard dependency (`lightning/loggers/wandb.py`)."""

    def __init__(self, config: WandbLoggerConfig | None = None):
        import wandb  # noqa: F401 — fail fast if unavailable

        self.config = config or WandbLoggerConfig()
        self._run = None

    def on_fit_start(self, trainer, objective, datamodule, start_step) -> None:
        if not _primary_host():
            return
        import wandb

        cfg = self.config
        save_dir = Path(cfg.save_dir) / cfg.project / (cfg.name or "")
        save_dir.mkdir(parents=True, exist_ok=True)
        run_config = getattr(getattr(trainer, "checkpointer", None), "run_config", None)
        self._run = wandb.init(
            project=cfg.project,
            name=cfg.name,
            entity=cfg.entity,
            dir=str(save_dir),
            mode=cfg.mode,
            config=run_config,
            resume="allow",
        )
        if cfg.log_code:
            # resolved config as a run file + the package's .py sources as a
            # code artifact — the reference's `experiment.save(config_path)`
            # + `log_code` pair (save_config_callback.py:38-41), so a run is
            # reproducible from its W&B page alone
            import yaml

            if run_config is not None:
                config_path = save_dir / "config.yaml"
                with open(config_path, "w") as f:
                    yaml.safe_dump(run_config, f, sort_keys=False)
                self._run.save(str(config_path), base_path=str(save_dir), policy="now")
            import llm_training_tpu

            root = Path(llm_training_tpu.__file__).parent
            self._run.log_code(
                root=str(root),
                name=f"source-{cfg.project}",
                include_fn=lambda p: p.endswith(".py"),
            )

    def on_step_end(self, trainer, step, metrics) -> None:
        if self._run is not None:
            self._run.log(
                {k: v for k, v in metrics.items() if isinstance(v, (int, float)) or hasattr(v, "item")},
                step=step,
            )

    def on_validation_end(self, trainer, step, metrics) -> None:
        self.on_step_end(trainer, step, metrics)

    def on_telemetry(self, trainer, step, record) -> None:
        # W&B merges re-logs at the same step, so the end-of-fit tail
        # (final checkpoint save/wait) updates the run's last history row
        self.on_step_end(trainer, step, record)

    def on_fit_end(self, trainer, state) -> None:
        if self._run is not None:
            self._run.finish()
            self._run = None
