"""Training time + MFU estimator.

Capability parity: reference `lightning/callbacks/training_time_estimator.py`
— its only benchmarking tool: an N-step timed dry run extrapolated to a
total-training-time table (`:62-83`), optionally stopping the run
(`:32-37` disables checkpointing for the dry run; here `stop_after_steps`
ends the fit). TPU-native addition: tokens/sec/device and **MFU** against
the chip's peak bf16 FLOP/s, using
the standard decoder FLOP model (6·params·tokens + 12·L·H·D·S·tokens for
attention scores/values).
"""

from __future__ import annotations

import logging
import time

import jax
from pydantic import BaseModel, ConfigDict, Field

logger = logging.getLogger(__name__)

# peak dense bf16 FLOP/s per chip by device_kind substring — the ONE peak
# table. Source: Google Cloud TPU documentation,
# the per-generation system-architecture pages ("TPU v5e": 197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s). A kind that matches nothing has no peak
# (None): an unknown device is an error to a measurement, never a default.
_PEAK_FLOPS = (
    ("v6", 918e12),  # Trillium
    ("v5p", 459e12),
    ("v5 lite", 197e12),  # v5e
    ("v5e", 197e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
)


def peak_flops_per_device() -> float | None:
    kind = jax.devices()[0].device_kind.lower()
    for key, flops in _PEAK_FLOPS:
        if key in kind:
            return flops
    return None


def transformer_step_flops(
    num_params: int,
    tokens_per_step: int,
    num_layers: int | None = None,
    hidden_size: int | None = None,
    seq_len: int | None = None,
) -> float:
    """FLOPs for one fwd+bwd step: 6·N·T plus the attention quadratic term
    12·L·S·H·T when the shape is known (PaLM appendix B convention)."""
    flops = 6.0 * num_params * tokens_per_step
    if num_layers and hidden_size and seq_len:
        flops += 12.0 * num_layers * hidden_size * seq_len * tokens_per_step
    return flops


class TrainingTimeEstimatorConfig(BaseModel):
    model_config = ConfigDict(extra="forbid")

    # measure steps [skip_first_n_steps, skip_first_n_steps + num_steps)
    num_steps: int = Field(20, ge=1)
    skip_first_n_steps: int = Field(2, ge=0)  # compile + warmup excluded, like `:40-62`
    stop_after_steps: int | None = Field(None, ge=1)  # dry run: end the fit afterwards


class TrainingTimeEstimator:
    """Reports steps/sec, tokens/sec(/device), MFU, and extrapolated total
    training time once the measurement window closes."""

    def __init__(self, config: TrainingTimeEstimatorConfig | None = None):
        self.config = config or TrainingTimeEstimatorConfig()
        self._t0 = None
        self._start_step = None
        self._start_tokens = 0
        self._num_params = None
        self._flops_hint: dict = {}
        self.result: dict | None = None

    def on_fit_start(self, trainer, objective, datamodule, start_step) -> None:
        self._fit_start_step = start_step
        model_cfg = getattr(getattr(objective, "model", None), "config", None)
        if model_cfg is not None:
            self._flops_hint = dict(
                num_layers=getattr(model_cfg, "num_hidden_layers", None),
                hidden_size=getattr(model_cfg, "hidden_size", None),
            )

    def _maybe_count_params(self, trainer) -> None:
        if self._num_params is None and getattr(trainer, "abstract_state", None) is not None:
            self._num_params = sum(
                leaf.size for leaf in jax.tree.leaves(trainer.abstract_state.params)
            )

    def on_train_step(self, trainer, step) -> None:
        cfg = self.config
        begin = self._fit_start_step + cfg.skip_first_n_steps
        if step >= begin and self._t0 is None:
            # drain the async dispatch queue: without this, perf_counter
            # timestamps measure dispatch rate, not device step time
            self._sync(trainer)
            self._t0 = time.perf_counter()
            self._start_step = step
            self._start_tokens = trainer.counters["consumed_tokens"]
        if self._t0 is not None and self.result is None and step - self._start_step >= cfg.num_steps:
            self._finish(trainer, step)
        if cfg.stop_after_steps and step - self._fit_start_step >= cfg.stop_after_steps:
            trainer.should_stop = True

    @staticmethod
    def _sync(trainer) -> None:
        if getattr(trainer, "last_metrics", None) is not None:
            jax.block_until_ready(trainer.last_metrics)

    def _finish(self, trainer, step) -> None:
        self._maybe_count_params(trainer)
        self._sync(trainer)
        elapsed = time.perf_counter() - self._t0
        steps = step - self._start_step
        tokens = trainer.counters["consumed_tokens"] - self._start_tokens
        n_dev = len(jax.devices())
        steps_per_sec = steps / elapsed
        tokens_per_sec = tokens / elapsed
        result = {
            "measured_steps": steps,
            "steps_per_sec": steps_per_sec,
            "tokens_per_sec": tokens_per_sec,
            "tokens_per_sec_per_device": tokens_per_sec / n_dev,
            "estimated_total_hours": (
                trainer.config.max_steps / steps_per_sec / 3600.0
            ),
        }
        peak = peak_flops_per_device()
        if self._num_params and peak:
            seq_len = getattr(trainer, "last_seq_len", None)
            step_flops = transformer_step_flops(
                self._num_params,
                int(tokens / steps),
                seq_len=seq_len,
                **self._flops_hint,
            )
            result["model_flops_per_step"] = step_flops
            result["mfu"] = step_flops * steps_per_sec / (peak * n_dev)
        # cross-check against XLA's own FLOP count for the compiled step
        # (telemetry gauge set by the trainer's AOT pre-compile). XLA counts
        # executed FLOPs per device — including remat recompute the analytic
        # model deliberately excludes — so mfu_xla >= mfu is expected under
        # gradient checkpointing; a LOWER mfu_xla flags a stale FLOP model
        telemetry = getattr(trainer, "telemetry", None)
        if telemetry is not None:
            # the gauge is PER-DEVICE FLOPs per train_step INVOCATION (one
            # micro-batch of the SPMD module); scale by accumulation and
            # device count so the published key is global per OPTIMIZER
            # step — the same units as model_flops_per_step above
            xla_flops = telemetry.snapshot().get("xla/flops_per_step")
            accum = getattr(getattr(trainer, "config", None), "accumulate_grad_batches", 1)
            if xla_flops and peak:
                global_xla_flops = xla_flops * accum * n_dev
                result["xla_flops_per_step"] = global_xla_flops
                result["mfu_xla"] = global_xla_flops * steps_per_sec / (peak * n_dev)
            # publish for the log-step metrics merge -> telemetry.jsonl ->
            # `report` (perf/ prefix routes them)
            for key, value in result.items():
                if isinstance(value, (int, float)):
                    telemetry.gauge(f"perf/{key}").set(float(value))
        self.result = result
        logger.info(
            "training time estimate: %s",
            {k: (round(v, 4) if isinstance(v, float) else v) for k, v in result.items()},
        )

    def on_fit_end(self, trainer, state) -> None:
        # short runs: close the window with whatever was measured
        if (
            self.result is None
            and self._t0 is not None
            and trainer.last_step is not None
            and trainer.last_step > self._start_step
        ):
            self._finish(trainer, trainer.last_step)
