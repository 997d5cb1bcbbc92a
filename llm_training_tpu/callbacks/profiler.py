"""jax.profiler trace capture over a step window.

TPU-native addition with no reference analogue (SURVEY.md §5.1: the
reference has no profiler integration). Captures an XLA/TensorBoard trace
for steps [start_step, start_step + num_steps) — the standard workflow for
finding HBM-bound ops and collective stalls.

When the fit owns a `ProfileTrigger` (telemetry/profiling.py), this
callback goes passive: the trainer reads `profile_window()` at fit start,
schedules the window on the trigger (same budget accounting, artifacts
inside the run dir by default), and marks the callback `_absorbed` — one
owner for jax.profiler.start/stop_trace, so a breach-fired capture can
never nest inside a config-window capture. The standalone path below is
kept for direct use outside a trainer fit (scripts, tests).
"""

from __future__ import annotations

import logging

import jax
from pydantic import BaseModel, ConfigDict

logger = logging.getLogger(__name__)

# standalone fallback only; inside a fit the ProfileTrigger resolves an
# unset trace_dir to <run_dir>/profile-window-<start> instead
DEFAULT_TRACE_DIR = "runs/profile"


class ProfilerCallbackConfig(BaseModel):
    model_config = ConfigDict(extra="forbid")

    # None = let the owner pick (ProfileTrigger: inside the run dir;
    # standalone: DEFAULT_TRACE_DIR)
    trace_dir: str | None = None
    start_step: int = 5  # past compile/warmup
    num_steps: int = 3


class ProfilerCallback:
    def __init__(self, config: ProfilerCallbackConfig | None = None):
        self.config = config or ProfilerCallbackConfig()
        self._active = False
        self._stop_step: int | None = None
        # set by the trainer when the window was handed to a ProfileTrigger
        self._absorbed = False

    def profile_window(self) -> tuple[int, int, str | None]:
        """The configured capture window, for a ProfileTrigger to adopt."""
        cfg = self.config
        return cfg.start_step, cfg.num_steps, cfg.trace_dir

    def on_train_step(self, trainer, step) -> None:
        if self._absorbed:
            return
        cfg = self.config
        if not self._active and cfg.start_step <= step < cfg.start_step + cfg.num_steps:
            # explicit stop boundary, clamped to the fit's last step: when
            # start_step + num_steps overruns max_steps the trace must still
            # stop inside the loop (at the final step) rather than relying
            # on teardown after the fit unwinds
            stop_step = cfg.start_step + cfg.num_steps
            max_steps = getattr(getattr(trainer, "config", None), "max_steps", None)
            if max_steps is not None:
                stop_step = min(stop_step, max_steps)
            if step >= stop_step:
                # zero-length window (e.g. start_step == max_steps): a trace
                # started now would capture only the fit epilogue — no later
                # on_train_step exists to close it inside the loop
                logger.warning(
                    "profiler window [%d, %d) truncated to nothing at step %d; "
                    "not tracing", cfg.start_step, cfg.start_step + cfg.num_steps, step,
                )
                return
            if cfg.trace_dir is None:
                # write the resolved dir back so callers (and tests) read
                # the actual capture location off the config afterwards
                cfg.trace_dir = DEFAULT_TRACE_DIR
            self._stop_step = stop_step
            jax.profiler.start_trace(cfg.trace_dir)
            self._active = True
            logger.info("profiler trace started at step %d -> %s", step, cfg.trace_dir)
        elif self._active and self._stop_step is not None and step >= self._stop_step:
            jax.profiler.stop_trace()
            self._active = False
            logger.info("profiler trace stopped at step %d", step)

    def on_fit_end(self, trainer, state) -> None:
        self.teardown()

    def teardown(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
