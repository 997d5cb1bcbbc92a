"""Triggered on-device profiler captures (docs/observability.md#profiling).

The device plane's flight recorder: when the host side decides something
is wrong — an SLO burn-rate breach, a hang watchdog about to SIGABRT, an
anomaly or rollback, an operator hitting `/profilez` or the serve
`{"type": "profile"}` control line — the NEXT few steps are exactly the
ones worth a device profile; a capture started any later records a
healthy program. `ProfileTrigger` splits the work across the two sides
of the repo's jax-free boundary:

- The **request surface** (`request()`, `schedule()`, `status()`) is
  jax-free and callable from any thread: the SLO monitor's breach path,
  the watchdog's dump path, the exporter's scrape handler threads, the
  serve stdin reader. It only records intent — enforcing the capture
  budget and cooldown (`LLMT_PROFILE_*` envs) so a burn-rate storm
  cannot profile-storm the run dir — and bumps `profile/*` counters.
- The **capture side** (`poll()`, `teardown()`) runs ONLY in the loop
  that owns the device (the trainer's optimizer-step loop, the serve
  engine loop). It imports jax lazily and drives
  `jax.profiler.start_trace`/`stop_trace` over a short step window. jax
  forbids nested captures, so a request arriving while a window is open
  is counted `profile/suppressed` instead of racing a second start —
  and the watchdog's pre-SIGABRT request can only ever be the marker
  half: its poll thread must never touch jax (a capture call there
  would block behind the very wedged dispatch it is reporting), so a
  hang profile materializes only if the loop limps through another
  step.

Artifacts land beside the correlated host flight dumps with MATCHING
tags: breach `n` of SLO target `train/step_time_p99_s` produces
`trace-flight-slo-train-step_time_p99_s-n.jsonl` (the host trace ring)
and `profile-slo-train-step_time_p99_s-n/` (the device trace) in the
same run dir, plus a `profile-<tag>.json` manifest that `report`
renders as the `== Profiling ==` section.
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from pathlib import Path

from llm_training_tpu import PROCESS_START
from llm_training_tpu.telemetry.registry import get_registry
from llm_training_tpu.telemetry.trace import PINNED_CAPACITY, get_tracer

logger = logging.getLogger(__name__)

# fallback artifact root when no run dir is known (mirrors the old
# ProfilerCallback default, so unconfigured captures stay findable)
DEFAULT_TRACE_ROOT = "runs/profile"

_TAG_SANITIZE = re.compile(r"[^A-Za-z0-9._-]+")


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        logger.warning("ignoring malformed %s=%r (want a float)", name, raw)
        return default


def _env_int(name: str, default: int) -> int:
    return int(_env_float(name, float(default)))


def install_trace_annotator(tracer=None) -> None:
    """The jax-holding half of `TraceRecorder.measure`: from here on every
    span the process tracer opens is doubled as a
    `jax.profiler.TraceAnnotation` (`llmt/<cat>/<name>`, the span's args as
    keyword arguments), so a profiler capture — a `ProfileTrigger` window,
    a benchmark's traced run — holds the program's own spans on the device
    trace's clock. With no capture open an annotation is a flag test.
    Called where the loops that own the device are built (`ServingEngine`,
    `Trainer.fit`); idempotent."""
    import jax

    (tracer or get_tracer()).set_annotator(
        lambda name, args: jax.profiler.TraceAnnotation(name, **args)
    )


# ------------------------------------------------------- start-up timeline
# (docs/observability.md#tracing, "Start-up timeline")

# jax's own duration events, and the `compile/<kind>` span each is recorded as
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # the backend's compile OR the persistent cache's read in its place
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
COMPILE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile/cache_hits",
    "/jax/compilation_cache/cache_misses": "compile/cache_misses",
}
# a shorter event is counted and not pinned: an inner function's trace lies
# inside its program's, hundreds of them of a millisecond each
PIN_SECONDS = 0.1
# the (kind, fun) rows the table keeps by name; what comes later sums under OTHER_FUN
_TABLE_ROWS = 1024
OTHER_FUN = "<other>"
# the spans that open a loop's own part of start-up: what lies before the
# first of them is the process's (imports, the backend, the weights)
LOOP_OPENERS = ("engine_init", "fit_prepare")


class _CompileListener:
    """What `install_compile_listener` registers, once a process: jax's
    compile events into a per-(kind, fun) table of count and seconds, the
    long ones into the tracer's pinned store as `compile/<kind>` spans."""

    def __init__(self):
        self._lock = threading.Lock()
        self.registered = False  # guarded by: _lock
        self.tracer = None  # guarded by: _lock (None: the process tracer at the event)
        self._table: dict[tuple[str, str], list] = {}  # guarded by: _lock
        self._short: dict[str, list] = {}  # guarded by: _lock (events under PIN_SECONDS, a kind)
        # a cache read is heard inside the backend event that follows it on
        # the same thread: held here until that event names its program
        self._cache_reads: dict[int, float] = {}  # guarded by: _lock
        self._ready = False  # guarded by: _lock
        self._heard = 0  # guarded by: _lock
        self._callback_s = 0.0  # guarded by: _lock

    def on_duration(self, event: str, duration: float, **kwargs) -> None:
        kind = COMPILE_EVENTS.get(event)
        if kind is None:
            return
        t_in = time.perf_counter()
        fun = str(kwargs.get("fun_name", "")).removeprefix("jit(").removesuffix(")")
        args = {"fun": fun} if fun else {}
        with self._lock:
            thread = threading.get_ident()
            if kind == "cache_read":
                self._cache_reads[thread] = duration
            elif kind == "backend" and thread in self._cache_reads:
                args["cache_read_s"] = self._cache_reads.pop(thread)
                self._add_locked("cache_read", fun, args["cache_read_s"])
            if kind != "cache_read":
                self._add_locked(kind, fun, duration)
            if duration < PIN_SECONDS:
                short = self._short.setdefault(kind, [0, 0.0])
                short[0] += 1
                short[1] += duration
            # a program handed to the backend after `setup/ready` is a
            # recompile (a new shape): one event a program, which carries the
            # program's own trace and lowering, whatever their length
            recompile = self._ready and kind == "backend"
            if recompile:
                args["after_ready"] = True
                for earlier in ("trace", "lower"):
                    args[f"{earlier}_s"] = self._table.get((earlier, fun), (0, 0.0, 0.0))[2]
            self._heard += 1
            if duration < PIN_SECONDS and not recompile:
                # all there is to nearly every event: one lock, two dictionaries
                self._callback_s += time.perf_counter() - t_in
                return
            tracer = self.tracer
        tracer = tracer or get_tracer()
        # a recompile is pinned whatever its length, while the store is under
        # half full: a process that goes on compiling (a benchmark's reference
        # after the window) cannot push its own start-up out
        if duration >= PIN_SECONDS or len(tracer.pinned()) < PINNED_CAPACITY // 2:
            # the event arrives when the work is over: [now - duration, now)
            now = tracer.clock()
            tracer.span("compile", kind, now - duration, now, pin=True, **args)
        if recompile:
            get_registry().counter("compile/after_ready").inc()
        with self._lock:
            self._callback_s += time.perf_counter() - t_in

    def _add_locked(self, kind: str, fun: str, seconds: float) -> None:
        """One event into the table, a row of count, seconds and the newest
        event's seconds. Caller holds `_lock`."""
        key = (kind, fun)
        # lint: allow(race-unguarded-shared): _locked-suffix helper — on_duration calls it inside its `with self._lock:` block
        if key not in self._table and len(self._table) >= _TABLE_ROWS:
            key = (kind, OTHER_FUN)
        # lint: allow(race-unguarded-shared): _locked-suffix helper — caller (on_duration) holds _lock across this call
        row = self._table.setdefault(key, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += seconds
        row[2] = seconds

    def on_event(self, event: str, **_) -> None:
        name = COMPILE_COUNTERS.get(event)
        if name is not None:
            get_registry().counter(name).inc()

    def totals(self) -> dict:
        with self._lock:
            kinds: dict[str, dict] = {}
            by_fun: dict[str, dict] = {}
            for (kind, fun), (count, seconds, _) in self._table.items():
                total = kinds.setdefault(kind, {
                    "count": 0, "seconds": 0.0, "short_count": 0, "short_seconds": 0.0,
                })
                total["count"] += count
                total["seconds"] += seconds
                by_fun.setdefault(kind, {})[fun] = {"count": count, "seconds": seconds}
            for kind, (count, seconds) in self._short.items():
                if kind in kinds:
                    kinds[kind]["short_count"] = count
                    kinds[kind]["short_seconds"] = seconds
            return {
                "kinds": kinds, "by_fun": by_fun,
                "heard": self._heard, "callback_s": self._callback_s,
            }

    def set_ready(self) -> None:
        with self._lock:
            self._ready = True


_compile_listener = _CompileListener()


def install_compile_listener(tracer=None) -> None:
    """The program hears jax's compile events: from here on every trace,
    lowering, backend compile and cache read of the process adds to
    `compile_totals()`, and the ones of `PIN_SECONDS` or more (after
    `setup/ready`: every backend event) are pinned in the tracer as
    `compile/<kind>` spans with the program's name as `fun`
    (`tracer`: where to, from now on; by default the process tracer as it is
    when the event comes). One registration a process, whoever calls
    (`jax.monitoring` keeps a listener for good): `configure_compile_cache`,
    `ServingEngine`, `Trainer.fit`, each where a loop begins to start up, so
    a call also ends the time "after ready" until `mark_setup_ready` is
    called again. The listener is a dictionary update an event; inside a
    steady window jax fires none."""
    listener = _compile_listener
    with listener._lock:
        listener.tracer = tracer
        # a loop is starting up: what it compiles is no recompile
        listener._ready = False
        if listener.registered:
            return
        listener.registered = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(listener.on_duration)
    jax.monitoring.register_event_listener(listener.on_event)


def compile_totals() -> dict:
    """What the listener has heard so far: `kinds` (count and seconds a kind,
    the events under `PIN_SECONDS` apart as `short_*`), `by_fun` (the same a
    program or function), and what hearing cost (`heard` events, `callback_s`
    seconds inside the callbacks)."""
    return _compile_listener.totals()


def _union_s(spans: list[dict]) -> float:
    """Seconds some span of `spans` covers (an inner function's long trace
    lies inside its program's and counts once)."""
    total, end = 0.0, float("-inf")
    for t0, t1 in sorted((e["ts"], e["ts"] + e["dur"]) for e in spans):
        total += max(0.0, t1 - max(t0, end))
        end = max(end, t1)
    return total


def mark_setup_ready(tracer=None, **args) -> None:
    """A loop has run each of its programs once: the pinned instant
    `setup/ready`, whose args are the start-up timeline in one line
    (`ready_s` since the package's first clock reading, `pre_loop_s` of it
    before the loop's first own span, the seconds the pinned `compile/*`
    spans cover a kind, and the listener's totals as they stand, the short
    events' part apart), and the gauge `setup/ready_s`. From the first one on
    every program handed to the backend counts in `compile/after_ready` and
    is pinned with its name: a recompile."""
    tracer = tracer or get_tracer()
    now = tracer.clock()
    pinned = [e for e in tracer.pinned() if e["ts"] <= now]
    loop_start = min(
        (e["ts"] for e in pinned if e["cat"] == "setup" and e["name"] in LOOP_OPENERS),
        default=now,
    )
    summary = {
        "ready_s": now - PROCESS_START,
        "pre_loop_s": max(0.0, loop_start - PROCESS_START),
    }
    totals = compile_totals()
    for kind, total in totals["kinds"].items():
        spans = [e for e in pinned if e["cat"] == "compile" and e["name"] == kind]
        # backend events do not nest; trace and lowering events do
        summary[f"{kind}_pinned_s"] = _union_s(spans)
        summary[f"{kind}_s"] = total["seconds"]
        summary[f"{kind}_n"] = total["count"]
        summary[f"{kind}_short_s"] = total["short_seconds"]
        summary[f"{kind}_short_n"] = total["short_count"]
    tracer.instant("setup", "ready", ts=now, pin=True, **args, **summary)
    get_registry().gauge("setup/ready_s").set(summary["ready_s"])
    _compile_listener.set_ready()


def sanitize_tag(tag: str) -> str:
    """Tags become file/dir names next to the flight dumps; collapse
    anything path-hostile instead of refusing the capture."""
    return _TAG_SANITIZE.sub("-", str(tag)).strip("-") or "capture"


class ProfileTrigger:
    """On-demand `jax.profiler` capture windows with budget + cooldown.

    One instance per process, owned by the loop that owns the device and
    published through `set_profile_trigger` so the jax-free layers (SLO
    monitor, watchdog, exporter handlers, serve reader) can reach the
    request surface without importing anything device-shaped.
    """

    def __init__(
        self,
        run_dir=None,
        registry=None,
        budget: int | None = None,
        cooldown_s: float | None = None,
        window_steps: int | None = None,
        clock=time.monotonic,
    ):
        self.run_dir = Path(run_dir) if run_dir else None
        self._registry = registry
        self._clock = clock
        # env knobs (docs/observability.md#profiling); explicit args win
        self.budget = (
            budget if budget is not None
            else _env_int("LLMT_PROFILE_BUDGET", 4)
        )
        self.cooldown_s = (
            cooldown_s if cooldown_s is not None
            else _env_float("LLMT_PROFILE_COOLDOWN_S", 120.0)
        )
        self.window_steps = max(1, (
            window_steps if window_steps is not None
            else _env_int("LLMT_PROFILE_STEPS", 2)
        ))
        root = os.environ.get("LLMT_PROFILE_DIR")
        if root:
            self.artifact_root = Path(root)
        elif self.run_dir is not None:
            self.artifact_root = self.run_dir
        else:
            self.artifact_root = Path(DEFAULT_TRACE_ROOT)
        self._lock = threading.Lock()
        self._pending: dict | None = None  # guarded by: _lock — accepted request awaiting poll()
        self._scheduled: list[dict] = []  # guarded by: _lock — config step windows
        self._active: dict | None = None  # guarded by: _lock — the open capture
        self._captures = 0  # guarded by: _lock
        self._requested = 0  # guarded by: _lock
        self._suppressed = 0  # guarded by: _lock
        self._last_accept_t: float | None = None  # guarded by: _lock
        self._history: list[dict] = []  # guarded by: _lock — completed captures (bounded)
        self._torn_down = False  # guarded by: _lock

    # ------------------------------------------------- jax-free request side

    def request(self, tag: str, source: str = "manual") -> dict:
        """Arm a capture window for the owning loop's next `poll()`.

        Jax-free and thread-safe: callable from scrape handlers, the SLO
        breach path, the watchdog poll thread, the serve reader. Returns
        `{"accepted": bool, "reason": ..., "tag": ...}`; a refusal is an
        answer, not an error. Counter side effects emit AFTER the lock is
        released (the SLOMonitor pattern), so this lock adds no edge into
        the registry leaf."""
        tag = sanitize_tag(tag)
        now = self._clock()
        with self._lock:
            if self._torn_down:
                reason = "torn-down"
            elif self._active is not None or self._pending is not None:
                # jax raises on nested start_trace; one window at a time
                reason = "busy"
            elif self._captures + len(self._scheduled) >= self.budget:
                reason = "budget"
            elif (
                self._last_accept_t is not None
                and now - self._last_accept_t < self.cooldown_s
            ):
                reason = "cooldown"
            else:
                reason = None
                self._last_accept_t = now
                self._pending = {"tag": tag, "source": source, "t_request": now}
            self._requested += 1
            if reason is not None:
                self._suppressed += 1
        registry = self._registry
        if registry is not None:
            registry.counter("profile/requested").inc()
            if reason is not None:
                registry.counter("profile/suppressed").inc()
                registry.counter(f"profile/suppressed/{reason}").inc()
        if reason is not None:
            logger.info(
                "profile request %r (source %s) suppressed: %s",
                tag, source, reason,
            )
        return {"accepted": reason is None, "reason": reason, "tag": tag}

    def schedule(
        self,
        start_step: int,
        num_steps: int,
        trace_dir: str | None = None,
        max_steps: int | None = None,
        source: str = "window",
    ) -> bool:
        """Register a config-time step window (the absorbed
        ProfilerCallback path): capture steps `[start_step, start_step +
        num_steps)`, stop boundary clamped to `max_steps` so a window
        overrunning the fit still closes inside the loop. Scheduled
        windows are explicit operator config — they count against the
        budget up front but bypass the cooldown."""
        stop_step = start_step + num_steps
        if max_steps is not None:
            stop_step = min(stop_step, max_steps)
        if stop_step <= start_step:
            logger.warning(
                "profile window [%d, %d) truncated to nothing; not tracing",
                start_step, start_step + num_steps,
            )
            return False
        entry = {
            "tag": sanitize_tag(f"window-{start_step}"),
            "source": source,
            "start_step": start_step,
            "stop_step": stop_step,
            "trace_dir": trace_dir,
        }
        with self._lock:
            self._scheduled.append(entry)
        return True

    def status(self) -> dict:
        """Jax-free snapshot for `/profilez` and tests."""
        with self._lock:
            return {
                "budget": self.budget,
                "cooldown_s": self.cooldown_s,
                "window_steps": self.window_steps,
                "requested": self._requested,
                "captures": self._captures,
                "suppressed": self._suppressed,
                "active": self._active["tag"] if self._active else None,
                "pending": self._pending["tag"] if self._pending else None,
                "scheduled": [dict(s) for s in self._scheduled],
                "history": [dict(h) for h in self._history[-8:]],
            }

    # --------------------------------------------- capture side (owner loop)

    def poll(self, step: int) -> None:
        """Drive at most ONE capture transition for this step. Called only
        by the loop that owns the device; the jax calls happen outside the
        lock, and stop-before-start means a window closing this step never
        nests with one opening."""
        start_info = stop_info = None
        with self._lock:
            if self._active is not None:
                if step >= self._active["stop_step"]:
                    stop_info = self._active
                    self._active = None
            else:
                info = self._take_due_locked(step)
                if info is not None:
                    self._active = info
                    start_info = info
        if stop_info is not None:
            self._finish_capture(stop_info, step)
        if start_info is not None and not self._begin_capture(start_info):
            with self._lock:
                self._active = None

    def _take_due_locked(self, step: int) -> dict | None:
        """The next capture due at `step`, with its window resolved.
        Caller holds `_lock`."""
        if self._torn_down:
            return None
        if self._pending is not None:
            # lint: allow(race-unguarded-shared): _locked-suffix helper — the only caller is poll(), which invokes it inside its `with self._lock:` block; the lexical checker cannot see through the call edge
            info, self._pending = self._pending, None
            info = dict(info)
            info["start_step"] = step
            info["stop_step"] = step + self.window_steps
            info.setdefault("trace_dir", None)
            return info
        for i, entry in enumerate(self._scheduled):
            # never start a window whose clamped stop boundary has passed
            # (a resume landing past the window must not open a trace only
            # teardown would close)
            if entry["start_step"] <= step < entry["stop_step"]:
                # lint: allow(race-unguarded-shared): _locked-suffix helper — caller (poll) holds _lock across this call
                del self._scheduled[i]
                return dict(entry)
            if step >= entry["stop_step"]:
                # lint: allow(race-unguarded-shared): _locked-suffix helper — caller (poll) holds _lock across this call
                del self._scheduled[i]
                return self._take_due_locked(step)
        return None

    def _trace_dir(self, info: dict) -> Path:
        explicit = info.get("trace_dir")
        if explicit:
            return Path(explicit)
        return self.artifact_root / f"profile-{info['tag']}"

    def _begin_capture(self, info: dict) -> bool:
        trace_dir = self._trace_dir(info)
        try:
            import jax

            trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(trace_dir))
        except Exception as e:  # noqa: BLE001 — profiling must never kill the run
            logger.warning(
                "profile capture %r failed to start (%s)", info["tag"], e
            )
            if self._registry is not None:
                self._registry.counter("profile/errors").inc()
            return False
        info["trace_dir"] = str(trace_dir)
        info["t_start"] = self._clock()
        with self._lock:
            self._captures += 1
        registry = self._registry
        if registry is not None:
            registry.counter("profile/captures").inc()
            registry.gauge("profile/last_capture_step").set(
                float(info["start_step"])
            )
        get_tracer().instant(
            "profile", "start", tag=info["tag"], source=info["source"],
            step=info["start_step"],
        )
        logger.info(
            "device profile %r started at step %d -> %s",
            info["tag"], info["start_step"], info["trace_dir"],
        )
        return True

    def _finish_capture(self, info: dict, step: int, reason: str = "window") -> None:
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            logger.warning(
                "profile capture %r failed to stop (%s)", info["tag"], e
            )
            if self._registry is not None:
                self._registry.counter("profile/errors").inc()
            return
        duration = self._clock() - info.get("t_start", self._clock())
        record = {
            "tag": info["tag"],
            "source": info["source"],
            "start_step": info["start_step"],
            "stop_step": step,
            "trace_dir": info.get("trace_dir"),
            "duration_s": round(duration, 4),
            "stopped_by": reason,
        }
        with self._lock:
            self._history.append(record)
            del self._history[:-32]
        registry = self._registry
        if registry is not None:
            registry.gauge("profile/last_capture_duration_s").set(duration)
        get_tracer().instant(
            "profile", "stop", tag=info["tag"], step=step, reason=reason,
        )
        self._write_manifest(record)
        logger.info(
            "device profile %r stopped at step %d (%.2fs)",
            info["tag"], step, duration,
        )

    def _write_manifest(self, record: dict) -> None:
        """`profile-<tag>.json` beside the capture dir — what `report`
        reads. Never raises: a manifest error must not mask the condition
        being profiled."""
        try:
            self.artifact_root.mkdir(parents=True, exist_ok=True)
            path = self.artifact_root / f"profile-{record['tag']}.json"
            with open(path, "w") as f:
                json.dump(record, f)
                f.write("\n")
        except OSError as e:
            logger.warning("profile manifest write failed: %s", e)

    def teardown(self) -> None:
        """Stop a dangling capture (fit died mid-window) and refuse
        further requests. Idempotent; owner-loop only (it calls jax)."""
        with self._lock:
            self._torn_down = True
            active, self._active = self._active, None
            self._pending = None
            self._scheduled = []
        if active is not None:
            self._finish_capture(
                active, active["start_step"], reason="teardown"
            )


# Process-global trigger, mirroring trace.py's get_tracer/set_tracer: the
# jax-free layers (slo breach path, watchdog dump, anomaly dump, serve
# reader) resolve the owner loop's trigger through this module global.
_current_lock = threading.Lock()
_current: ProfileTrigger | None = None  # guarded by: _current_lock


def set_profile_trigger(trigger: ProfileTrigger | None) -> None:
    global _current
    with _current_lock:
        _current = trigger


def get_profile_trigger() -> ProfileTrigger | None:
    with _current_lock:
        return _current


def build_profile_trigger(registry=None, run_dir=None, **kwargs) -> ProfileTrigger:
    """Construct a trigger and publish it as the process global. Always
    returns one (unlike `build_slo_monitor` there is no arming config —
    `LLMT_PROFILE_BUDGET=0` refuses every request but keeps the counters
    and `/profilez` answering honestly)."""
    trigger = ProfileTrigger(run_dir=run_dir, registry=registry, **kwargs)
    set_profile_trigger(trigger)
    return trigger
