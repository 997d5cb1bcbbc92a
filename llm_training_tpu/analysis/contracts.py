"""Repo-specific contract tables the rules check against.

This is deliberately data-in-code (not a config file): a contract change is
a reviewed diff next to the code that carries it, and each entry records WHY
the invariant exists so a violation message can say more than "don't".
"""

from __future__ import annotations

# ---------------------------------------------------------------- rule 2
# Modules whose MODULE-LEVEL import graph must never reach jax (transitively
# through repo-internal module-level imports; function-body imports are the
# sanctioned lazy escape hatch). Keys are repo-relative paths; values are
# the reason the contract exists — quoted in the violation message.
JAX_FREE_CONTRACTS: dict[str, str] = {
    "llm_training_tpu/resilience/supervisor.py": (
        "the supervisor relaunches dead fits; it must never own a TPU "
        "backend or it dies with the child it is supposed to restart"
    ),
    "llm_training_tpu/resilience/elastic.py": (
        "topology planning runs in the supervisor's pre-backend path "
        "(device probes happen in a subprocess)"
    ),
    "llm_training_tpu/serve/__init__.py": (
        "the serve package surface is host-only (scheduler/allocator); "
        "the engine is the designated lazy import"
    ),
    "llm_training_tpu/serve/paged_cache.py": (
        "the block allocator is pure host policy; the pool constructors "
        "import jax lazily at call time"
    ),
    "llm_training_tpu/serve/scheduler.py": (
        "admission/eviction/chunked-prefill policy is pure host code by "
        "design — testable without a backend"
    ),
    "llm_training_tpu/serve/journal.py": (
        "the request journal is host-side durability bookkeeping; replay "
        "must be readable by supervisors and tests that never touch a "
        "backend"
    ),
    "llm_training_tpu/serve/router.py": (
        "the router is the fleet control plane over serve children: the "
        "replicas own the backends, and a router that initialized jax "
        "would hold the very devices it is supposed to route around"
    ),
    "scripts/router_smoke.py": (
        "the router smoke drives the route CLI as a subprocess, exactly "
        "like the loadgen — the children own the backend"
    ),
    "chip_smoke.py": (
        "the chip smoke's parent runs each phase as a child, strictly one "
        "at a time: a chip belongs to one process, and a parent that had "
        "touched jax would hold it"
    ),
    "llm_training_tpu/compile_cache.py": (
        "the jax-free parent chip_smoke.py names the compile cache "
        "directory through this module; only configure_compile_cache, "
        "called in its children, imports jax"
    ),
    "scripts/serve_loadgen.py": (
        "the loadgen drives the serve CLI as a subprocess and must keep "
        "feeding/timing requests while the child owns the backend"
    ),
    "llm_training_tpu/rl/reward.py": (
        "verifiable rewards are pure host scoring over token lists, run "
        "on the rollout-collection path between engine steps — importing "
        "a backend there couples scoring latency to device state"
    ),
    "scripts/rl_smoke.py": (
        "the RL smoke drives the rl-fit CLI as a subprocess, exactly "
        "like the loadgen — the child owns the backend"
    ),
    "llm_training_tpu/telemetry/trace.py": (
        "the serve scheduler (host-only policy) imports the tracer at "
        "module level, and the trace/report/export paths must run anywhere "
        "the run dir is mounted — tracing can never pull a backend"
    ),
    "llm_training_tpu/telemetry/exporter.py": (
        "scrape handler threads must never own device work: a /metrics or "
        "/healthz request that triggers a jax call can block behind the "
        "exact wedged dispatch the probe exists to report"
    ),
    "llm_training_tpu/telemetry/slo.py": (
        "the SLO monitor is fed from the serve loop and read from the "
        "exporter's scrape thread; breach evaluation must never pay a "
        "backend import or a wedged device stalls the alert that reports it"
    ),
    "llm_training_tpu/telemetry/fleet.py": (
        "the fleet aggregator is a scrape PARENT like the loadgen: it "
        "must keep sweeping while replicas own backends, and the fleet "
        "CLI must run on operator machines that have none"
    ),
    "llm_training_tpu/resilience/durability.py": (
        "the ckpt CLI verifies/mirrors checkpoint trees on operator "
        "machines with no backend, and the mirror daemon thread must "
        "never touch jax or it can block behind the wedged dispatch a "
        "restore is about to recover from"
    ),
    "scripts/durability_smoke.py": (
        "the durability smoke drives fit / ckpt / report as "
        "subprocesses, exactly like the crash-resume smoke — the "
        "children own the backend"
    ),
    # the lint gate itself: precommit runs it before any backend exists and
    # it must stay millisecond-cheap
    "llm_training_tpu/analysis/__init__.py": (
        "the lint gate is the first precommit stage and must never pay a "
        "backend import"
    ),
}

# import roots that violate a jax-free contract when reached module-level
BANNED_IMPORT_ROOTS = ("jax", "jaxlib")

# ---------------------------------------------------------------- rule 4
# where the telemetry routing registry lives; the rule parses the literal
# TELEMETRY_PREFIXES / TELEMETRY_KEYS tuples out of this file's AST so the
# lint can never drift from what the logger actually routes
TELEMETRY_REGISTRY_FILE = "llm_training_tpu/callbacks/loggers.py"

# attribute-call receivers that publish metrics: any `<recv>.gauge(name)` /
# `.counter(name)` / `.timer(name)` where the receiver's terminal identifier
# contains one of these substrings (registry, self.telemetry, get_registry())
TELEMETRY_RECEIVER_HINTS = ("registry", "telemetry")
TELEMETRY_PUBLISH_METHODS = ("gauge", "counter", "timer")

# ---------------------------------------------------------------- rule 5
# env-var namespaces this repo owns; every read of one must be documented
ENV_VAR_PATTERN = r"^(LLMT|FLASH|PAGED)_[A-Z0-9]+(?:_[A-Z0-9]+)*$"

# the docs corpus an env var must appear in (any of these files)
ENV_DOC_FILES = (
    "README.md",
    "docs/performance.md",
    "docs/resilience.md",
    "docs/serving.md",
    "docs/observability.md",
    "docs/inference.md",
    "docs/config.md",
    "docs/parallelism.md",
    "docs/static-analysis.md",
    "docs/post-training.md",
)

# ---------------------------------------------------------------- rule 6
# where the known-logical-axes registry lives; the `logical-axis-literal`
# rule parses the literal KNOWN_LOGICAL_AXES tuple out of this file's AST
# (same never-drifts trick as rule 4) so axis-name typos in models/ fail
# at lint time, before the shardcheck audit ever eval_shapes anything
SHARDING_REGISTRY_FILE = "llm_training_tpu/parallel/sharding.py"
KNOWN_AXES_NAME = "KNOWN_LOGICAL_AXES"
# calls whose tuple arguments carry logical-axis names
LOGICAL_AXIS_CALLS = ("with_logical_partitioning", "with_logical_constraint")
# helper functions threading axes through (llama/gemma `_dense`) declare
# the parameter under this name; literal tuples at their call sites count
LOGICAL_AXIS_PARAM = "logical_axes"
# the directory whose files the rule scans (model param metadata only;
# tests construct intentionally-broken fixtures)
MODELS_DIR = "llm_training_tpu/models/"

# ------------------------------------------------------- racecheck (--races)
# Classes / module functions a FOREIGN thread is contractually allowed to
# call — concurrency the AST cannot see from their own module (the spawn
# site lives elsewhere). Keys are repo-relative paths; inner keys are class
# or function names; values are WHY the surface is cross-thread — quoted in
# findings so a violation message explains the contract it broke. Declaring
# a class here makes racecheck require a `# guarded by:` declaration (and a
# held lock at every mutation) for each of its shared attributes.
THREAD_SHARED_CONTRACTS: dict[str, dict[str, str]] = {
    "llm_training_tpu/telemetry/registry.py": {
        "Counter": "producer threads (prefetcher, checkpointer) record "
        "concurrently with the step loop",
        "Gauge": "same contract as Counter — any thread may publish",
        "Timer": "same contract as Counter — any thread may time",
        "TelemetryRegistry": "the registry's docstring contract: all "
        "mutation goes through one RLock, so any thread may record",
        "get_registry": "the module-global current registry is read from "
        "worker threads (new threads do not inherit contextvars)",
    },
    "llm_training_tpu/telemetry/trace.py": {
        "TraceRecorder": "the ring is the crash flight recorder — the "
        "watchdog thread flight-dumps it while the main loop records",
        "get_tracer": "worker threads and the watchdog resolve the "
        "process tracer through this module global",
        "set_tracer": "same global as get_tracer",
    },
    "llm_training_tpu/telemetry/goodput.py": {
        "GoodputLedger": "the hang watchdog reads current_phase from its "
        "poll thread while the train loop brackets phases — and the "
        "metrics exporter's scrape threads render summary()/current_phase "
        "per /metrics///statusz request",
    },
    "llm_training_tpu/telemetry/exporter.py": {
        "MetricsExporter": "the HTTP server's per-request handler threads "
        "render scrapes while the owning loop starts/stops the exporter "
        "and mutates the scrape counters",
    },
    "llm_training_tpu/telemetry/slo.py": {
        "SLOMonitor": "the serve loop / train loop observe requests and "
        "steps while the exporter's scrape threads read last_alert() and "
        "breach counts",
    },
    "llm_training_tpu/telemetry/profiling.py": {
        "ProfileTrigger": "the request surface is called from the SLO "
        "breach path, the watchdog poll thread, /profilez handler "
        "threads, and the serve stdin path while the owning loop polls "
        "capture transitions",
        "get_profile_trigger": "breach paths and handler threads resolve "
        "the process trigger through this module global",
        "set_profile_trigger": "same global as get_profile_trigger",
    },
    "llm_training_tpu/telemetry/fleet.py": {
        "FleetAggregator": "the background sweep loop publishes snapshots "
        "while the federation server's per-request handler threads render "
        "them and the owner starts/stops the aggregator",
    },
    "llm_training_tpu/serve/journal.py": {
        "RequestJournal": "the serve CLI journals deliveries from its "
        "stdin reader thread while the engine journals progress from the "
        "step loop (the PR 12 lost-delivery race class)",
    },
    "llm_training_tpu/rl/rollout.py": {
        "RolloutCollector": "the collection loop bumps rollout counters "
        "between engine steps while the rl-fit exporter's scrape threads "
        "read stats() per /metrics request",
    },
    "llm_training_tpu/serve/router.py": {
        "Router": "the route CLI's main loop mutates routing state while "
        "the exporter's scrape threads render live_stats() and the "
        "per-replica stdout reader threads feed the event queue",
    },
    "llm_training_tpu/resilience/chaos.py": {
        "Chaos": "chaos_point fires from the prefetcher worker (data "
        "site) concurrently with trainer-thread sites",
        "chaos_point": "the process-global harness is read from worker "
        "threads at every injection site",
        "get_chaos": "same global as chaos_point (the serve engine reads "
        "it from the step loop)",
    },
    "llm_training_tpu/resilience/durability.py": {
        "MirrorDaemon": "the mirror/scrub thread mutates the mirrored/"
        "failed bookkeeping sets while the owning Checkpointer calls "
        "notify()/drain()/stats() from the train loop's save and wait "
        "barriers",
    },
    "llm_training_tpu/resilience/watchdog.py": {
        "HangWatchdog": "beat() is called from the prefetcher worker "
        "(heartbeat hook) as well as the train loop, racing the poll "
        "thread's staleness checks",
    },
}

# Global lock-acquisition order (outer first): while holding a lock, only
# locks LATER in this tuple may be acquired. The interleaving harness
# (analysis/interleave.py) records acquisition edges at test time and
# asserts them against this order; the static race-lock-order rule reports
# inversions it can prove lexically. Rationale: the journal/trace/registry
# locks are leaves that any subsystem may take while doing its own locked
# work (metric publication, flight dumps), so they sort last; harness and
# watchdog locks wrap policy decisions and sort first.
LOCK_ORDER = (
    "chaos",     # resilience/chaos.py Chaos._lock + _active_lock
    "router",    # serve/router.py Router._lock — wraps routing policy and
                 # appends to the router's RequestJournal while held (the
                 # assignment/terminal records must be atomic with the
                 # routing-state transition they witness), so it must sort
                 # before "journal"; chaos hooks fire outside it
    "fleet",     # telemetry/fleet.py FleetAggregator._lock (snapshot swap
                 # only; sweeps compose — scrapes, rollups, the SLO feed —
                 # entirely outside it, so no edge into slo/registry)
    "exporter",  # telemetry/exporter.py MetricsExporter._lock (scrape
                 # counters only; handlers compose responses WITHOUT
                 # holding it while calling other subsystems)
    "watchdog",  # resilience/watchdog.py HangWatchdog._lock
    "goodput",   # telemetry/goodput.py GoodputLedger._lock
    "slo",       # telemetry/slo.py SLOMonitor._lock (window state only;
                 # breach side effects emit after release)
    "profiling", # telemetry/profiling.py ProfileTrigger._lock +
                 # _current_lock (admission state only; counter/tracer
                 # side effects and jax.profiler calls all happen after
                 # release, so no edge into trace/registry)
    "rl",        # rl/rollout.py RolloutCollector._lock (counter dict
                 # only; harvest/trace side effects emit after release,
                 # so no edge into trace/registry beyond the leaf order)
    "durability", # resilience/durability.py MirrorDaemon._lock (the
                 # mirrored/failed bookkeeping sets only; all filesystem
                 # work and every registry publication happen OUTSIDE it,
                 # so its only potential edge is into the registry leaf)
    "journal",   # serve/journal.py RequestJournal._lock
    "trace",     # telemetry/trace.py TraceRecorder._lock + _current_lock
    "registry",  # telemetry/registry.py TelemetryRegistry._lock (leaf)
)

# ---------------------------------------------------------------- rule 7
# Why thread targets must stay jax-free (the `thread-jax-free` rule): the
# host layer's threads exist to stay responsive while the main thread owns
# the device — a watchdog that calls into jax can block behind the exact
# wedged dispatch it is supposed to diagnose, and a reader/journal thread
# that triggers compilation stalls intake for seconds. The ONE sanctioned
# exception is the DevicePrefetcher worker, whose entire job is overlapping
# jax.device_put with the step — it carries an inline
# `# lint: allow(thread-jax-free)` suppression with this rationale.
THREAD_JAX_FREE_WHY = (
    "host-layer threads (watchdog, stdin reader, journal, timers) must "
    "never own device work: a jax call there can deadlock behind the "
    "wedged main-thread dispatch it exists to outlive"
)

# ---------------------------------------------------------------- rule 3
# jit wrappers whose first function argument starts a traced region
JIT_WRAPPERS = ("jit", "pjit")
# higher-order jax/functools combinators that forward their function-valued
# arguments into the traced region
HIGHER_ORDER = (
    "grad",
    "value_and_grad",
    "vmap",
    "pmap",
    "remat",
    "checkpoint",
    "custom_vjp",
    "custom_jvp",
    "scan",
    "cond",
    "switch",
    "while_loop",
    "fori_loop",
    "map",
    "associative_scan",
    "shard_map",
    "partial",
    "defvjp",
    "defjvp",
)
