"""Telemetry & goodput subsystem.

One registry + one goodput ledger per fit (owned by the Trainer), device
gauges sampled on log steps, `jax.profiler` annotations naming the same
phases, a model-health layer (per-layer grad/update norms, MoE router
health, host-side spike detection + anomaly dumps), a request/step trace
recorder with a crash flight recorder (`telemetry/trace.py`), and a
`report` CLI that renders the persisted artifacts. See
docs/observability.md for the schema and phase definitions.

The package surface stays jax-free at import time: the health layer (the
one jax-importing submodule) loads lazily through ``__getattr__``, so the
serve scheduler — a graftlint jax-free contract — can import the tracer
through this package without pulling a backend.
"""

from llm_training_tpu.telemetry.anomaly import (
    EmaZScore,
    dump_anomaly,
    offending_layers,
    resolve_run_dir,
    top_layers,
)
from llm_training_tpu.telemetry.device import (
    HBMTimeline,
    compiled_attribution_gauges,
    compiled_cost_gauges,
    hbm_gauges,
)
from llm_training_tpu.telemetry.exporter import (
    MetricsExporter,
    resolve_metrics_port,
    start_exporter,
)
from llm_training_tpu.telemetry.goodput import PHASES, GoodputLedger
from llm_training_tpu.telemetry.profiling import (
    ProfileTrigger,
    build_profile_trigger,
    compile_totals,
    get_profile_trigger,
    install_compile_listener,
    install_trace_annotator,
    mark_setup_ready,
    set_profile_trigger,
)
from llm_training_tpu.telemetry.slo import (
    SLOMonitor,
    build_slo_monitor,
    slo_config_from_env,
)
from llm_training_tpu.telemetry.registry import (
    TelemetryRegistry,
    get_registry,
    set_registry,
)
from llm_training_tpu.telemetry.trace import (
    TraceRecorder,
    get_tracer,
    set_tracer,
)

# health imports jax at module level; resolve these names on first access so
# the package import graph stays backend-free (PEP 562)
_LAZY_HEALTH = (
    "HealthConfig",
    "build_param_groups",
    "layer_health_metrics",
    "moe_router_health",
)


def __getattr__(name):
    if name in _LAZY_HEALTH:
        from llm_training_tpu.telemetry import health

        return getattr(health, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "PHASES",
    "EmaZScore",
    "GoodputLedger",
    "HBMTimeline",
    "HealthConfig",
    "MetricsExporter",
    "ProfileTrigger",
    "SLOMonitor",
    "TelemetryRegistry",
    "TraceRecorder",
    "build_param_groups",
    "build_profile_trigger",
    "build_slo_monitor",
    "compile_totals",
    "compiled_attribution_gauges",
    "compiled_cost_gauges",
    "dump_anomaly",
    "get_profile_trigger",
    "get_registry",
    "get_tracer",
    "hbm_gauges",
    "install_compile_listener",
    "install_trace_annotator",
    "mark_setup_ready",
    "set_profile_trigger",
    "layer_health_metrics",
    "moe_router_health",
    "offending_layers",
    "resolve_metrics_port",
    "resolve_run_dir",
    "set_registry",
    "set_tracer",
    "slo_config_from_env",
    "start_exporter",
    "top_layers",
]
