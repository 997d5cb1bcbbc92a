"""graftlint: repo-native static analysis (docs/static-analysis.md).

Pure-Python AST checks for the invariants the rest of the codebase runs on
but nothing else enforces — the ones whose violations historically cost
chip-hours before surfacing:

- **pallas-kernel-arity**: every `pl.pallas_call` site's implied ref count
  (scalar prefetch + in_specs + outputs + scratch) matches the kernel's
  positional signature. Round 4's chip run died with `_dq_kernel() missing
  2 required positional arguments`; this rule makes that a lint failure.
- **jax-free-import**: declared jax-free modules (supervisor, elastic, the
  serve package surface, chip_smoke.py, serve_loadgen) stay jax-free through
  their *transitive module-level* import graph; lazy function-body imports
  are the sanctioned escape hatch.
- **host-sync**: `.item()` / `jax.device_get` / `np.asarray` / `print` /
  `float(jnp...)` coercions inside functions reachable from the jitted
  step/decode entry points — tracer leaks and per-step device round trips.
- **telemetry-prefix**: every metric name published through the telemetry
  registry matches `callbacks.loggers.TELEMETRY_PREFIXES`/`TELEMETRY_KEYS`,
  so a new subsystem's gauges can never silently miss telemetry.jsonl.
- **env-doc-drift**: every `LLMT_*`/`FLASH_*`/`PAGED_*` env var
  the code reads appears in the docs env tables.
- **logical-axis-literal**: every string literal used as logical-axis
  param metadata under models/ appears in the `KNOWN_LOGICAL_AXES`
  registry (`parallel/sharding.py`) — a typo'd axis name used to become a
  silently fully-replicated weight.
- **thread-jax-free**: functions reachable from `threading.Thread`
  targets, `Timer` callbacks, or signal handlers never reach jax — a
  watchdog calling into jax can block behind the wedged dispatch it
  exists to diagnose (the prefetcher worker is the one sanctioned,
  suppressed exception).

The package also ships **racecheck** (`--races`, `racecheck.py` +
`threadmodel.py` + `interleave.py`, docs/static-analysis.md#racecheck):
a jax-free thread-model audit — the AST's thread-entry graph checked
against the `# guarded by:` contract registry (unguarded shared
mutation, lock-order inversions, signal-handler safety) plus a
seed-deterministic interleaving harness whose failing schedules replay
byte-identically — and **shardcheck** (`--audit`, `shard_audit.py` +
`hbm_budget.py`): an abstract-interpretation audit that `jax.eval_shape`s
every registered model family's init and resolves the param/opt-state/
KV-cache trees against a mesh-configuration matrix — unknown axes,
duplicate-axis drops, indivisible dims, large replicated tensors, and a
per-chip HBM-fit estimate (docs/static-analysis.md#audit).

The AST lint gate NEVER imports jax (enforced by its own jax-free
contract): `python -m llm_training_tpu.analysis` is the first precommit
gate and must fail in milliseconds, before any backend exists. Only the
`--audit` mode imports jax (lazily, CPU-only, zero FLOPs).
"""

from llm_training_tpu.analysis.engine import (
    Finding,
    RepoContext,
    all_rules,
    main,
    run_analysis,
)

__all__ = ["Finding", "RepoContext", "all_rules", "main", "run_analysis"]
