"""Serving subsystem (docs/serving.md): continuous batching over a paged
KV cache.

The serving tier: a block-pool KV cache with
per-request block tables (`paged_cache.py` + `models/base.PagedDecodeState`),
a ragged paged-decode attention path (`ops/paged_attention.py`, Pallas
kernel in `ops/pallas/paged_attention.py`), a request scheduler with
admission / chunked-prefill interleaving / eviction (`scheduler.py`), and
the jitted continuous-batching engine (`engine.py`) — behind the streaming
`serve` CLI subcommand and `scripts/serve_loadgen.py`. The resilience
layer (docs/serving.md#resilience) adds deadlines + load shedding in the
scheduler, the `RequestJournal` durability log (`journal.py`), hot weight
reload, and graceful drain / supervised replay in the CLI. The fleet
resilience tier (docs/serving.md#router) adds `router.py` + the `route`
CLI: health-aware routing over N serve replicas with failover replay,
hedged retries, and SLO-driven elasticity.

Scheduler, allocator, and journal import eagerly (host-only, no jax); the
engine is lazy, mirroring `llm_training_tpu.infer`.
"""

from llm_training_tpu.serve.journal import RequestJournal, replay_journal
from llm_training_tpu.serve.paged_cache import BlockAllocator, init_paged_pool
from llm_training_tpu.serve.router import (
    ReplicaHandle,
    RoutedRequest,
    Router,
    fold_replica_journals,
    namespaced_id,
)
from llm_training_tpu.serve.scheduler import (
    Scheduler,
    SchedulerConfig,
    ServeRequest,
)

__all__ = [
    "BlockAllocator",
    "ReplicaHandle",
    "RequestJournal",
    "RoutedRequest",
    "Router",
    "Scheduler",
    "SchedulerConfig",
    "ServeConfig",
    "ServeRequest",
    "ServingEngine",
    "fold_replica_journals",
    "init_paged_pool",
    "namespaced_id",
    "replay_journal",
]

_LAZY = {
    "ServeConfig": "llm_training_tpu.serve.engine",
    "ServingEngine": "llm_training_tpu.serve.engine",
}


def __getattr__(name: str):
    if name in _LAZY:
        import importlib

        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
