"""The SPMD training loop.

Capability parity: the reference's `Trainer.fit` call stack (SURVEY.md §3.1):
environment setup → mesh → model configure/materialize → optimizer → hot
loop with grad clip + optimizer step + metrics, plus validation and
checkpoint hooks. FSDP2Strategy/DeepSpeedStrategy (SURVEY.md §2.8) have no
analogue classes: parameter sharding IS the `fsdp` mesh axis, master weights
ARE fp32 params with a bf16 forward, grad accumulation is `optax.MultiSteps`,
grad-norm computation is `optax.global_norm` inside the jitted step.
"""

from __future__ import annotations

import logging
import time
from contextlib import ExitStack
from typing import Any, Callable, Iterator

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from pydantic import BaseModel, ConfigDict

from llm_training_tpu.callbacks.nan_guard import LossSpikeError, NonFiniteLossError
from llm_training_tpu.optim.builder import build_optimizer
from llm_training_tpu.optim.quantized_state import (
    cast_state,
    decode_state,
    encode_state,
    uncast_state,
)
from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh
from llm_training_tpu.parallel.sharding import (
    DEFAULT_LOGICAL_AXIS_RULES,
    logical_to_spec,
    resolve_spec,
)
from llm_training_tpu.resilience import (
    GracefulShutdown,
    HangWatchdog,
    PreemptionInterrupt,
    RecoveryManager,
    ResilienceConfig,
    check_data_continuity,
    config_from_env,
    get_chaos,
    install_chaos,
    uninstall_chaos,
)
from llm_training_tpu.telemetry import (
    GoodputLedger,
    HBMTimeline,
    HealthConfig,
    TelemetryRegistry,
    build_param_groups,
    build_profile_trigger,
    compiled_attribution_gauges,
    compiled_cost_gauges,
    get_tracer,
    hbm_gauges,
    install_compile_listener,
    install_trace_annotator,
    layer_health_metrics,
    mark_setup_ready,
    resolve_run_dir,
    set_profile_trigger,
    set_registry,
)
from llm_training_tpu.trainer.state import TrainState

logger = logging.getLogger(__name__)

# flax scan adds a 'layers' stacking axis to scanned params; keep it unsharded.
LOGICAL_AXIS_RULES = tuple(DEFAULT_LOGICAL_AXIS_RULES) + (("layers", None),)


def offload_memory_kinds() -> tuple[str, str]:
    """(compute_kind, host_kind) for optimizer-state offload on THIS
    backend. TPU/GPU devices address ('device', 'pinned_host'); a CPU
    device addresses only 'unpinned_host' — which is also its default
    memory — so both sides collapse to it and offload degrades to a
    same-memory placement. That keeps the whole offload metadata path
    (sharding resolution, memory-kind annotation, the blocked step's
    host/device twins) exercisable in CPU containers instead of raising
    'Could not find memory addressable by device cpu'."""
    kinds = {m.kind for m in jax.devices()[0].addressable_memories()}
    if "pinned_host" in kinds:
        return ("device" if "device" in kinds else "pinned_host", "pinned_host")
    fallback = "unpinned_host" if "unpinned_host" in kinds else "device"
    return fallback, fallback


class TrainerConfig(BaseModel):
    model_config = ConfigDict(extra="forbid")

    max_steps: int = 1000
    seed: int = 42
    accumulate_grad_batches: int = 1
    log_every_n_steps: int = 10
    val_check_interval: int | None = None
    limit_val_batches: int | None = None
    checkpoint_every_n_steps: int | None = None
    # batches placed on device ahead of the step loop by a worker thread
    # (the reference's pin_memory/prefetch_factor analogue); 0 disables
    prefetch_batches: int = 2
    # park optimizer state (mu/nu) in host memory (`pinned_host`), copying
    # it through HBM around each update — the reference's DeepSpeed
    # CPU-offload lever (`deepspeed_strategy.py:23-37`) as XLA host
    # offloading. Buys ~8 bytes/param of HBM for a per-step host round
    # trip that is LINK-BANDWIDTH BOUND (r5 chip measurement: per-leaf
    # copy/update/copy chains overlap nothing — 0.3035 vs 0.313 MFU —
    # because the update compute is negligible next to the transfers; and
    # host-side Adam via XLA host compute is 3-4x slower than the
    # transfers it would save). The working lever is offload_state_dtype,
    # which shrinks the bytes in EITHER layout: per-leaf blocks (when
    # accumulate_grad_batches == 1 and no frozen_modules) or the
    # serialized whole-tree round trip (accumulation / freeze masks),
    # where the codec's field whitelist keeps MultiSteps' fp32 grad
    # accumulators exact. NOTE: memory-kind annotations only execute on
    # TPU — the CPU
    # backend lacks the placement custom-call, so tests assert layout
    # metadata and numerics with device kinds, and the chip proves
    # placement
    offload_optimizer_state: bool = False
    # storage dtype for the offloaded state (works in both layouts —
    # per-leaf blocks and the serialized accumulation/freeze path):
    #   float32  — exact, 8 bytes/param round-trips each step
    #   bfloat16 — elementwise cast, 4 bytes/param (~2x less transfer)
    #   int8     — block-quantized (mu: sym int8, nu: sqrt uint8 with ceil
    #              rounding — see optim/quantized_state.py), 2 bytes/param
    #              + 1.6% scales (~4x less mu/nu transfer; under grad
    #              accumulation the fp32 acc_grads stay exact by field
    #              whitelist, capping that path's overall saving at ~2x).
    #              The capability analogue of DeepSpeed's quantized
    #              ZeRO-offload knobs (deepspeed_strategy.py:70-102),
    #              built for the real bottleneck here: the host link, not
    #              HBM
    offload_state_dtype: str = "float32"
    # quantization block (elements of the last axis sharing one scale) for
    # offload_state_dtype=int8; arrays whose last axis is not a multiple
    # stay fp32. 256 = 1.6% scale overhead
    offload_quant_block: int = 256
    # model-health layer (telemetry/health.py): per-layer-group grad/param/
    # update norms + MoE router health computed inside a jitted step VARIANT
    # every `health.every_n_steps` optimizer steps. Default (unset) builds
    # no variant — the compiled train step is byte-identical to health-off
    health: HealthConfig = HealthConfig()
    # fault tolerance (resilience/): preemption signal handling (on by
    # default — zero cost until a signal arrives), hang watchdog (off by
    # default), data-source retry policy, and the fault-injection harness
    # (docs/resilience.md)
    resilience: ResilienceConfig = ResilienceConfig()
    mesh: MeshConfig = MeshConfig()


def _batch_shardings(batch: dict[str, np.ndarray], mesh: Mesh) -> dict[str, NamedSharding]:
    spec = logical_to_spec(("batch", "act_seq"), LOGICAL_AXIS_RULES)
    return {k: NamedSharding(mesh, spec) for k in batch}


def _grads_and_metrics(objective, state: "TrainState", batch, with_health: bool = False):
    """Shared train-step preamble (both optimizer paths must stay in sync).
    `with_health` asks the objective for its health extras (MoE router
    stats) — only passed when the objective's signature supports it."""
    step_rng = jax.random.fold_in(state.rng, state.step)

    def loss_fn(params):
        if with_health:
            return objective.loss_and_metrics(
                params, batch, rng=step_rng, train=True, with_health=True
            )
        return objective.loss_and_metrics(params, batch, rng=step_rng, train=True)

    return jax.grad(loss_fn, has_aux=True)(state.params)


def _objective_supports_health(objective) -> bool:
    import inspect

    try:
        params = inspect.signature(objective.loss_and_metrics).parameters
    except (TypeError, ValueError):
        return False
    return "with_health" in params


class Trainer:
    """Drives objective + datamodule over a mesh.

    Usage: Trainer(config).fit(objective, datamodule).
    Callbacks (logging, checkpointing, timing) hook `on_step_end`.
    """

    def __init__(
        self,
        config: TrainerConfig,
        callbacks: list[Any] | None = None,
        checkpointer: Any | None = None,
        devices: list | None = None,
    ):
        self.config = config
        self.callbacks = callbacks or []
        self.checkpointer = checkpointer
        self.devices = devices  # None = all (tests pin subsets)
        self.mesh: Mesh | None = None
        self.state_shardings = None
        # host-side persistent counters (reference metrics/consumed_*.py);
        # python ints — no overflow; saved/restored via checkpoint metadata
        self.counters = {"consumed_samples": 0, "consumed_tokens": 0}
        # callback-visible run state (time/MFU estimator reads these).
        # abstract_state is the jax.eval_shape tree — safe to inspect any
        # time; live TrainState buffers are donated into the next step and
        # must never be cached by callbacks outside the current hook call:
        # live_state is the loop's state for the duration of the
        # on_train_step / on_step_end calls and None outside them
        self.live_state: TrainState | None = None
        self.should_stop = False
        # callbacks set this when the state must NOT be persisted (e.g. the
        # NaN guard stopping on divergence — saving would poison resume)
        self.abort_final_save = False
        # resilience runtime (built per fit): signal-driven shutdown manager,
        # hang watchdog, and whether this fit is ending due to a preemption
        # (fit then raises PreemptionInterrupt after the emergency save)
        self._shutdown: GracefulShutdown | None = None
        self._watchdog: HangWatchdog | None = None
        # live telemetry (built per fit, both optional): the /metrics //
        # statusz//healthz exporter (LLMT_METRICS_PORT) and the SLO
        # burn-rate monitor (LLMT_SLO_*) — docs/observability.md
        self._exporter = None
        self._slo = None
        self._profile_trigger = None
        self._hbm_timeline = None
        self._preempted = False
        # rollback-and-skip recovery (resilience/recovery.py): built per fit
        # when cfg.resilience.recovery is set; the save path persists its
        # skip-list/cooldown metadata into every checkpoint
        self._recovery: RecoveryManager | None = None
        # metadata of the checkpoint this fit restored from (callback state
        # + recovery riders come out of it); None on fresh starts
        self._restored_meta: dict | None = None
        # elastic topology (resilience/elastic.py): the plan this fit's mesh
        # came from (None with resilience.elastic unset) and the global
        # batch size the data stream is keyed to (the checkpoint data_state
        # rider — a resume must never change it)
        self.topology_plan = None
        self._global_batch_size: int | None = None
        # optimizer step of the newest in-loop interval save this fit (the
        # final-save epilogue skips re-saving an identical step)
        self._last_interval_save: int | None = None
        self.abstract_state = None
        self.last_step: int | None = None
        self.last_seq_len: int | None = None
        # host snapshot of the newest health step's metrics (NaN/spike
        # provenance reads this — callbacks/nan_guard.py); None until the
        # first health step (or always, with health.every_n_steps unset)
        self.last_health: dict[str, float] | None = None
        self._param_groups = None
        # per-fit telemetry: a thread-safe metric registry (prefetcher and
        # checkpointer record into it) + the goodput wall-time ledger; both
        # flow into the metrics dict on log steps (docs/observability.md)
        self.telemetry = TelemetryRegistry()
        self.ledger = GoodputLedger()
        # blocked optimizer offload (decided at fit start): the optimizer
        # state is a TUPLE of per-param-leaf states, each running its own
        # copy-in -> update -> copy-out chain with global grad clipping
        # factored out front (it couples all leaves). The layout exists for
        # the compressed storage dtypes (offload_state_dtype) — the r5 chip
        # measurement showed the chains themselves overlap nothing.
        self._blocked_offload = False
        self._clip_norm: float | None = None

    # ------------------------------------------------------------ setup

    def _build_tx(
        self, objective, schedule_transform: Callable | None = None
    ) -> tuple[optax.GradientTransformation, optax.Schedule]:
        """Decide the optimizer LAYOUT and build the transformation. The
        blocked (per-leaf) offload step needs a clip-free leaf-local
        transform; accumulation (MultiSteps wraps the whole tree) and
        path-named freeze masks fall back to the serialized round trip.
        fit and validate_from_checkpoint both go through here so the
        opt_state pytree layout — which checkpoints persist — always
        matches. `schedule_transform` (the recovery LR cooldown) wraps the
        LR schedule only — it can never change the opt_state layout, so a
        rebuilt tx accepts a previously-restored state unchanged."""
        cfg = self.config
        self._blocked_offload = (
            cfg.offload_optimizer_state
            and cfg.accumulate_grad_batches == 1
            and not objective.config.frozen_modules
        )
        if cfg.offload_state_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"offload_state_dtype {cfg.offload_state_dtype!r}; expected "
                "float32, bfloat16 or int8"
            )
        if cfg.offload_state_dtype != "float32" and not cfg.offload_optimizer_state:
            raise ValueError(
                "offload_state_dtype != float32 is a storage codec for the "
                "OFFLOADED state; set offload_optimizer_state=True"
            )
        if cfg.offload_quant_block < 1:
            raise ValueError(
                f"offload_quant_block must be >= 1, got {cfg.offload_quant_block}"
            )
        optim_config = objective.config.optim
        self._clip_norm = None
        if self._blocked_offload:
            self._clip_norm = optim_config.grad_clip_norm
            optim_config = optim_config.model_copy(update={"grad_clip_norm": None})
        tx, schedule = build_optimizer(
            optim_config,
            num_total_steps=cfg.max_steps,
            frozen_modules=objective.config.frozen_modules or None,
            schedule_transform=schedule_transform,
        )
        if cfg.accumulate_grad_batches > 1:
            tx = optax.MultiSteps(tx, cfg.accumulate_grad_batches)
        return tx, schedule

    def _opt_init(self, tx, params) -> Any:
        """Whole-tree optimizer state, or (blocked offload) one state per
        param leaf. Flattening stops at Partitioned boxes so per-leaf init
        preserves the sharding metadata zeros_like carries through them;
        boxed and unboxed trees flatten in the same order."""
        if not self._blocked_offload:
            if self.config.offload_optimizer_state:
                # serialized path (accumulation / freeze masks): compress
                # the whole tree — the codec's field whitelist leaves
                # MultiSteps accumulators and masked placeholders exact
                return self._encode(tx.init(params))
            return tx.init(params)
        leaves = jax.tree.flatten(
            params, is_leaf=lambda x: isinstance(x, nn.Partitioned)
        )[0]
        return tuple(self._encode(tx.init(leaf)) for leaf in leaves)

    def _encode(self, state):
        """Storage codec for one offloaded per-leaf state block (identity
        unless offload_state_dtype compresses it)."""
        dtype = self.config.offload_state_dtype
        if dtype == "bfloat16":
            return cast_state(state, jnp.bfloat16)
        if dtype == "int8":
            return encode_state(state, block=self.config.offload_quant_block)
        return state

    def _decode(self, state):
        dtype = self.config.offload_state_dtype
        if dtype == "bfloat16":
            return uncast_state(state)
        if dtype == "int8":
            return decode_state(state)
        return state

    def _abstract_state(self, objective, sample_batch, tx) -> Any:
        """Shape-evaluate init to get the param tree WITH logical-axis
        metadata, then map to shardings (the analogue of the reference's
        meta-device init, `base_lm.py:256-267`)."""

        def make_state(rng):
            params = objective.init_params(rng, sample_batch)
            # zeros_like maps through the Partitioned boxes, so the abstract
            # opt_state (mu/nu) carries the same sharding annotations as params
            opt_state = self._opt_init(tx, params)
            return TrainState.create(params, opt_state, jax.random.key(1))

        return jax.eval_shape(make_state, jax.random.key(self.config.seed))

    def _state_shardings(self, abstract_state) -> Any:
        # STRICT resolution: an unknown logical-axis name in any param's
        # metadata raises UnknownLogicalAxisError naming the leaf — the
        # legacy behavior silently replicated the weight across the mesh
        # (OOM/crawl only on real hardware; see `python -m
        # llm_training_tpu.analysis --audit`). Duplicate-mesh-axis drops are
        # legal but no longer invisible: they surface once as a warning.
        drops = []

        def leaf_sharding(path, leaf):
            if isinstance(leaf, nn.Partitioned):
                if not jax.tree_util.tree_leaves(leaf.value):
                    # a box around an EMPTY pytree — optax.masked wraps
                    # frozen params' opt-state slots in MaskedNode(), and
                    # zeros_like maps it THROUGH the Partitioned box. There
                    # is no array to shard; emitting a sharding here would
                    # give the shardings tree a leaf the unboxed state tree
                    # doesn't have, breaking every frozen-modules restore
                    # (DPO/GRPO reference params)
                    return leaf.value
                spec, leaf_drops = resolve_spec(
                    leaf.names, LOGICAL_AXIS_RULES, strict=True,
                    path=jax.tree_util.keystr(path),
                )
                drops.extend(leaf_drops)
            else:
                spec = PartitionSpec()
            return NamedSharding(self.mesh, spec)

        shardings = jax.tree_util.tree_map_with_path(
            leaf_sharding,
            abstract_state,
            is_leaf=lambda x: isinstance(x, nn.Partitioned),
        )
        for drop in drops:
            logger.warning(
                "sharding: %s dim %d (logical %r) dropped duplicate mesh "
                "axes %s — an earlier dim of the tensor already consumed "
                "them; the dim stays wider per chip than the rule table "
                "suggests", drop.path, drop.position, drop.axis,
                list(drop.mesh_axes),
            )
        if self.config.offload_optimizer_state:
            _, host_kind = offload_memory_kinds()

            def maybe_host(sharding, leaf):
                # only real arrays (mu/nu) move to host; rank-0 counters stay
                # on device — the SPMD partitioner rejects host placement of
                # side-effect scalars ("Side-effect HLO must have sharding")
                shape = leaf.value.shape if isinstance(leaf, nn.Partitioned) else leaf.shape
                if len(shape) == 0:
                    return sharding
                return sharding.with_memory_kind(host_kind)

            shardings = shardings.replace(
                opt_state=jax.tree.map(
                    maybe_host,
                    shardings.opt_state,
                    abstract_state.opt_state,
                    is_leaf=lambda x: isinstance(x, (NamedSharding, nn.Partitioned)),
                )
            )
        return shardings

    def _build_step(self, objective, tx) -> Callable:
        return self._make_step(objective, tx, with_health=False)

    def _build_health_step(self, objective, tx) -> Callable:
        """The instrumented step variant: same update math as `_build_step`
        plus per-layer-group health metrics (and the objective's MoE router
        health, when it supports the `with_health` flag). Compiled
        separately and called only on health-cadence steps, so the default
        step stays byte-identical."""
        return self._make_step(objective, tx, with_health=True)

    def _make_step(self, objective, tx, with_health: bool) -> Callable:
        offload = self.config.offload_optimizer_state
        objective_health = with_health and _objective_supports_health(objective)
        if offload:
            # device-resident twins of the host-kind opt-state shardings:
            # the update math runs in HBM, bracketed by explicit copies
            compute_kind, _ = offload_memory_kinds()
            opt_device = jax.tree.map(
                lambda s: s.with_memory_kind(compute_kind),
                self.state_shardings.opt_state,
            )
            opt_host = self.state_shardings.opt_state
        if self._blocked_offload:
            return self._build_blocked_offload_step(
                objective, tx, opt_device, opt_host,
                with_health=with_health, objective_health=objective_health,
            )

        def train_step(state: TrainState, batch: dict[str, jnp.ndarray]):
            grads, metrics = _grads_and_metrics(
                objective, state, batch, objective_health
            )
            # a device profile reads the update, the offload's copies around
            # it and the gradient norm by this name (docs/observability.md)
            with jax.named_scope("optimizer"):
                opt_state = state.opt_state
                if offload:
                    opt_state = self._decode(
                        jax.tree.map(jax.device_put, opt_state, opt_device)
                    )
                updates, opt_state = tx.update(grads, opt_state, state.params)
                if offload:
                    opt_state = jax.tree.map(
                        jax.device_put, self._encode(opt_state), opt_host
                    )
                params = optax.apply_updates(state.params, updates)
                metrics["grad_norm"] = optax.global_norm(grads)
            if with_health:
                metrics.update(
                    layer_health_metrics(
                        self._param_groups, state.params, grads, updates
                    )
                )
            new_state = state.replace(
                step=state.step + 1,
                params=params,
                opt_state=opt_state,
            )
            return new_state, metrics

        return train_step

    def _build_blocked_offload_step(
        self, objective, tx, opt_device, opt_host,
        with_health: bool = False, objective_health: bool = False,
    ) -> Callable:
        """Per-leaf offloaded update (VERDICT r4 #5): `tx` here EXCLUDES
        grad clipping (built with grad_clip_norm=None; the global norm
        couples every leaf, so it is applied up front as a scalar re-scale
        — identical math to optax.clip_by_global_norm). Each param leaf
        carries its own optimizer-state block whose storage may be
        compressed (self._encode/_decode, offload_state_dtype) — the lever
        that actually cuts the host round trip; the r5 chip measurement
        showed leaf-chain overlap alone recovers nothing (0.3035 vs 0.313
        MFU). Usable-CPU-offload analogue: `deepspeed_strategy.py:23-37`
        + its quantized-offload knobs (`:70-102`)."""
        clip_norm = self._clip_norm

        def train_step(state: TrainState, batch: dict[str, jnp.ndarray]):
            grads, metrics = _grads_and_metrics(
                objective, state, batch, objective_health
            )
            # the same scope as the plain step's: norm, clip, per-leaf update
            # and the copies around it
            with jax.named_scope("optimizer"):
                gnorm = optax.global_norm(grads)
                metrics["grad_norm"] = gnorm
                # health reads the PRE-clip gradients (same semantics as the
                # non-offload step): the clip rescale is global, so a single
                # NaN leaf would smear NaN over every group and destroy the
                # per-layer provenance this exists for
                raw_grads = grads
                if clip_norm is not None:
                    scale = clip_norm / jnp.maximum(gnorm, clip_norm)
                    grads = jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)

                p_leaves, p_def = jax.tree.flatten(state.params)
                g_leaves = jax.tree.flatten(grads)[0]
                new_params, new_opt, upd_leaves = [], [], []
                for p, g, o_host, sh_dev, sh_host in zip(
                    p_leaves, g_leaves, state.opt_state, opt_device, opt_host
                ):
                    o_dev = jax.tree.map(jax.device_put, o_host, sh_dev)
                    upd, o_fp = tx.update(g, self._decode(o_dev), p)
                    new_opt.append(
                        jax.tree.map(jax.device_put, self._encode(o_fp), sh_host)
                    )
                    upd_leaves.append(upd)
                    new_params.append(optax.apply_updates(p, upd))
            if with_health:
                metrics.update(
                    layer_health_metrics(
                        self._param_groups, state.params, raw_grads,
                        jax.tree.unflatten(p_def, upd_leaves),
                    )
                )
            new_state = state.replace(
                step=state.step + 1,
                params=jax.tree.unflatten(p_def, new_params),
                opt_state=tuple(new_opt),
            )
            return new_state, metrics

        return train_step

    def _build_eval_step(self, objective) -> Callable:
        def eval_step(state: TrainState, batch):
            _, metrics = objective.loss_and_metrics(
                state.params, batch, rng=state.rng, train=False
            )
            return {"loss": metrics["loss"], "target_tokens": metrics["target_tokens"]}

        return eval_step

    # ------------------------------------------------------------ topology

    def _mesh_axis_sizes(self) -> dict[str, int]:
        """The live mesh's per-axis degrees — the ONE source both the
        segment_topology audit event and the checkpoint `topology` rider
        record (the planner pins model axes to the latter, so the two must
        never drift)."""
        return {
            str(name): int(size)
            for name, size in zip(self.mesh.axis_names, self.mesh.devices.shape)
        }

    def _resolve_topology(self, resume_step: int | None = None):
        """The elastic front door of fit: (devices, mesh_config, plan).

        With `resilience.elastic` unset this only applies the chaos device
        clamp (LLMT_CHAOS_DEVICES, a no-op unless the env var is set) and
        returns the config mesh untouched. With it set, the planner fits
        the mesh to the LIVE device pool: model axes pinned to the degrees
        recorded in the checkpoint being resumed, the data axis scaled to
        absorb the capacity change (resilience/elastic.py)."""
        from llm_training_tpu.resilience.elastic import (
            chaos_device_limit,
            plan_topology,
        )

        cfg = self.config
        devices = self.devices
        if devices is None:
            # the chaos shrink applies only to the default all-devices
            # path: tests that pin an explicit subset stay authoritative
            limit = chaos_device_limit()
            if limit is not None:
                devices = list(jax.devices())
                if limit < len(devices):
                    logger.warning(
                        "chaos: shrinking visible devices %d -> %d "
                        "(LLMT_CHAOS_DEVICES)", len(devices), limit,
                    )
                    devices = devices[:limit]
        if cfg.resilience.elastic is None:
            return devices, cfg.mesh, None
        if devices is None:
            devices = list(jax.devices())
        checkpoint_mesh = None
        checkpoint_batch = None
        if self.checkpointer is not None:
            meta = self.checkpointer.read_meta(resume_step)
            checkpoint_mesh = ((meta or {}).get("topology") or {}).get("mesh")
            checkpoint_batch = ((meta or {}).get("data_state") or {}).get(
                "global_batch_size"
            )
        plan = plan_topology(
            len(devices),
            cfg.mesh.axis_sizes(),
            checkpoint_mesh=checkpoint_mesh,
            global_batch_size=checkpoint_batch,
        )
        logger.info(
            "elastic topology: %s over %d device(s) [%s, from %s]",
            plan.axis_sizes, plan.device_count, plan.decision, plan.source,
        )
        return (
            devices[: plan.device_count],
            MeshConfig.from_axis_sizes(plan.axis_sizes),
            plan,
        )

    def _publish_topology(self, plan) -> None:
        """Tag this segment with its world: goodput cost basis (chip count
        + $/chip-hour -> goodput-per-dollar gauges), elastic/* telemetry,
        and — under a supervisor — a segment_topology event in
        supervisor.jsonl keyed by the launch attempt."""
        from llm_training_tpu.resilience.elastic import (
            log_segment_topology,
            resolve_chip_price,
            segment_attempt,
        )

        chips = int(self.mesh.devices.size)
        price = resolve_chip_price(self.config.resilience.elastic)
        self.ledger.set_cost_basis(chips, price)
        self.telemetry.gauge("elastic/segment").set(segment_attempt())
        self.telemetry.gauge("elastic/device_count").set(chips)
        self.telemetry.gauge("elastic/data_parallel_size").set(
            int(self.mesh.shape["data"])
        )
        log_segment_topology(
            self._mesh_axis_sizes(),
            chips,
            decision=plan.decision if plan is not None else "static mesh",
            price_per_chip_hour=price,
        )

    # ------------------------------------------------------------ fit

    def fit(
        self,
        objective,
        datamodule,
        resume_step: int | None = None,
        state: TrainState | None = None,
    ) -> TrainState:
        cfg = self.config
        # the start-up timeline (docs/observability.md#tracing): jax's compile
        # events heard from here on, and everything up to the step's compile
        # (mesh, optimizer, shardings, the state's placement or restore) as
        # one pinned span, which `_fit_inner` closes
        install_compile_listener()
        self._fit_prepare = ExitStack()
        self._fit_prepare.enter_context(
            get_tracer().measure("setup", "fit_prepare", pin=True)
        )
        devices, mesh_config, plan = self._resolve_topology(resume_step)
        self.mesh = build_mesh(mesh_config, devices)
        self.topology_plan = plan
        datamodule.setup()

        # fresh telemetry per fit, installed as the process-current registry
        # so components constructed elsewhere (the checkpointer) find it
        self.telemetry = TelemetryRegistry()
        self.ledger.start()
        self._publish_topology(plan)
        previous_registry = set_registry(self.telemetry)
        resil = cfg.resilience
        self._preempted = False
        self._last_interval_save = None
        # fault injection first (env overlays the config), so every other
        # resilience layer — and the checkpointer/prefetcher call sites —
        # sees the harness
        install_chaos(config_from_env(resil.chaos), registry=self.telemetry)
        self._shutdown = (
            GracefulShutdown().install() if resil.handle_signals else None
        )
        self._watchdog = None
        run_dir = resolve_run_dir(self)
        if resil.watchdog_timeout_s:
            self._watchdog = HangWatchdog(
                resil.watchdog_timeout_s,
                run_dir=run_dir,
                ledger=self.ledger,
                registry=self.telemetry,
                action=resil.watchdog_action,
            ).start()
        # SLO monitor (docs/observability.md#slo): armed only when
        # LLMT_SLO_* targets are set — otherwise zero cost. The step loop
        # feeds it optimizer-step intervals and goodput; breaches bump
        # slo/* counters and flight-dump the trace ring into the run dir —
        # process 0 only, like every run-dir artifact (N hosts breaching
        # together would clobber one dump file)
        from llm_training_tpu.telemetry.slo import build_slo_monitor

        self._slo = build_slo_monitor(
            registry=self.telemetry,
            run_dir=run_dir if jax.process_index() == 0 else None,
        )
        # device-profile trigger (docs/observability.md#profiling): the
        # request surface is jax-free and process-wide — SLO breaches,
        # watchdog dumps, anomaly dumps, /profilez, and the `profile` CLI
        # all arm captures through it; only this loop's poll() below
        # touches jax.profiler. Process 0 only for the artifact root —
        # captures are run-dir artifacts like flight dumps.
        self._profile_trigger = build_profile_trigger(
            registry=self.telemetry,
            run_dir=run_dir if jax.process_index() == 0 else None,
        )
        # absorb ProfilerCallback step windows into the trigger: the
        # config window becomes a scheduled capture (same budget, same
        # artifact naming) and the callback goes passive — one owner for
        # jax.profiler.start/stop_trace, so an SLO-fired capture can never
        # nest inside a config-window capture (jax raises on nesting)
        for cb in self.callbacks:
            window = getattr(cb, "profile_window", None)
            if callable(window):
                start_step, num_steps, trace_dir = window()
                self._profile_trigger.schedule(
                    start_step, num_steps,
                    trace_dir=trace_dir, max_steps=cfg.max_steps,
                )
                cb._absorbed = True
        # per-device HBM timeline (docs/observability.md#device-plane):
        # sampled on log steps into <run_dir>/hbm.jsonl + registry gauges
        self._hbm_timeline = HBMTimeline(
            run_dir=run_dir if jax.process_index() == 0 else None,
            registry=self.telemetry,
        )
        # live-telemetry exporter (docs/observability.md#live-telemetry):
        # /metrics (registry + ledger), /statusz (phase, step, segment),
        # /healthz (red on a stale watchdog beat). LLMT_METRICS_PORT=0/unset
        # disables; a port collision degrades to a warning, never a crash.
        from llm_training_tpu.resilience.elastic import segment_attempt
        from llm_training_tpu.telemetry.exporter import start_exporter

        self._exporter = start_exporter(
            registry=self.telemetry,
            ledger=self.ledger,
            watchdog=self._watchdog,
            slo=self._slo,
            profile=self._profile_trigger,
            status_fn=lambda: {
                "step": self.last_step,
                "segment": segment_attempt(),
            },
        )
        # trace sink (docs/observability.md#tracing): lifecycle events land
        # in <run_dir>/trace.jsonl; per-step spans only with
        # LLMT_TRACE_TRAIN=1. Process 0 only — run-dir artifacts follow the
        # JsonlLogger policy. attach_sink is False when another owner
        # already holds the sink — then it keeps it.
        trace_attached = False
        if run_dir is not None and jax.process_index() == 0:
            trace_attached = get_tracer().attach_sink(run_dir / "trace.jsonl")
        try:
            with self.mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
                return self._fit_inner(objective, datamodule, resume_step, state)
        finally:
            self._fit_prepare.close()  # a fit that raised before its compile
            if self._exporter is not None:
                self._exporter.stop()
                self._exporter = None
            self._slo = None
            if self._profile_trigger is not None:
                # closes any dangling capture window (fit raised mid-trace)
                # and unpublishes the process-wide trigger so the next fit
                # — or a serve loop in the same process — starts clean
                self._profile_trigger.teardown()
                set_profile_trigger(None)
                self._profile_trigger = None
            self._hbm_timeline = None
            if self._watchdog is not None:
                self._watchdog.stop()
                self._watchdog = None
            if self._shutdown is not None:
                self._shutdown.uninstall()
                self._shutdown = None
            if trace_attached:
                get_tracer().detach_sink()
            uninstall_chaos()
            set_registry(previous_registry)
            # callbacks that alter process state (output tees, profiler
            # traces) must restore it even when fit raises mid-run
            for cb in self.callbacks:
                if hasattr(cb, "teardown"):
                    cb.teardown()

    def _fit_inner(self, objective, datamodule, resume_step, state) -> TrainState:
        cfg = self.config
        # every span below opens through tracer.measure, which also opens
        # its profiler annotation `llmt/train/<name>` once the annotator is
        # installed (docs/observability.md#tracing): coarse lifecycle events
        # (compile, validation, checkpoint_save, segment boundaries) always
        # reach the sink; the per-micro-step data_load/train_step spans are
        # written only with LLMT_TRACE_TRAIN=1 — the ring records them
        # regardless, so the flight recorder has context on every crash
        tracer = get_tracer()
        install_trace_annotator(tracer)
        trace_train = tracer.train_steps
        batches = datamodule.train_batches(start_step=0)
        sample_batch = next(batches)

        tx, schedule = self._build_tx(objective)

        dp_ways = self.mesh.shape["data"] * self.mesh.shape["fsdp"]
        batch_size = next(iter(sample_batch.values())).shape[0]
        self._global_batch_size = batch_size
        if batch_size % dp_ways != 0:
            # the reference's world-size divisibility assert (fsdp2_strategy.py:185-191)
            raise ValueError(
                f"global batch size {batch_size} must be divisible by "
                f"data*fsdp mesh ways ({dp_ways})"
            )

        # a pipe axis only does work when the model splits into matching
        # stages; a silent mismatch would replicate every computation
        # across it (pipe>1, stages=1) or pay GPipe bubbles for nothing
        pp_mesh = self.mesh.shape.get("pipe", 1)
        # check EVERY model the objective runs (DPO/ORPO carry a ref model
        # too — an unpipelined ref on a pipe mesh would replicate its whole
        # forward across the axis)
        models = {"model": getattr(objective, "model", None)}
        ref = getattr(objective, "ref_model", None)
        if ref is not None and ref is not models["model"]:
            models["ref_model"] = ref
        for name, model in models.items():
            if model is None:
                continue
            pp_model = getattr(getattr(model, "config", None), "pipeline_stages", 1)
            if pp_mesh > 1 and pp_model != pp_mesh:
                raise ValueError(
                    f"mesh pipeline_parallel_size={pp_mesh} but {name} has "
                    f"pipeline_stages={pp_model}; they must match (the pipe "
                    "axis shards the model's stage dimension)"
                )
            if pp_mesh == 1 and pp_model > 1:
                logger.warning(
                    "%s pipeline_stages=%d with no pipe mesh axis: the "
                    "GPipe schedule runs sequentially (debug mode) — its "
                    "bubbles cost throughput without parallelism",
                    name, pp_model,
                )
            if (
                pp_model > 1
                and self.mesh.shape.get("expert", 1) > 1
                and getattr(getattr(model, "config", None), "num_experts", None)
            ):
                # the EP dispatch is a shard_map, which cannot sit under
                # the pipeline's stage vmap; MoE under PP runs the plain
                # (ragged/dense/bucketed) dispatch with experts sharded
                # over fsdp/tensor like other params
                raise ValueError(
                    "pipeline_stages > 1 does not compose with "
                    "expert_parallel_size > 1 (shard_map under the stage "
                    "vmap); use fsdp/tensor sharding for the experts"
                )

        # the boxed (Partitioned-annotated) abstract tree exists only to
        # derive shardings; the canonical runtime state is unboxed
        abstract_boxed = self._abstract_state(objective, sample_batch, tx)
        self.state_shardings = self._state_shardings(abstract_boxed)
        abstract_state = nn.meta.unbox(abstract_boxed)
        self.abstract_state = abstract_state
        batch_shardings = _batch_shardings(sample_batch, self.mesh)

        # restore or initialize, directly into sharded buffers
        self._restored_meta = None
        if state is None and self.checkpointer is not None:
            try:
                restored = self.checkpointer.maybe_restore(
                    abstract_state, self.state_shardings, resume_step
                )
            except Exception as e:
                # the optimizer-state pytree LAYOUT depends on run settings
                # (blocked offload = per-leaf tuple; MultiSteps wraps the
                # tree), so flipping them across a resume cannot restore
                raise RuntimeError(
                    "checkpoint restore failed — note the optimizer-state "
                    "layout depends on offload_optimizer_state, "
                    "offload_state_dtype, offload_quant_block, "
                    "accumulate_grad_batches, and frozen_modules; resume "
                    "with the same settings the checkpoint was written with"
                ) from e
            if restored is not None:
                state, meta = restored
                self.counters.update(meta.get("counters", {}))
                self._restored_meta = meta
                # elastic data contract (docs/resilience.md#elastic): a
                # resume may change the replica count, never the global
                # batch the (seed, step) sample stream is keyed to — raise
                # under elastic, warn on the legacy path
                check_data_continuity(
                    meta.get("data_state"), batch_size,
                    elastic=cfg.resilience.elastic is not None,
                )
                if self.topology_plan is not None:
                    # the planner may have fallen back to the config (meta
                    # read failed, or restore fell back to an older step):
                    # never let orbax reshard model axes silently
                    from llm_training_tpu.resilience.elastic import (
                        verify_restored_topology,
                    )

                    verify_restored_topology(
                        self.topology_plan, meta.get("topology")
                    )
                # callback state riders (NanGuard EMA/z-score trackers):
                # without this every resume restarts the spike detector's
                # warmup blind — right when spikes are most likely
                self._load_callback_state(meta)
        pre_trained = (
            objective.pretrained_source()
            if hasattr(objective, "pretrained_source")
            else None
        )
        # init jits emit all-device buffers; offloaded (host-kind) leaves
        # move EAGERLY afterwards — a mixed-memory-kind out_shardings would
        # annotate every output, which some partitioners reject
        init_shardings = self.state_shardings
        if cfg.offload_optimizer_state:
            compute_kind, _ = offload_memory_kinds()
            init_shardings = jax.tree.map(
                lambda s: s.with_memory_kind(compute_kind), self.state_shardings
            )

        def init_state() -> TrainState:
            """Fresh sharded state (pretrained or seed-init) — the fit-start
            path, and the recovery rollback target when no committed
            checkpoint exists (both are deterministic in cfg.seed)."""
            if pre_trained and objective.config.load_weights:
                # stream HF weights straight into sharded buffers (reference
                # rank-0-load + broadcast, base_lm.py:175-193)
                logger.info("loading pre-trained weights from %s", pre_trained)
                dtypes = jax.tree.map(lambda leaf: leaf.dtype, abstract_state.params)
                params = objective.pretrained_params(self.state_shardings.params, dtypes)
                opt_state = jax.jit(
                    lambda p: self._opt_init(tx, p),
                    out_shardings=init_shardings.opt_state,
                )(params)
                return jax.device_put(
                    TrainState.create(params, opt_state, jax.random.key(cfg.seed + 1)),
                    self.state_shardings,
                )
            logger.info("initializing parameters on the mesh")

            def make_state(rng):
                params = objective.init_params(rng, sample_batch)
                opt_state = self._opt_init(tx, params)
                return nn.meta.unbox(
                    TrainState.create(params, opt_state, jax.random.key(cfg.seed + 1))
                )

            fresh = jax.jit(make_state, out_shardings=init_shardings)(
                jax.random.key(cfg.seed)
            )
            if cfg.offload_optimizer_state:
                fresh = jax.device_put(fresh, self.state_shardings)
            return fresh

        if state is None:
            state = init_state()

        # rollback-and-skip recovery (resilience/recovery.py): restore the
        # persisted skip-list/cooldown riders so a resumed run replays the
        # same data skips and LR; a restored cooldown window re-wraps the
        # schedule before the steps compile (layout untouched)
        recovery = None
        self._recovery = None
        if cfg.resilience.recovery is not None:
            recovery = RecoveryManager(
                cfg.resilience.recovery,
                registry=self.telemetry,
                metadata=(self._restored_meta or {}).get("recovery"),
            )
            self._recovery = recovery
            transform = recovery.schedule_transform()
            if transform is not None:
                tx, schedule = self._build_tx(objective, schedule_transform=transform)

        train_step = jax.jit(
            self._build_step(objective, tx),
            in_shardings=(self.state_shardings, batch_shardings),
            out_shardings=(self.state_shardings, None),
            donate_argnums=0,
        )
        # the instrumented step variant (health.every_n_steps): same update
        # math + per-layer health metrics; compiled separately so the plain
        # step (and therefore every non-health step) is byte-identical to a
        # health-off run. The grouping plan comes from the BOXED abstract
        # tree (Partitioned names identify scan-stacked leaves).
        health_every = cfg.health.every_n_steps
        health_step = None
        if health_every:
            self._param_groups = build_param_groups(abstract_boxed.params)
            health_step = jax.jit(
                self._build_health_step(objective, tx),
                in_shardings=(self.state_shardings, batch_shardings),
                out_shardings=(self.state_shardings, None),
                donate_argnums=0,
            )
        eval_step = jax.jit(
            self._build_eval_step(objective),
            in_shardings=(self.state_shardings, batch_shardings),
        )

        # AOT-compile the hot step up front: the compile lands in its own
        # goodput phase (and compile_time_s gauge) instead of skewing the
        # first step, and the Compiled object exposes XLA's cost/memory
        # analysis — the cross-check for the analytic MFU model. A compile
        # failure (a kernel Mosaic refuses, a step that outgrows HBM) is a
        # failure of the fit and raises here.
        # With health on EVERY optimizer step (and no accumulation) the
        # plain step would never execute — skip its compile entirely (the
        # health variant compiles on its first call, billed to the compile
        # phase) instead of burning a full XLA compile on dead code.
        aot_step = None
        plain_step_used = not (
            health_every == 1 and cfg.accumulate_grad_batches == 1
        )
        self._fit_prepare.close()
        t_compile = time.perf_counter()
        with self.ledger.measure("compile"), \
                tracer.measure("train", "compile", pin=True):
            if plain_step_used:
                aot_step = train_step.lower(state, sample_batch).compile()
            else:
                logger.info(
                    "health.every_n_steps=1: skipping the plain-step AOT "
                    "compile (the health step variant runs every step)"
                )
        if aot_step is not None:
            self.telemetry.gauge("compile_time_s").set(time.perf_counter() - t_compile)
            for name, value in compiled_cost_gauges(aot_step).items():
                self.telemetry.gauge(name).set(value)
            # compute/comm attribution (docs/observability.md#device-plane):
            # walk the compiled step's HLO for collective payload bytes and
            # split them per mesh axis — the static comm fraction that
            # report prints
            for name, value in compiled_attribution_gauges(
                aot_step, self._mesh_axis_sizes()
            ).items():
                self.telemetry.gauge(name).set(value)
        step_fn = aot_step if aot_step is not None else train_step

        # state.step counts micro-steps (train_step invocations): resume
        # continues the data stream exactly where it stopped, independent of
        # the accumulation factor
        start_micro = int(jax.device_get(state.step))
        micro_steps = cfg.max_steps * cfg.accumulate_grad_batches
        # chaos SIGKILL only fires in runs that started from scratch, so a
        # supervisor's relaunch (resuming past a checkpoint) survives the
        # trigger step (chaos.maybe_sigkill, the supervise-gate contract)
        fresh_start = start_micro == 0

        for cb in self.callbacks:
            if hasattr(cb, "on_fit_start"):
                cb.on_fit_start(
                    self, objective, datamodule, start_micro // cfg.accumulate_grad_batches
                )

        self.should_stop = False
        self.abort_final_save = False
        self.last_step = None
        self.last_metrics = None
        self.last_health = None
        health_compiled = False
        self.last_seq_len = (
            sample_batch["input_ids"].shape[1] if "input_ids" in sample_batch else None
        )

        skip_list = recovery.skip_list if recovery is not None else None
        # the start-up timeline's last span: the fit's first executed step
        # through the first host fetch of what a step made (a log step's, or
        # the health variant's), after which the fit is ready
        first_step = ExitStack()
        first_step_args: dict | None = None

        def first_fetch_done(step: int) -> None:
            nonlocal first_step_args
            if first_step_args is not None:
                first_step_args["step"] = step
                first_step_args = None
                first_step.close()
                mark_setup_ready(tracer, loop="fit", step=step)

        def data_stream(from_micro: int):
            # the skip-list keyword only reaches datamodules when recovery
            # is on — the default stream stays byte-identical to a
            # recovery-less build (and to subclasses overriding
            # train_batches with the historical signature)
            if skip_list is not None:
                return datamodule.train_batches(
                    start_step=from_micro, skip_list=skip_list
                )
            return datamodule.train_batches(start_step=from_micro)

        def run_segment(state: TrainState, seg_start: int) -> TrainState:
            """One recoverable stretch of the micro-step loop: from
            `seg_start` to completion (or a guard raise / stop request).
            The recovery path catches NanGuard errors around this, rolls
            the state back, and re-enters with a later-start segment —
            with recovery unset there is exactly one segment and the loop
            below is the whole fit, byte-identical to before."""
            nonlocal health_compiled, step_fn, first_step_args
            prefetcher = None
            tracer.instant(
                "train", "segment_start", micro=seg_start,
                step=seg_start // cfg.accumulate_grad_batches,
            )
            batches = data_stream(seg_start)
            # throughput window: (start time, start step). Reset after the
            # first optimizer step of this segment so JIT compile/warmup
            # never skews steps_per_sec (compile is its own telemetry gauge
            # + goodput phase).
            start_step0 = seg_start // cfg.accumulate_grad_batches
            first_process_step = start_step0 + 1
            window_time, window_step = time.perf_counter(), start_step0
            # SLO step-cadence anchor (host-observed optimizer-step
            # intervals); reset per segment so a resume's restore/compile
            # never bills as one giant slow step
            slo_step_t: float | None = None
            try:
                # constructed inside the try so an exception anywhere after
                # the worker thread starts still reaches prefetcher.close()
                if cfg.prefetch_batches > 0:
                    from llm_training_tpu.data.prefetch import DevicePrefetcher

                    watchdog = self._watchdog
                    prefetcher = DevicePrefetcher(
                        # an iterator FACTORY, not a bare iterator: data
                        # retries can then rebuild a closed generator at the
                        # batch being retried (docs/resilience.md)
                        lambda produced: data_stream(seg_start + produced),
                        batch_shardings,
                        depth=cfg.prefetch_batches,
                        host_aux_fn=self._batch_counts,
                        registry=self.telemetry,
                        retries=cfg.resilience.data_retries,
                        retry_backoff_s=cfg.resilience.data_retry_backoff_s,
                        heartbeat=(
                            (lambda: watchdog.beat("prefetcher")) if watchdog else None
                        ),
                    )
                    batches = iter(prefetcher)
                if self.last_step is None:  # no step of this fit has run yet
                    first_step_args = first_step.enter_context(
                        tracer.measure("setup", "first_step", pin=True)
                    )
                for micro in range(seg_start, micro_steps):
                    if self._watchdog is not None:
                        self._watchdog.beat("train_loop", step=micro)
                    with jax.profiler.StepTraceAnnotation("train", step_num=micro):
                        with self.ledger.measure("data_wait"), \
                                tracer.measure(
                                    "train", "data_load",
                                    write=trace_train, step=micro,
                                ):
                            if prefetcher is not None:
                                batch, counts = next(batches)
                            else:
                                batch = next(batches)
                                counts = self._batch_counts(batch)
                        # health cadence: the instrumented variant runs on the
                        # optimizer steps `health.every_n_steps` selects (its jit
                        # recompiles per shape natively; first compile bills to
                        # the compile phase like the AOT step's)
                        use_health = (
                            health_step is not None
                            and (micro + 1) % cfg.accumulate_grad_batches == 0
                            and ((micro + 1) // cfg.accumulate_grad_batches)
                            % health_every == 0
                        )
                        # without the AOT pre-compile, the first invocation blocks
                        # on trace+compile — bill it to the compile phase
                        first_compiling = aot_step is None and micro == seg_start
                        phase = "compile" if first_compiling else "step_compute"
                        t_step = time.perf_counter()
                        with tracer.measure(
                            "train", "train_step", write=trace_train, step=micro
                        ):
                            if use_health:
                                health_phase = (
                                    "compile" if not health_compiled
                                    else "step_compute"
                                )
                                with self.ledger.measure(health_phase):
                                    state, metrics = health_step(state, batch)
                                if not health_compiled and aot_step is None:
                                    # no plain-step AOT ran: the health compile
                                    # IS the run's train-step compile
                                    self.telemetry.gauge("compile_time_s").set(
                                        time.perf_counter() - t_step
                                    )
                                health_compiled = True
                                first_compiling = False
                            else:
                                try:
                                    with self.ledger.measure(phase):
                                        state, metrics = step_fn(state, batch)
                                except TypeError:
                                    # the AOT executable is pinned to
                                    # sample_batch's shapes; pad-to-longest
                                    # collators emit variable sequence lengths.
                                    # The mismatch raises BEFORE execution
                                    # (donated buffers intact), so fall back
                                    # permanently to the jitted callable, which
                                    # recompiles per shape like it always did.
                                    # The retry (jit trace + compile) bills to
                                    # the compile phase; LATER new-shape
                                    # recompiles happen inside the jit call
                                    # and land in step_compute: the warning
                                    # below says so once, and the compile
                                    # listener counts each in
                                    # `compile/after_ready` and pins a
                                    # `compile/*` span with its program
                                    # (docs/observability.md#tracing)
                                    if step_fn is train_step:
                                        raise
                                    logger.warning(
                                        "AOT train step rejected batch shapes at "
                                        "micro step %d (variable-length batches?); "
                                        "falling back to jit recompilation", micro,
                                    )
                                    step_fn = train_step
                                    with self.ledger.measure("compile"):
                                        state, metrics = step_fn(state, batch)
                            if first_compiling:
                                self.telemetry.gauge("compile_time_s").set(
                                    time.perf_counter() - t_step
                                )

                    self._apply_counts(counts)

                    if (micro + 1) % cfg.accumulate_grad_batches != 0:
                        continue
                    step = (micro + 1) // cfg.accumulate_grad_batches
                    self.last_step = step
                    if self._slo is not None:
                        now_step = time.perf_counter()
                        if slo_step_t is not None:
                            self._slo.observe_step(
                                now_step - slo_step_t, step=step
                            )
                        slo_step_t = now_step
                    if self._profile_trigger is not None:
                        # AFTER the SLO observe above: a breach fired there
                        # arms a request, and this poll starts its capture
                        # on the very next statement — the profiled window
                        # begins at the first step after the breach
                        self._profile_trigger.poll(step)
                    # fresh (non-donated) device arrays; callbacks that need wall-
                    # clock accuracy can jax.block_until_ready(trainer.last_metrics)
                    self.last_metrics = metrics
                    if use_health:
                        # pull the health metrics to host and publish them as
                        # registry gauges: telemetry.jsonl, W&B, and `report` get
                        # them through the registry snapshot on log steps with no
                        # extra wiring, and NaN/spike provenance (nan_guard)
                        # reads the stash. The blocking fetch drains the dispatch
                        # queue, so it bills to step_compute like the log fetch —
                        # this sync IS what the health variant costs a step
                        # over the plain one.
                        health_keys = [k for k in metrics if k.startswith("health/")]
                        with self.ledger.measure("step_compute"):
                            host = jax.device_get({k: metrics[k] for k in health_keys})
                        first_fetch_done(step)
                        for key in health_keys:
                            del metrics[key]
                        self.last_health = {k: float(v) for k, v in host.items()}
                        for key, value in self.last_health.items():
                            self.telemetry.gauge(key).set(value)
                    self.live_state = state
                    for cb in self.callbacks:
                        # fires EVERY optimizer step (no metrics, no device sync);
                        # on_step_end below fires only on log steps with host metrics
                        if hasattr(cb, "on_train_step"):
                            cb.on_train_step(self, step)

                    if step % cfg.log_every_n_steps == 0 or step == cfg.max_steps:
                        # ONE batched transfer: per-value device_get pays one
                        # host<->device round trip per metric, which on a
                        # remote-attached TPU leaves the chip idle between steps.
                        # The blocking fetch drains the async dispatch queue, so
                        # its wall time is accumulated device step time —
                        # goodput bills it to step_compute
                        with self.ledger.measure("step_compute"):
                            metrics = {
                                k: np.asarray(v) for k, v in jax.device_get(metrics).items()
                            }
                        first_fetch_done(step)
                        # divergence injection (chaos nan_step/spike_step):
                        # poison the HOST metrics the guards read — the
                        # device state stays healthy, which is exactly what
                        # the rollback-and-skip loop needs to prove on CPU
                        chaos = get_chaos()
                        if chaos is not None:
                            chaos.maybe_poison_metrics(
                                step, metrics, fresh_start=fresh_start
                            )
                        now = time.perf_counter()
                        metrics["lr"] = np.asarray(schedule(step))
                        metrics["steps_per_sec"] = (step - window_step) / max(
                            now - window_time, 1e-9
                        )
                        metrics.update(self.counters)
                        window_time, window_step = now, step
                        # telemetry rides the metrics dict: JSONL/W&B loggers
                        # persist the goodput breakdown, device gauges, and
                        # registry snapshot (compile_time_s, data/*, checkpoint/*)
                        metrics.update(self.ledger.summary())
                        if self._slo is not None:
                            # before the snapshot below, so this log step's
                            # record carries the fresh slo/* burn gauges
                            self._slo.observe_goodput(
                                float(metrics["goodput/goodput_pct"]), step=step
                            )
                        # per-device HBM sample: publishes the hbm/* gauges
                        # (worst device + per-device rollup) AND appends to
                        # the run dir's hbm.jsonl timeline in one pass
                        if self._hbm_timeline is not None:
                            metrics.update(self._hbm_timeline.sample(step))
                        else:
                            metrics.update(hbm_gauges())
                        metrics.update(self.telemetry.snapshot())
                        logger.info(
                            "step %d | loss %.4f | grad_norm %.3f | %.2f steps/s "
                            "| goodput %.1f%%",
                            step, metrics["loss"], metrics["grad_norm"],
                            metrics["steps_per_sec"], metrics["goodput/goodput_pct"],
                        )
                        for cb in self.callbacks:
                            if hasattr(cb, "on_step_end"):
                                cb.on_step_end(self, step, metrics)
                    self.live_state = None

                    if step == first_process_step:
                        # drop the compile/warmup-laden first step from the next
                        # throughput window (after its possible log above)
                        window_time, window_step = time.perf_counter(), step

                    if cfg.val_check_interval and step % cfg.val_check_interval == 0:
                        with self.ledger.measure("validation"), \
                                tracer.measure("train", "validation", step=step):
                            self._run_validation(eval_step, state, datamodule, step)

                    if (
                        self.checkpointer is not None
                        and cfg.checkpoint_every_n_steps
                        and step % cfg.checkpoint_every_n_steps == 0
                        # a guard may have flagged THIS step's state as diverged
                        # (on_step_end runs first) — never persist it
                        and not self.abort_final_save
                        # guards only see metrics on log steps; the save gate must
                        # not trust log cadence — check this step's loss directly
                        and self._loss_finite(metrics, step)
                    ):
                        with self.ledger.measure("checkpoint_save"), \
                                tracer.measure(
                                    "train", "checkpoint_save", step=step
                                ):
                            self.checkpointer.save(
                                step, state, counters=dict(self.counters),
                                extra=self._save_extra(),
                            )
                        self._last_interval_save = step

                    # simulated failures (fault injection): a REAL SIGTERM to
                    # this process, so the whole handler -> boundary-check ->
                    # emergency-save path below is the one being exercised;
                    # or a SIGKILL — the hard death only `supervise` survives
                    chaos = get_chaos()
                    if chaos is not None:
                        # slow-step first: the injected dead time lands in
                        # the NEXT boundary's SLO interval like a real
                        # sustained regression would
                        chaos.maybe_slow_step(step)
                        chaos.maybe_sigterm(step)
                        chaos.maybe_sigkill(step, fresh_start)

                    if self._shutdown is not None and self._shutdown.should_stop(
                        step, cfg.resilience.preemption_sync_every_n_steps
                    ):
                        logger.warning(
                            "preemption (%s) at step %d: committing an emergency "
                            "checkpoint, then exiting resumable",
                            self._shutdown.reason, step,
                        )
                        self.telemetry.counter("resilience/preemptions").inc()
                        self._preempted = True
                        self.should_stop = True

                    if self.should_stop:
                        logger.info("stopping at step %d (callback request)", step)
                        break
                return state
            finally:
                # a callback that raised must not leave the state pinned
                self.live_state = None
                # a segment that ended before any fetch: the span, no `ready`
                first_step_args = None
                first_step.close()
                if prefetcher is not None:
                    prefetcher.close()

        try:
            # the recovery driver: one segment with recovery unset; with it,
            # a NanGuard raise rolls the state back to the last committed
            # checkpoint, registers the poisoned data window, optionally
            # cools the LR, and re-enters — all without leaving the process
            # (docs/resilience.md#recovery). Budget exhaustion re-raises as
            # RecoveryExhaustedError (CLI exit 76).
            while True:
                try:
                    state = run_segment(state, start_micro)
                    break
                except (NonFiniteLossError, LossSpikeError) as failure:
                    if recovery is None:
                        raise
                    # raises RecoveryExhaustedError when the budget is spent
                    plan = recovery.on_failure(failure, self.last_step or 0)
                    # the traceback frames pin the (discarded) diverged
                    # state's buffers; clear them before the restore
                    # allocates a second copy
                    import traceback as _tb

                    _tb.clear_frames(failure.__traceback__)
                    state, start_micro = self._rollback_state(init_state)
                    failed_micro_end = plan.failed_step * cfg.accumulate_grad_batches
                    win_start, win_len = recovery.register_skip(
                        failed_micro_end, start_micro
                    )
                    logger.warning(
                        "recovery rollback %d/%d after %s at step %d: restored "
                        "micro-step %d, skipping data window [%d, %d), resuming "
                        "in-process",
                        plan.rollback_index, recovery.config.max_rollbacks,
                        type(failure).__name__, plan.failed_step, start_micro,
                        win_start, win_start + win_len,
                    )
                    # flight recorder: the ring holds the steps that led
                    # into the divergence — dump them next to the guard's
                    # anomaly-<step>.json before the loop re-enters
                    tracer.instant(
                        "resilience", "rollback",
                        failed_step=plan.failed_step,
                        restored_micro=start_micro,
                        rollback_index=plan.rollback_index,
                        failure=type(failure).__name__,
                    )
                    rollback_run_dir = resolve_run_dir(self)
                    if rollback_run_dir is not None:
                        tracer.flight_dump(
                            rollback_run_dir, f"rollback-{plan.failed_step}"
                        )
                    if self._profile_trigger is not None:
                        # matching-tag device profile of the re-entered
                        # steps: did the rollback actually clear the
                        # device-side pathology, or does the replayed
                        # window stall the same way?
                        self._profile_trigger.request(
                            f"rollback-{plan.failed_step}", source="rollback"
                        )
                    for cb in self.callbacks:
                        if hasattr(cb, "on_rollback"):
                            cb.on_rollback(
                                self, start_micro // cfg.accumulate_grad_batches
                            )
                    if recovery.register_cooldown(
                        start_micro // cfg.accumulate_grad_batches
                    ):
                        # re-wrap the LR schedule and rebuild the jitted
                        # steps against it. The opt-state LAYOUT is
                        # untouched (only the schedule closure changed), so
                        # the restored state drops straight in; the rebuilt
                        # step's first call recompiles (billed to the
                        # compile phase — aot_step is dropped).
                        tx, schedule = self._build_tx(
                            objective,
                            schedule_transform=recovery.schedule_transform(),
                        )
                        train_step = jax.jit(
                            self._build_step(objective, tx),
                            in_shardings=(self.state_shardings, batch_shardings),
                            out_shardings=(self.state_shardings, None),
                            donate_argnums=0,
                        )
                        if health_every:
                            health_step = jax.jit(
                                self._build_health_step(objective, tx),
                                in_shardings=(self.state_shardings, batch_shardings),
                                out_shardings=(self.state_shardings, None),
                                donate_argnums=0,
                            )
                            health_compiled = False
                        aot_step = None
                        step_fn = train_step
        finally:
            # the watchdog patrols the LOOP; the epilogue below legitimately
            # blocks on the final save + async barrier for however long the
            # checkpoint takes — a dump (or worse, an abort) mid-commit
            # would manufacture the very partial checkpoint it guards
            # against. fit's finally makes this stop idempotent.
            if self._watchdog is not None:
                self._watchdog.stop()

        final_save_committed = False
        if (
            self.checkpointer is not None
            and self.last_step is not None
            and not self.abort_final_save
            and self._loss_finite(self.last_metrics, self.last_step)
        ):
            # label with the step actually reached: an early stop
            # (should_stop) must not masquerade as a completed run
            with self.ledger.measure("checkpoint_save"), \
                    tracer.measure(
                        "train", "checkpoint_save", step=self.last_step
                    ):
                # force=True: this step may collide with a stale/partial
                # entry from a PREVIOUS run of the same dir (the emergency-
                # save case) — but when THIS fit's interval save already
                # wrote the identical state, re-saving would be pure waste
                if self.last_step != self._last_interval_save:
                    if self._preempted:
                        self.telemetry.counter("resilience/emergency_saves").inc()
                    self.checkpointer.save(
                        self.last_step, state, counters=dict(self.counters),
                        force=True, extra=self._save_extra(),
                    )
                # the barrier: after this, the newest save (emergency or
                # interval) is durable — safe to exit
                self.checkpointer.wait()
                final_save_committed = True
        elif self.checkpointer is not None and self._preempted:
            # the emergency save was vetoed (diverged/non-finite state) —
            # still barrier any in-flight async interval save so what the
            # relaunch restores is durable before the resumable exit
            with self.ledger.measure("checkpoint_save"):
                self.checkpointer.wait()
        # one final telemetry record: the post-loop checkpoint save/wait
        # landed after the last log step, so without this flush every
        # logger's totals would miss that tail (report reads the last
        # telemetry record as the run total)
        if self.last_step is not None:
            counts = tracer.counts()
            self.telemetry.gauge("trace/events_recorded").set(counts["recorded"])
            self.telemetry.gauge("trace/events_written").set(counts["written"])
            self.telemetry.gauge("trace/flight_dumps").set(counts["flight_dumps"])
            tracer.flush()
            record = {
                **self.ledger.summary(),
                **hbm_gauges(),
                **self.telemetry.snapshot(),
            }
            for cb in self.callbacks:
                if hasattr(cb, "on_telemetry"):
                    cb.on_telemetry(self, self.last_step, record)
        for cb in self.callbacks:
            if hasattr(cb, "on_fit_end"):
                cb.on_fit_end(self, state)
        if self._preempted:
            # after the emergency checkpoint is durable and every logger is
            # flushed/closed: hand the supervisor contract up (the CLI maps
            # this to RESUMABLE_EXIT_CODE; relaunching `fit` resumes via
            # maybe_restore)
            saved = final_save_committed
            raise PreemptionInterrupt(
                self.last_step,
                f"preempted ({self._shutdown.reason if self._shutdown else 'signal'}) "
                f"at step {self.last_step}; "
                + (
                    "emergency checkpoint committed — relaunch fit with the "
                    "same config to resume"
                    if saved
                    else "NO resumable checkpoint written by this fit — a "
                    "relaunch resumes from the newest previous one, if any"
                ),
            )
        return state

    def _run_validation(self, eval_step, state, datamodule, step) -> None:
        losses, weights = [], []
        for i, batch in enumerate(datamodule.val_batches()):
            if self.config.limit_val_batches and i >= self.config.limit_val_batches:
                break
            if self._watchdog is not None:
                # a validation epoch can legitimately outlast the no-progress
                # timeout; each batch is progress
                self._watchdog.beat("train_loop", step=step)
            out = jax.device_get(eval_step(state, batch))
            losses.append(out["loss"])
            weights.append(out["target_tokens"])
        if losses:
            val_loss = float(np.average(losses, weights=weights))
            logger.info("step %d | val_loss %.4f", step, val_loss)
            for cb in self.callbacks:
                if hasattr(cb, "on_validation_end"):
                    cb.on_validation_end(self, step, {"val_loss": val_loss})

    @staticmethod
    def _loss_finite(metrics, step) -> bool:
        """True when this step's loss can be persisted. Forces a device sync,
        so it is called only on checkpoint steps — a diverged state must never
        become the newest checkpoint regardless of log cadence."""
        if metrics is None or "loss" not in metrics:
            return True
        loss = float(jax.device_get(metrics["loss"]))
        if np.isfinite(loss):
            return True
        logger.warning(
            "skipping checkpoint at step %d: non-finite loss %s", step, loss
        )
        return False

    @staticmethod
    def _batch_counts(batch: dict) -> tuple[int, int]:
        """(samples, tokens) from the HOST-side numpy batch; handles both CLM
        batches (`input_ids`) and preference batches
        (`chosen_/rejected_input_ids`). Must run before device placement —
        on a device copy it would force a blocking sync each step."""
        id_keys = [k for k in batch if k == "input_ids" or k.endswith("_input_ids")]
        first = batch[id_keys[0]]
        samples = int(first.shape[0])
        tokens = 0
        for key in id_keys:
            prefix = key[: -len("input_ids")]
            seg = batch.get(prefix + "segment_ids")
            tokens += int((seg > 0).sum()) if seg is not None else int(batch[key].size)
        return samples, tokens

    def _apply_counts(self, counts: tuple[int, int]) -> None:
        self.counters["consumed_samples"] += counts[0]
        self.counters["consumed_tokens"] += counts[1]

    # ------------------------------------------------------------ recovery

    def _rollback_state(self, init_state_fn: Callable) -> tuple[TrainState, int]:
        """Rewind to the last committed checkpoint (consumed counters and
        callback state included — replayed steps must not double-count),
        or to a deterministic fresh init when nothing was ever committed.
        Returns (state, micro-step to resume from)."""
        if self.checkpointer is not None:
            # barrier any in-flight async save first: the newest commit is
            # the rollback target, not a half-written step
            with self.ledger.measure("checkpoint_save"):
                self.checkpointer.wait()
            restored = self.checkpointer.maybe_restore(
                self.abstract_state, self.state_shardings
            )
            if restored is not None:
                state, meta = restored
                self.counters = {"consumed_samples": 0, "consumed_tokens": 0}
                self.counters.update(meta.get("counters", {}))
                self._load_callback_state(meta)
                return state, int(jax.device_get(state.step))
        logger.warning(
            "recovery: no committed checkpoint to roll back to — "
            "re-initializing from step 0"
        )
        self.counters = {"consumed_samples": 0, "consumed_tokens": 0}
        return init_state_fn(), 0

    def _save_extra(self) -> dict:
        """JSON-serializable checkpoint-metadata riders: the recovery
        skip-list/cooldown windows (a resumed run must replay the same
        skips), the live topology + data-stream cursor (what an elastic
        relaunch plans its new mesh against — docs/resilience.md#elastic),
        and every callback's `state_dict` (NanGuard's EMA/z-score trackers
        and counters)."""
        extra: dict = {}
        if self._recovery is not None:
            extra["recovery"] = self._recovery.metadata()
        if self.mesh is not None:
            extra["topology"] = {
                "device_count": int(self.mesh.devices.size),
                "mesh": self._mesh_axis_sizes(),
            }
            if self._global_batch_size:
                micro = (self.last_step or 0) * self.config.accumulate_grad_batches
                dp_ways = int(self.mesh.shape["data"]) * int(self.mesh.shape["fsdp"])
                extra["data_state"] = {
                    # the stream key an elastic resume must hold fixed
                    "global_batch_size": int(self._global_batch_size),
                    # samples drawn from the global stream so far: the
                    # cursor is step-derived, NOT replica-derived, which is
                    # exactly why a DP resize replays the same stream
                    "sample_cursor": micro * int(self._global_batch_size),
                    # rows each data-parallel shard served under THIS
                    # topology (informational: the next segment derives its
                    # own stride from the same global batch)
                    "replica_stride": int(self._global_batch_size) // dp_ways,
                }
        cb_state: dict = {}
        for cb in self.callbacks:
            fn = getattr(cb, "state_dict", None)
            if callable(fn):
                try:
                    cb_state[type(cb).__name__] = fn()
                except Exception:
                    logger.exception(
                        "callback %s state_dict failed (not persisted)",
                        type(cb).__name__,
                    )
        if cb_state:
            extra["callbacks"] = cb_state
        return extra

    def _load_callback_state(self, meta: dict | None) -> None:
        """Restore callback state riders from checkpoint metadata (keyed by
        callback class name; absent entries and failures leave the callback
        at its fresh-construction state)."""
        states = (meta or {}).get("callbacks") or {}
        for cb in self.callbacks:
            data = states.get(type(cb).__name__)
            if data is not None and hasattr(cb, "load_state_dict"):
                try:
                    cb.load_state_dict(data)
                except Exception:
                    logger.exception(
                        "callback %s load_state_dict failed (starting fresh)",
                        type(cb).__name__,
                    )

    # ------------------------------------------------------------ validate

    def restore_for_inference(
        self,
        objective,
        resume_step: int | None = None,
        sample_batch: dict | None = None,
    ) -> TrainState:
        """READ-ONLY restore for the inference/eval CLIs (`generate`,
        `evaluate` — docs/inference.md): build the mesh and the abstract
        train state exactly as `fit` would (the optimizer-state pytree
        layout depends on the trainer settings, so the SAME TrainerConfig
        the checkpoint was written under must be used), then restore the
        newest (or given) step straight into sharded buffers with
        repair=False — an inference run must never delete or repair
        anything in the checkpoint directory. Leaves `self.mesh` /
        `self.state_shardings` populated for the caller's own jits.

        `sample_batch` feeds the objective's init_params shape evaluation;
        objectives whose init reads non-CLM keys (DPO/ORPO use
        `chosen_input_ids`) must pass a real batch — the CLM-shaped
        synthetic default only suits single-model causal-LM objectives."""
        if self.checkpointer is None:
            raise ValueError("restore_for_inference requires a checkpointer")
        self.mesh = build_mesh(self.config.mesh, self.devices)
        with self.mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
            if sample_batch is None:
                # parameter shapes are sequence-length independent, so a
                # synthetic batch is enough to shape-evaluate the state
                sample_batch = {"input_ids": np.zeros((1, 8), np.int32)}
            tx, _ = self._build_tx(objective)
            abstract_boxed = self._abstract_state(objective, sample_batch, tx)
            self.state_shardings = self._state_shardings(abstract_boxed)
            abstract_state = nn.meta.unbox(abstract_boxed)
            self.abstract_state = abstract_state
            restored = self.checkpointer.maybe_restore(
                abstract_state, self.state_shardings, resume_step, repair=False
            )
            if restored is None:
                raise ValueError(
                    f"no checkpoint found in {self.checkpointer.directory}"
                )
            state, _ = restored
            return state

    def validate_from_checkpoint(
        self, objective, datamodule, resume_step: int | None = None
    ) -> dict[str, float]:
        """Restore the latest (or given) checkpoint and run validation
        (the CLI `validate` subcommand, reference `llm-training validate`)."""
        datamodule.setup()
        # a REAL batch, not the synthetic default: DPO/ORPO objectives
        # shape-evaluate from preference keys (chosen_/rejected_input_ids)
        sample_batch = next(datamodule.train_batches())
        state = self.restore_for_inference(
            objective, resume_step, sample_batch=sample_batch
        )
        with self.mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
            eval_step = jax.jit(
                self._build_eval_step(objective),
                in_shardings=(self.state_shardings, _batch_shardings(sample_batch, self.mesh)),
            )
            losses, weights = [], []
            limit = self.config.limit_val_batches
            for i, batch in enumerate(datamodule.val_batches()):
                if limit and i >= limit:
                    break
                out = jax.device_get(eval_step(state, batch))
                losses.append(out["loss"])
                weights.append(out["target_tokens"])
        if not losses:
            raise ValueError("datamodule produced no validation batches")
        result = {"val_loss": float(np.average(losses, weights=weights))}
        logger.info("validate: %s", result)
        return result

    def validate(self, objective, datamodule, state: TrainState) -> dict[str, float]:
        datamodule.setup()
        mesh = self.mesh or build_mesh(self.config.mesh, self.devices)
        # same sharding discipline as fit/validate_from_checkpoint: explicit
        # in_shardings (state shardings from fit if available, else the live
        # arrays' own shardings)
        state_shardings = (
            self.state_shardings
            if self.state_shardings is not None
            else jax.tree.map(lambda x: x.sharding, state)
        )
        with mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
            eval_step = None
            losses, weights = [], []
            for batch in datamodule.val_batches():
                if eval_step is None:
                    eval_step = jax.jit(
                        self._build_eval_step(objective),
                        in_shardings=(state_shardings, _batch_shardings(batch, mesh)),
                    )
                out = jax.device_get(eval_step(state, batch))
                losses.append(out["loss"])
                weights.append(out["target_tokens"])
        if not losses:
            raise ValueError(
                "datamodule produced no validation batches "
                "(set validation_split or provide a val dataset)"
            )
        return {"val_loss": float(np.average(losses, weights=weights))}
