"""On-device model-health metrics: per-layer-group gradient/parameter/update
norms and MoE router statistics.

Large-scale TPU training treats per-layer norm monitoring as the primary
tool for catching instabilities before they burn accelerator-hours (arXiv
2204.06514 §5): a run whose scalar loss still looks healthy can already have
one layer's gradients exploding. This module computes that signal INSIDE the
jitted train step (no extra forward, no host round trip beyond the one
`device_get` the trainer issues on health steps) at a configurable cadence —
`HealthConfig.every_n_steps`, default off, in which case the compiled train
step is byte-identical to the uninstrumented one.

Metric cardinality is bounded by grouping parameters per *layer group*
rather than per tensor: scanned decoder stacks (the flax `nn.scan` 'layers'
stacking axis) yield one group per layer index along the stack; unscanned
`layers_<i>` module paths group per block; everything else (embeddings,
final norm, lm_head) groups under its top-level module name. The grouping
spec is derived host-side from the *boxed* abstract parameter tree (the
`nn.Partitioned` logical-axis metadata identifies stacked leaves), so the
jitted metric computation is pure array math over a static plan.

Key schema (all fp32 scalars; see docs/observability.md):

- ``health/grad_norm/<group>``      — L2 norm of the group's gradients
- ``health/param_norm/<group>``     — L2 norm of the group's parameters
- ``health/update_norm/<group>``    — L2 norm of the optimizer update
- ``health/update_ratio/<group>``   — update_norm / param_norm (the classic
  "effective learning rate" stability signal; ~1e-3 is healthy, >>1e-2
  flags a layer about to blow up)
- ``health/moe/router_entropy/layer_<i>``  — normalized entropy of the
  layer's expert load distribution (1.0 = perfectly balanced, →0 = collapse)
- ``health/moe/max_expert_share/layer_<i>`` / ``min_expert_share`` — hottest
  / coldest expert's share of the layer's routed assignments
- ``health/moe/aux_loss/layer_<i>`` — per-layer Switch/Mixtral balancing
  loss E·Σ(f·P) (the pooled scalar the objective optimizes hides per-layer
  imbalance)
- ``health/moe/load_frac/expert_<e>`` — per-expert load fraction averaged
  over MoE layers (emitted only when num_experts <= MAX_EXPERT_KEYS)
- ``health/moe/dropped_rows`` / ``dropped_frac`` — (token, expert)
  assignments lost to capacity buffers (EP rank buffers / bucketed capacity)
"""

from __future__ import annotations

import math
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
from pydantic import BaseModel, ConfigDict, Field

# per-expert load_frac keys are emitted only up to this expert count —
# beyond it the per-layer entropy/share scalars carry the signal without
# exploding metric cardinality (DeepSeek-V3 has 256 routed experts)
MAX_EXPERT_KEYS = 32

# scan-stacked parameter axes named by nn.scan's metadata_params
# (models use PARTITION_NAME 'layers'); pipeline parallelism adds a
# 'stages' vmap axis OUTSIDE it — per-layer keys must span (stage, layer)
# so provenance names one real decoder layer, not the same within-stage
# index of every stage
_STACK_AXIS_NAME = "layers"
_STAGE_AXIS_NAME = "stages"
_BLOCK_RE = re.compile(r"^(.+?)_(\d+)$")


class HealthConfig(BaseModel):
    """Trainer-level cadence for the model-health layer.

    `every_n_steps: None` (the default) disables it entirely — no health
    step is built and the compiled train step is unchanged. When set, every
    N-th optimizer step runs the instrumented step variant and the trainer
    publishes the host-fetched metrics into the telemetry registry (so
    `telemetry.jsonl`, W&B, and `report` pick them up with no extra wiring).
    The fetch forces one device sync per health step (what that costs a
    step has no chip measurement: PERF.md section 7; see
    docs/observability.md for guidance).
    """

    model_config = ConfigDict(extra="forbid")

    every_n_steps: int | None = Field(None, ge=1)


class ParamGroups:
    """Static per-leaf grouping plan: `leaves[i] = (group, axes, length)`
    aligned with the flatten order of the (unboxed) parameter tree. `axes`
    is the tuple of stacking axis indices for stacked leaves — ('stages',
    'layers') order under pipeline parallelism, so the flattened per-index
    norms enumerate GLOBAL decoder layers (stage s, within-stage i ⇒
    s·L/S + i) — and None for plain leaves; `length` is the flattened
    per-group index count."""

    def __init__(self, leaves: list[tuple[str, tuple[int, ...] | None, int | None]]):
        self.leaves = leaves

    def __len__(self) -> int:
        return len(self.leaves)


def _path_components(path) -> list[str]:
    comps = []
    for entry in path:
        key = getattr(entry, "key", None)
        if key is None:
            key = getattr(entry, "idx", None)
        if key is None:
            key = getattr(entry, "name", entry)
        comps.append(str(key))
    return comps


def _stack_base(prefix: list[str]) -> str:
    """Group base for a scan-stacked leaf: the path down to (and including)
    the scan module — 'layers'/'*_layers' by this repo's naming convention,
    falling back to the top component (the pipeline's 'pipeline/ticks'
    nesting). Multi-model objectives (DPO's policy/ref pair) keep their
    subtree prefix, so 'policy/layers' and 'ref/layers' never collide."""
    for i, comp in enumerate(prefix):
        if comp == _STACK_AXIS_NAME or comp.endswith("_" + _STACK_AXIS_NAME):
            return "/".join(prefix[: i + 1])
    return prefix[0] if prefix else "root"


def build_param_groups(boxed_params) -> ParamGroups:
    """Derive the layer-group plan from the BOXED abstract parameter tree
    (`jax.eval_shape` of init, before `nn.meta.unbox`): `nn.Partitioned`
    leaves whose logical names contain the scan stacking axis ('layers')
    group per index along that axis under their scan-module path
    (`layers_00`, `moe_layers_03`, `policy/layers_01`, ...); unscanned
    `<module>_<i>` path components group per block; everything else groups
    under its (subtree-qualified) module name. Boxed and unboxed trees
    flatten in the same leaf order, so the plan indexes straight into the
    step's params/grads/updates leaves."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        boxed_params, is_leaf=lambda x: isinstance(x, nn.Partitioned)
    )
    leaves: list[tuple[str, int | None, int | None]] = []
    for path, leaf in flat:
        comps = [c for c in _path_components(path) if c != "params"]
        prefix = comps[:-1] if len(comps) > 1 else comps
        names = tuple(leaf.names) if isinstance(leaf, nn.Partitioned) else ()
        shape = leaf.value.shape if isinstance(leaf, nn.Partitioned) else leaf.shape
        if _STACK_AXIS_NAME in names:
            # stage axis (pipeline) first so the flattened index is the
            # global decoder-layer number
            axes = tuple(
                names.index(n) for n in (_STAGE_AXIS_NAME, _STACK_AXIS_NAME)
                if n in names
            )
            length = 1
            for axis in axes:
                length *= int(shape[axis])
            leaves.append((_stack_base(prefix), axes, length))
            continue
        group = None
        for i, comp in enumerate(prefix):
            match = _BLOCK_RE.match(comp)
            if match:
                stem, idx = match.groups()
                group = "/".join(prefix[:i] + [f"{stem}_{int(idx):02d}"])
                break
        if group is None:
            group = "/".join(prefix[:2]) if prefix else (comps[0] if comps else "root")
        leaves.append((group, None, None))
    return ParamGroups(leaves)


def _sq(x: jnp.ndarray, axes: tuple[int, ...] | None) -> jnp.ndarray:
    """Sum of squares reduced over everything but `axes`, returned FLAT in
    `axes` order (stage-major under PP ⇒ global layer order)."""
    x = x.astype(jnp.float32)
    if axes is None:
        return jnp.sum(x * x)
    out = jnp.sum(x * x, axis=tuple(i for i in range(x.ndim) if i not in axes))
    # the reduction keeps surviving dims in array order; permute to `axes`
    # order before flattening
    kept = sorted(axes)
    out = out.transpose([kept.index(a) for a in axes])
    return out.reshape(-1)


def layer_health_metrics(
    groups: ParamGroups, params, grads, updates, prefix: str = "health"
) -> dict[str, jnp.ndarray]:
    """Per-layer-group grad/param/update norms + update-to-param ratios,
    computed inside the jitted step (tiny reductions — XLA fuses them into
    the backward). Stacked groups emit one key per layer index
    (`<base>_<i:02d>`); the key set is static, the values are traced.

    Under gradient accumulation the health step runs on the boundary
    micro-step: grad norms reflect that single micro-batch's gradients —
    the SAME semantics as the headline `grad_norm` metric — while
    update norms reflect the full accumulated MultiSteps update (so
    update_ratio is the real per-optimizer-step movement)."""
    trees = (params, grads, updates)
    flat = [jax.tree.leaves(t) for t in trees]
    if any(len(f) != len(groups) for f in flat):
        raise ValueError(
            f"param-group plan covers {len(groups)} leaves but trees have "
            f"{[len(f) for f in flat]} — was the plan built from a different "
            "model?"
        )
    acc: dict[str, list] = {}
    meta: dict[str, int | None] = {}
    for i, (group, axes, length) in enumerate(groups.leaves):
        sqs = [_sq(f[i], axes) for f in flat]
        if group in acc:
            if meta[group] != length:
                # a scalar+vector (or mismatched-stack) mix would silently
                # broadcast into garbage norms — the grouping rule must keep
                # stacked and plain leaves in distinct groups
                raise ValueError(
                    f"param group {group!r} mixes leaves with stack lengths "
                    f"{meta[group]} and {length}"
                )
            acc[group] = [a + s for a, s in zip(acc[group], sqs)]
        else:
            acc[group] = sqs
            meta[group] = length
    out: dict[str, jnp.ndarray] = {}

    def emit(key: str, p_sq, g_sq, u_sq) -> None:
        p_n, g_n, u_n = jnp.sqrt(p_sq), jnp.sqrt(g_sq), jnp.sqrt(u_sq)
        out[f"{prefix}/param_norm/{key}"] = p_n
        out[f"{prefix}/grad_norm/{key}"] = g_n
        out[f"{prefix}/update_norm/{key}"] = u_n
        out[f"{prefix}/update_ratio/{key}"] = u_n / (p_n + 1e-12)

    for group, (p_sq, g_sq, u_sq) in acc.items():
        length = meta[group]
        if length is None:
            emit(group, p_sq, g_sq, u_sq)
        else:
            for i in range(length):
                emit(f"{group}_{i:02d}", p_sq[i], g_sq[i], u_sq[i])
    return out


def moe_router_health(router_stats, n_tokens: int) -> dict[str, jnp.ndarray]:
    """Per-MoE-layer router health from `CausalLMOutput.router_stats`
    (sel_frac [L, E], mean_prob [L, E], dropped scalar, static layer_ids).

    sel_frac rows sum to ~top_k (each of the K selections per token counts,
    HF load_balancing_loss_func scale), so the load distribution is the row
    normalized to 1. Entropy is normalized by log(E) → 1.0 when perfectly
    balanced. dropped_frac approximates dropped / total assignments using
    `n_tokens` for the token count (padding-token bias is negligible at the
    cadences this runs at)."""
    sel = router_stats.sel_frac.astype(jnp.float32)  # [L, E]
    prob = router_stats.mean_prob.astype(jnp.float32)
    n_layers, n_experts = sel.shape
    ids = router_stats.layer_ids or tuple(range(n_layers))
    load = sel / jnp.maximum(sel.sum(axis=-1, keepdims=True), 1e-9)
    entropy = -(load * jnp.log(load + 1e-9)).sum(axis=-1) / math.log(max(n_experts, 2))
    aux = n_experts * (sel * prob).sum(axis=-1)
    out: dict[str, jnp.ndarray] = {}
    for j, layer_id in enumerate(ids):
        key = f"layer_{int(layer_id):02d}"
        out[f"health/moe/router_entropy/{key}"] = entropy[j]
        out[f"health/moe/max_expert_share/{key}"] = load[j].max()
        out[f"health/moe/min_expert_share/{key}"] = load[j].min()
        out[f"health/moe/aux_loss/{key}"] = aux[j]
    if n_experts <= MAX_EXPERT_KEYS:
        mean_load = load.mean(axis=0)
        for e in range(n_experts):
            out[f"health/moe/load_frac/expert_{e:02d}"] = mean_load[e]
    dropped = jnp.asarray(router_stats.dropped, jnp.float32)
    total_rows = jnp.maximum(sel.sum() * float(n_tokens), 1.0)
    out["health/moe/dropped_rows"] = dropped
    out["health/moe/dropped_frac"] = dropped / total_rows
    return out
