"""Arcee AFMoE model config (`model_type: afmoe`,
https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json).

The field names are the source's own. A stack whose layers differ in how much
of the past they keep: `layer_types[l] == "sliding_attention"` attends inside
`sliding_window` with rotary positions, `"full_attention"` (every
`global_attn_every_n_layers`-th) over everything with no positional term. The
first `num_dense_layers` layers have a dense SwiGLU MLP of
`intermediate_size`, the others a sigmoid-routed sparse expert layer with
`num_shared_experts` always on (`models/deepseek/model.py:DeepseekMoE`,
version 3, no groups).

What the source's keys do not give is this family's assumption, written from
the family's published modelling code and listed in docs/models.md: the
attention output's sigmoid gate and its shape, the RMSNorm over each q and k
head, no rotary on the full layers, the four norms of a layer and their
order, the embedding's `sqrt(hidden_size)` under `mup_enabled`, the expert
bias's role (the choice only), the shared expert's width, `initializer_range`
and the attention scale.
"""

from __future__ import annotations

from typing import ClassVar, Literal

from pydantic import model_validator

from llm_training_tpu.models.base import BaseModelConfig, KVCacheSpec

LayerType = Literal["sliding_attention", "full_attention"]


class AfmoeConfig(BaseModelConfig):
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144  # the dense layers' MLP
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: Literal["silu"] = "silu"
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: None = None
    tie_word_embeddings: Literal[False] = False
    mup_enabled: bool = True
    pad_token_id: int | None = None
    bos_token_id: int | None = None
    eos_token_id: int | list[int] | None = None

    # --- which layers keep a window. `layer_types` past the depth is ignored,
    # so a depth cut keeps the list; None: every `global_attn_every_n_layers`-th
    # layer is full, the others sliding.
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: list[LayerType] | None = None

    # --- experts
    num_experts: int = 128  # the router's outputs
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: Literal["sigmoid"] = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    # groups of experts: published as 1 of 1 (no limit), and nothing else is
    # implemented
    num_expert_groups: Literal[1] = 1
    num_limited_groups: Literal[1] = 1
    load_balance_coeff: float = 0.001  # the bias's update rate: read by no forward
    # an expert-parallel share: this many experts, from `experts_first` on,
    # are held (and computed) here; the router still scores all of them.
    # None = all.
    experts_held: int | None = None
    experts_first: int = 0
    moe_impl: Literal["auto", "dense", "ragged"] = "auto"

    enable_gradient_checkpointing: bool = False
    recompute_granularity: Literal["full", "selective"] = "full"
    scan_layers: bool = True
    # the kernel of the forward WITHOUT a cache (training, evaluation)
    attention_impl: Literal["auto", "xla", "pallas"] = "auto"

    # what DeepseekMoE dispatches on, not options of this family: sigmoid
    # scores with a bias for the choice only (its version 3), no expert groups
    version: ClassVar[int] = 3
    n_group: ClassVar[None] = None
    topk_method: ClassVar[str] = "noaux_tc"

    @model_validator(mode="after")
    def _validate(self) -> "AfmoeConfig":
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if self.layer_types is not None and len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of {self.num_hidden_layers}"
            )
        if not 0 <= self.experts_first <= self.num_experts - self.num_experts_held:
            raise ValueError(
                f"experts {self.experts_first}..{self.experts_first + self.num_experts_held} "
                f"are not among the router's {self.num_experts}"
            )
        return self

    # --- the names DeepseekMoE reads
    @property
    def n_routed_experts(self) -> int:
        return self.num_experts

    @property
    def n_shared_experts(self) -> int:
        return self.num_shared_experts

    @property
    def norm_topk_prob(self) -> bool:
        return self.route_norm

    @property
    def routed_scaling_factor(self) -> float:
        return self.route_scale

    @property
    def num_experts_held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim

    def layer_is_window(self, layer_idx: int) -> bool:
        if self.layer_types is not None:
            return self.layer_types[layer_idx] == "sliding_attention"
        return (layer_idx + 1) % self.global_attn_every_n_layers != 0

    @property
    def layer_kinds(self) -> list[tuple[bool, bool]]:
        """(keeps a window, has experts) a layer."""
        return [
            (self.layer_is_window(i), i >= self.num_dense_layers)
            for i in range(self.num_hidden_layers)
        ]

    @property
    def scan_plan(self) -> tuple[int, int, int]:
        """(layers looped in front, scanned periods, layers in a period). The
        dense layers do not end on a period of the window pattern, so the
        whole periods that hold them are looped; what follows repeats one body
        and scans. (depth, 0, 0) = loop it all: `scan_layers` off, or no whole
        period left."""
        from llm_training_tpu.models.moe_scan_io import detect_period

        kinds, depth = self.layer_kinds, self.num_hidden_layers
        period = detect_period([window for window, _ in kinds]) if self.scan_layers else 0
        if not period:
            return depth, 0, 0
        front = min(depth, -(-self.num_dense_layers // period) * period)
        periods = (depth - front) // period
        if not periods or (depth - front) % period:
            return depth, 0, 0
        return front, periods, period

    def cache_specs(self) -> tuple[tuple[KVCacheSpec, KVCacheSpec], None]:
        """The one declaration both pools, the dense buffers and their
        shardings derive from (`infer/cache.py:kv_groups`): the key/value
        layers in TWO groups, those that keep every token and those that keep
        `sliding_window`. The groups follow from `layer_types`; a page budget
        a request from the window, the engine's chunk and page size
        (`serve/paged_cache.py:window_page_budget`)."""
        window = sum(is_window for is_window, _ in self.layer_kinds)
        heads, dim = self.num_key_value_heads, self.head_dim
        return (
            (
                KVCacheSpec(self.num_hidden_layers - window, heads, dim),
                KVCacheSpec(window, heads, dim, window=self.sliding_window),
            ),
            None,
        )
