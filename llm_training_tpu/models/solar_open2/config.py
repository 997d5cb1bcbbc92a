"""Solar-Open2 model config (`model_type: solar_open2`,
https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json).

A hybrid stack: the layers named in `gqa_layers` are gated softmax
attention with no positional term at all (`use_rope` false), the others
Kimi Delta Attention (a delta rule with a decay per key channel); every
layer's MLP is a sigmoid-routed sparse expert layer with shared experts
(`models/deepseek/model.py:DeepseekMoE`, version 3, no groups).

Sizes the published config does not give are this family's assumptions,
listed in docs/models.md: the low ranks of the two KDA projections
(= the KDA head size), the GQA gate (one sigmoid gate an output
channel from its own projection), no qk-norm, the router's score function
and correction bias (GLM-4.5's convention, which `solar_open` follows).
"""

from __future__ import annotations

from typing import ClassVar, Literal

from pydantic import model_validator

from llm_training_tpu.models.base import (
    BaseModelConfig,
    KVCacheSpec,
    RecurrentCacheSpec,
)


class SolarOpen2Config(BaseModelConfig):
    vocab_size: int = 196608
    hidden_size: int = 4096
    # the dense MLP's width: read only by layers below `first_k_dense_replace`
    intermediate_size: int = 10240
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    rms_norm_eps: float = 1e-5
    pad_token_id: int | None = None
    bos_token_id: int | None = None
    eos_token_id: int | list[int] | None = None
    tie_word_embeddings: bool = False
    # published, and read by nothing while `use_rope` is false
    rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    use_rope: bool = False

    # --- layer kinds: softmax attention on these layers, KDA on the others.
    # Indices past the depth are ignored, so a depth cut keeps the list.
    gqa_layers: list[int] | None = None
    gqa_interval: int = 3  # KDA layers between two GQA layers (None above: every 4th from 0)
    use_gqa_gate: bool = True

    # --- KDA (the published `linear_attn_config`, flat)
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True

    # --- experts (the names DeepseekMoE reads)
    n_routed_experts: int = 320  # the router's outputs
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1280
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    # an expert-parallel share: this many experts, from `experts_first` on,
    # are held (and computed) here; the router still scores all of them.
    # None = all.
    experts_held: int | None = None
    experts_first: int = 0
    moe_impl: Literal["auto", "dense", "ragged"] = "auto"

    enable_gradient_checkpointing: bool = False
    recompute_granularity: Literal["full", "selective"] = "full"
    scan_layers: bool = True
    attention_impl: Literal["auto", "xla", "pallas"] = "auto"

    # what DeepseekMoE dispatches on, not options of this family: sigmoid
    # scores with a correction bias (its version 3), no expert groups
    version: ClassVar[int] = 3
    n_group: ClassVar[None] = None
    topk_method: ClassVar[str] = "noaux_tc"

    @model_validator(mode="after")
    def _validate(self) -> "SolarOpen2Config":
        if self.use_rope:
            raise ValueError("solar_open2 with use_rope=true is not implemented (published: false)")
        if self.kda_use_full_proj:
            raise ValueError("kda_use_full_proj=true is not implemented (published: false)")
        if self.first_k_dense_replace:
            raise ValueError("first_k_dense_replace > 0 is not implemented (published: 0)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        held = self.num_experts_held
        if not 0 <= self.experts_first <= self.n_routed_experts - held:
            raise ValueError(
                f"experts {self.experts_first}..{self.experts_first + held} are not "
                f"among the router's {self.n_routed_experts}"
            )
        return self

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim

    @property
    def num_experts_held(self) -> int:
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    @property
    def kda_rank(self) -> int:
        """Width of the decay's and the output gate's low-rank projections
        (`kda_use_full_proj` false): the KDA head size, by assumption."""
        return self.linear_head_dim

    def layer_is_gqa(self, layer_idx: int) -> bool:
        if self.gqa_layers is not None:
            return layer_idx in self.gqa_layers
        return layer_idx % (self.gqa_interval + 1) == 0

    @property
    def layer_kinds(self) -> list[bool]:
        """True = GQA, False = KDA, a layer."""
        return [self.layer_is_gqa(i) for i in range(self.num_hidden_layers)]

    @property
    def scan_period(self) -> int:
        """Scan-body depth: the pattern's period (4 as published); a stack of
        one period scans once over itself; 0 = loop."""
        if not self.scan_layers:
            return 0
        from llm_training_tpu.models.moe_scan_io import detect_period

        return detect_period(self.layer_kinds) or self.num_hidden_layers

    def cache_specs(self) -> tuple[KVCacheSpec, RecurrentCacheSpec]:
        """The one declaration the pool, the slab and their shardings derive
        from (`infer/cache.py:cache_specs`): pages for the GQA layers, a
        fixed slab a decode slot for the KDA layers."""
        kinds = self.layer_kinds
        width = self.linear_num_heads * self.linear_head_dim
        return (
            KVCacheSpec(sum(kinds), self.num_key_value_heads, self.head_dim),
            RecurrentCacheSpec(
                layers=len(kinds) - sum(kinds), heads=self.linear_num_heads,
                key_dim=self.linear_head_dim, value_dim=self.linear_head_dim,
                conv_taps=self.linear_conv_kernel_dim - 1, conv_channels=3 * width,
            ),
        )
