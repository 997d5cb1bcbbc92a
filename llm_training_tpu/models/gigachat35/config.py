"""GigaChat 3.5 model config (`model_type: gigachat3_5`,
https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json).

A DeepSeek-V3-style stack (`DeepseekConfig`'s keys: latent attention, a
sigmoid router over routed experts beside one shared expert, leading dense
layers, multi-token-prediction modules) in which the MIXER changes by layer:
the layers of `full_attention_layers` are latent attention (MLA) with an
output gate, every other layer a gated delta rule (Qwen3-Next's Gated
DeltaNet: `linear_*` keys, fewer key heads than value heads, ONE convolution
over q, k and v). The feed-forward changes by depth, as in the family
(`first_k_dense_replace`), so the two patterns are out of phase. Four norms a
layer (`layernorm_type: pre_post`), each a `ZeroCenteredGatedNorm`; every
SwiGLU is clamped (`swiglu_limit`).

What the published keys do not settle is this family's assumption, listed in
docs/models.md and in the benchmark's configuration file under `assumed`: the
norm's formula, the attention gate's place and input, the delta-rule block's
output gate, `beta`'s range and the decay's initialisation, the clamp's
place, the multi-token-prediction modules' mixer.

The stack decodes (docs/inference.md, docs/serving.md) with TWO kinds of
cache, declared by `cache_specs()`: a latent row a token for each MLA layer,
a fixed slab a decode slot for each delta-rule layer.
"""

from __future__ import annotations

from typing import Any, ClassVar, Literal

from pydantic import field_validator, model_validator

from llm_training_tpu.models.base import LatentCacheSpec, RecurrentCacheSpec
from llm_training_tpu.models.deepseek.config import DeepseekConfig


class GigaChat35Config(DeepseekConfig):
    mtp_modules_max: ClassVar[int] = 2

    version: Literal[3] = 3
    vocab_size: int = 128256
    hidden_size: int = 7168
    intermediate_size: int = 18432
    num_hidden_layers: int = 40
    num_attention_heads: int = 64
    max_position_embeddings: int = 262144
    rope_theta: float = 100000.0
    rope_scaling: dict[str, Any] | None = {
        "type": "yarn", "factor": 8, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 32768,
    }
    # false: the softmax scale is 1/sqrt(qk_head_dim) whatever the yarn factor
    use_mla_scaling_factor: bool = True
    q_lora_rank: int | None = 1536
    n_routed_experts: int | None = 256
    moe_intermediate_size: int | None = 2048
    first_k_dense_replace: int = 3
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 2
    # `layernorm_type: pre_post`: the family's four norm sites
    sandwich_norm: Literal[True] = True

    # --- which layers are latent attention. None: every fourth, from layer 3
    # (as published). Indices past the depth are dropped, so a depth cut may
    # keep the list.
    full_attention_layers: list[int] | None = None
    # `o_proj(attn * sigmoid(gate_proj x))` on the MLA layers
    gated_attention: bool = True

    # --- the other layers' gated delta rule
    linear_num_key_heads: int = 32
    linear_num_value_heads: int = 64
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # the output gate is `linear_sigmoid_gate_scale * sigmoid(.)`
    linear_sigmoid_gate_scale: float = 2.0
    linear_attn_o_norm_eps: float = 1e-6
    delta_chunk_size: int = 64

    # `ZeroCenteredGatedNorm`: x / rms(x) * layernorm_gating_weight * sigmoid(w)
    layernorm_gating_weight: float = 2.0
    # every SwiGLU's gate is held under it, its linear branch inside +-it
    swiglu_limit: float | None = 10.0

    @field_validator("n_group", "topk_group")
    @classmethod
    def _one_group_is_none(cls, value):
        # published as 1 and 1: every expert in one group, which selects nothing
        return None if value == 1 else value

    @model_validator(mode="after")
    def _validate_hybrid(self) -> "GigaChat35Config":
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError("linear_num_value_heads must be a multiple of linear_num_key_heads")
        if self.n_routed_experts is None:
            raise ValueError("gigachat3_5 without routed experts is not implemented")
        if self.full_attention_layers is not None and sorted(set(self.full_attention_layers)) != list(
            self.full_attention_layers
        ):
            raise ValueError("full_attention_layers must be ascending layer indices")
        if not any(self.layer_kinds):
            raise ValueError("no layer of full_attention_layers lies inside num_hidden_layers")
        if self.first_k_dense_replace >= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace leaves no layer with experts")
        return self

    @property
    def attention_scale(self) -> float:
        if not self.use_mla_scaling_factor:
            return self.qk_head_dim ** -0.5
        return super().attention_scale

    @property
    def layer_kinds(self) -> list[bool]:
        """True = latent attention, False = the delta rule, a layer."""
        if self.full_attention_layers is None:
            return [i % 4 == 3 for i in range(self.num_hidden_layers)]
        full = set(self.full_attention_layers)
        return [i in full for i in range(self.num_hidden_layers)]

    @property
    def scan_plan(self) -> tuple[int, int, int]:
        """(looped prefix, period, scanned periods): the leading dense layers
        are looped, the layers after them scan in whole periods of the mixer
        pattern (every one of them has experts), and what is left at the end
        (fewer layers than a period) is looped again. Period 0: loop it all."""
        if not self.scan_layers:
            return self.num_hidden_layers, 0, 0
        prefix = self.first_k_dense_replace
        rest = self.layer_kinds[prefix:]  # never empty: `_validate_hybrid`
        period = next(
            p for p in range(1, len(rest) + 1)
            if all(rest[i] == rest[i % p] for i in range(len(rest)))
        )
        return prefix, period, len(rest) // period

    def cache_specs(self) -> tuple[LatentCacheSpec, RecurrentCacheSpec | None]:
        """The one declaration the latent pool, the slab, the dense buffers
        and their shardings derive from (`infer/cache.py`): one row a token
        for each MLA layer, a fixed slab a decode slot for each delta-rule
        layer; each layer addresses its own kind by its own index."""
        kinds = self.layer_kinds
        heads, key_heads = self.linear_num_value_heads, self.linear_num_key_heads
        linear = len(kinds) - sum(kinds)
        return (
            LatentCacheSpec(
                layers=sum(kinds), latent_dim=self.kv_lora_rank, rope_dim=self.qk_rope_head_dim
            ),
            None if not linear else RecurrentCacheSpec(
                layers=linear, heads=heads,
                key_dim=self.linear_key_head_dim, value_dim=self.linear_value_head_dim,
                conv_taps=self.linear_conv_kernel_dim - 1,
                conv_channels=2 * key_heads * self.linear_key_head_dim
                + heads * self.linear_value_head_dim,
            ),
        )

    @property
    def num_scanned_layers(self) -> int:
        _, period, periods = self.scan_plan
        return period * periods
