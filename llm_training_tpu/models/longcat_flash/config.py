"""LongCat-Flash language model config (`model_type: longcat_flash`; the
text stack of https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json,
whose audio and vision encoders and codec decoder are outside this repo:
token ids in, logits out).

The field names are the source's own (`ffn_hidden_size`, `num_layers`,
`moe_topk`, ...). `num_layers` counts DOUBLE layers: two latent-attention
(MLA) blocks, two dense SwiGLU FFNs and one shortcut-connected MoE each
(`model.py`). The router scores `n_routed_experts + zero_expert_num`
outputs; the last `zero_expert_num` are zero-compute experts (the identity).

What the source does not give is this family's assumption, listed in
docs/models.md: the order of the sub-blocks, softmax scores with the
correction bias for the choice only and no renormalisation, the values of
the two MLA scale factors behind `mla_scale_q_lora` / `mla_scale_kv_lora`,
interleaved rotary pairs. Interleaved rotary and the untied head are
constants of the family (the source is untied), not fields.
"""

from __future__ import annotations

from typing import Literal

from pydantic import model_validator

from llm_training_tpu.models.base import BaseModelConfig, LatentCacheSpec


class LongcatFlashConfig(BaseModelConfig):
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28  # double layers
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    attention_method: Literal["MLA"] = "MLA"
    attention_bias: bool = False
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    initializer_range: float = 0.02
    pad_token_id: int | None = None
    bos_token_id: int | None = None
    eos_token_id: int | list[int] | None = None

    # --- experts: the router has n_routed_experts + zero_expert_num outputs
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: Literal["identity"] = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    # an expert-parallel share: this many of the real experts, from
    # `experts_first` on, are held (and computed) here; the router still
    # scores all of them, and the zero-compute experts are every chip's own.
    # None = all.
    experts_held: int | None = None
    experts_first: int = 0
    moe_impl: Literal["auto", "dense", "ragged"] = "auto"

    enable_gradient_checkpointing: bool = False
    recompute_granularity: Literal["full", "selective"] = "full"
    scan_layers: bool = True
    # the kernel of the forward WITHOUT a cache (training, evaluation). A call
    # with a cache picks by the backend, as every family's does
    # (`models/cache.py:LayerCache`): this field does not reach it
    attention_impl: Literal["auto", "xla", "pallas"] = "auto"

    @model_validator(mode="after")
    def _validate(self) -> "LongcatFlashConfig":
        if self.attention_bias:
            raise ValueError("longcat_flash with attention_bias=true is not implemented")
        held = self.num_experts_held
        if not 0 <= self.experts_first <= self.n_routed_experts - held:
            raise ValueError(
                f"experts {self.experts_first}..{self.experts_first + held} are not "
                f"among the {self.n_routed_experts} real experts"
            )
        return self

    # the name the time estimator and the shard audit read
    @property
    def num_hidden_layers(self) -> int:
        return self.num_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def router_width(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def num_experts_held(self) -> int:
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    @property
    def counts_expert_assignments(self) -> bool:
        """A share of the experts counts where its tokens' choices went
        (`CausalLMOutput.moe_assignments`; `serve/engine.py` reads this)."""
        return self.experts_held is not None

    @property
    def q_scale(self) -> float:
        """`s_q`: what `mla_scale_q_lora` multiplies the up-projected query by."""
        return (self.hidden_size / self.q_lora_rank) ** 0.5 if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        """`s_kv`: what `mla_scale_kv_lora` multiplies the normalised latent by."""
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 if self.mla_scale_kv_lora else 1.0

    def cache_specs(self) -> tuple[LatentCacheSpec, None]:
        """The one declaration the latent pool, the dense latent buffer and
        their shardings derive from (`infer/cache.py`): one row a token for
        each of the two MLA blocks of a double layer."""
        return (
            LatentCacheSpec(
                layers=2 * self.num_layers, latent_dim=self.kv_lora_rank,
                rope_dim=self.qk_rope_head_dim,
            ),
            None,
        )
