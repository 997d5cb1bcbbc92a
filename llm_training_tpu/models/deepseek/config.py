"""DeepSeek V2/V3 model config.

Family member beyond the reference's named models (the reference reaches
DeepSeek only through `HFCausalLM`'s torch wrapping,
`src/llm_training/models/hf_causal_lm/hf_causal_lm.py:22`); here the MLA +
grouped-MoE computation graph is native. `version=2` mirrors HF
`DeepseekV2Config` (softmax routing, greedy / group-limited-greedy top-k);
`version=3` mirrors `DeepseekV3Config` (sigmoid routing with the noaux
e_score_correction_bias and top-2-sum group selection).

`model_type: pangu_ultra_moe` (openPangu-Ultra-MoE) is `version=3` without
expert groups plus two things: `sandwich_norm` (a norm after the attention
and after the MLP as well as before each) and `num_nextn_predict_layers`
(the multi-token-prediction module, `model.py:MTPModule`).

The stack decodes (docs/inference.md, docs/serving.md): every layer's MLA
block leaves ONE latent row a token, declared by `cache_specs()`.
"""

from __future__ import annotations

from typing import Any, ClassVar, Literal

from pydantic import model_validator

from llm_training_tpu.models.base import BaseModelConfig, LatentCacheSpec


class DeepseekConfig(BaseModelConfig):
    # the multi-token-prediction modules a stack of this config chains at most
    mtp_modules_max: ClassVar[int] = 1

    version: Literal[2, 3] = 3

    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432  # dense layers (and the MoE-free prefix)
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    max_position_embeddings: int = 4096
    initializer_range: float = 0.02
    rms_norm_eps: float = 1e-6
    pad_token_id: int | None = None
    bos_token_id: int | None = 0
    eos_token_id: int | None = 1
    tie_word_embeddings: bool = False
    rope_theta: float = 10000.0
    rope_scaling: dict[str, Any] | None = None
    # HF checkpoints store rope weights interleaved (complex-pair layout);
    # version=2 always rotates this way, version=3 carries the flag
    rope_interleave: bool = True
    attention_bias: bool = False
    attention_dropout: float = 0.0

    # --- MLA (multi-head latent attention) dims
    q_lora_rank: int | None = None  # None = full-rank q_proj (V2-Lite)
    kv_lora_rank: int = 512
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128

    # --- MoE; n_routed_experts None = every layer dense
    n_routed_experts: int | None = None
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int | None = None
    first_k_dense_replace: int = 0  # layers [0, k) use the dense MLP
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    n_group: int | None = None
    topk_group: int | None = None
    # version=2 selection: 'greedy' (V2-Lite) or 'group_limited_greedy';
    # version=3 always uses the noaux top-2-sum group selection
    topk_method: Literal["greedy", "group_limited_greedy"] = "greedy"
    # 'ragged' = dropless grouped matmul; 'dense' = exact every-expert path
    moe_impl: Literal["auto", "dense", "ragged"] = "auto"
    # an expert-parallel share: this many of the routed experts, from
    # `experts_first` on, are held (and computed) here; the router still
    # scores all `n_routed_experts`, and the shared experts are every
    # chip's own. None = all.
    experts_held: int | None = None
    experts_first: int = 0
    # per-rank buffer slack for the expert-parallel dispatch: capacity =
    # ceil(T*K/ep * factor) rows (clamped to T*K); routing beyond it is
    # dropped, so raise this if EP training shows imbalance-driven drops
    ep_capacity_factor: float = 2.0

    # pangu_ultra_moe: `a = N(MLA(N x))`, `h = x + a`, `y = h + N(MLP(N h))`,
    # four norms a layer (`pre_mlp_layernorm`, `post_mlp_layernorm` beside
    # the two every layer has)
    sandwich_norm: bool = False
    # multi-token-prediction modules after the stack (0 or 1): a training
    # loss of their own (`lms/clm.py`); a forward without `return_mtp` never
    # runs them, and the cache holds no row for them
    num_nextn_predict_layers: int = 0

    enable_gradient_checkpointing: bool = False
    recompute_granularity: Literal["full", "selective"] = "full"
    # the dense prefix is looped; the uniform MoE suffix (everything from
    # first_k_dense_replace on) scans, keeping compile time ~flat in depth
    scan_layers: bool = True
    attention_impl: Literal["auto", "xla", "pallas"] = "auto"

    @model_validator(mode="after")
    def _validate(self) -> "DeepseekConfig":
        if self.attention_dropout != 0.0:
            raise ValueError("attention_dropout is not supported; set it to 0.0")
        if self.n_routed_experts is not None:
            if self.moe_intermediate_size is None:
                raise ValueError("n_routed_experts requires moe_intermediate_size")
            if self.n_group is not None:
                if self.n_routed_experts % self.n_group:
                    raise ValueError("n_routed_experts must divide into n_group groups")
                if self.topk_group is None:
                    raise ValueError("n_group requires topk_group")
            held = self.num_experts_held
            if not 0 <= self.experts_first <= self.n_routed_experts - held:
                raise ValueError(
                    f"experts {self.experts_first}..{self.experts_first + held} are not "
                    f"among the {self.n_routed_experts} routed experts"
                )
        elif self.experts_held is not None:
            raise ValueError("experts_held needs n_routed_experts")
        if not 0 <= self.num_nextn_predict_layers <= self.mtp_modules_max:
            raise ValueError(
                f"num_nextn_predict_layers: {self.mtp_modules_max} multi-token-prediction "
                f"module(s) are implemented, not {self.num_nextn_predict_layers}"
            )
        self.rope_config  # trigger validation
        return self

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def rope_config(self):
        from llm_training_tpu.ops.rope_utils import rope_config_from_hf

        return rope_config_from_hf(
            self.rope_scaling, self.rope_theta, self.qk_rope_head_dim,
            self.max_position_embeddings,
        )

    @property
    def attention_scale(self) -> float:
        """1/sqrt(qk_head_dim), squared-mscale-corrected under DeepSeek yarn
        (HF DeepseekV2/V3Attention.__init__)."""
        import math

        scale = self.qk_head_dim ** -0.5
        if self.rope_scaling:
            mscale_all_dim = self.rope_scaling.get("mscale_all_dim", 0)
            factor = self.rope_scaling.get("factor")
            if mscale_all_dim and factor and factor > 1:
                mscale = 0.1 * mscale_all_dim * math.log(factor) + 1.0
                scale = scale * mscale * mscale
        return scale

    @property
    def num_experts_held(self) -> int | None:
        return self.n_routed_experts if self.experts_held is None else self.experts_held

    @property
    def counts_expert_assignments(self) -> bool:
        """A share of the experts counts where its tokens' choices went
        (`CausalLMOutput.moe_assignments`; `serve/engine.py` reads this)."""
        return self.experts_held is not None

    def cache_specs(self) -> tuple[LatentCacheSpec, None]:
        """The one declaration the latent pool, the dense latent buffer and
        their shardings derive from (`infer/cache.py`): one row a token for
        each layer's MLA block, dense prefix and MoE suffix alike."""
        return (
            LatentCacheSpec(
                layers=self.num_hidden_layers, latent_dim=self.kv_lora_rank,
                rope_dim=self.qk_rope_head_dim,
            ),
            None,
        )

    def layer_is_moe(self, layer_idx: int) -> bool:
        return (
            self.n_routed_experts is not None
            and layer_idx >= self.first_k_dense_replace
        )

    @property
    def num_scanned_layers(self) -> int:
        """Depth of the scanned uniform MoE suffix (0 = loop everything).
        Dense-only configs loop: their uniform stack could scan too, but the
        graph is Llama-shaped and tiny test configs are the only users."""
        if not self.scan_layers or self.n_routed_experts is None:
            return 0
        return self.num_hidden_layers - self.first_k_dense_replace
