"""Llama model config.

Capability parity: reference `models/llama/llama_config.py:7-32` (all HF
Llama hparams + gradient-checkpointing knobs), with TPU-native additions:
`scan_layers` (compile-time: one traced layer scanned over depth) and
`attention_impl` (xla reference path vs pallas flash kernel).
"""

from __future__ import annotations

from typing import Any, Literal

from pydantic import model_validator

from llm_training_tpu.models.base import BaseModelConfig, KVCacheSpec
from llm_training_tpu.ops.rope_utils import RoPEConfig


class LlamaConfig(BaseModelConfig):
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int | None = None  # defaults to hidden_size // num_attention_heads
    max_position_embeddings: int = 4096
    initializer_range: float = 0.02
    rms_norm_eps: float = 1e-6
    pad_token_id: int | None = None
    bos_token_id: int | None = 1
    # a list on several HF families (Llama-3.x instruct, GLM)
    eos_token_id: int | list[int] | None = 2
    tie_word_embeddings: bool = False
    rope_theta: float = 10000.0
    attention_bias: bool = False
    # Qwen2-style asymmetry: q/k/v carry biases, o_proj does not.
    # None = same as attention_bias
    attention_out_bias: bool | None = None
    attention_dropout: float = 0.0
    mlp_bias: bool = False
    rope_scaling: dict[str, Any] | None = None
    # Mistral/Qwen2-style local attention (None = full causal); consumed by
    # LlamaAttention via ops.dot_product_attention's sliding_window arg
    sliding_window: int | None = None
    # OLMo-3-style per-layer 'sliding_attention' / 'full_attention' pattern;
    # sliding layers use UNSCALED default rope, full layers the configured
    # rope (+ rope_scaling). None = sliding_window applies to every layer.
    layer_types: list[str] | None = None
    # OLMo-3: sliding layers rotate with the UNSCALED default rope tables
    # while full layers use rope_scaling. Ministral shares the layer_types
    # pattern but rotates every layer with ONE table, so this stays False
    # for it.
    dual_local_rope: bool = False
    # Qwen3-style per-head RMSNorm on q and k (over head_dim, before RoPE);
    # scope 'full' is the OLMo-2/OLMoE variant (one norm over the whole
    # projected width, applied before the head reshape)
    qk_norm: bool = False
    qk_norm_scope: Literal["head", "full"] = "head"
    # HunYuan applies the per-head norms AFTER rotary; everyone else before
    qk_norm_position: Literal["pre_rope", "post_rope"] = "pre_rope"
    # OLMo/OLMoE: clamp q/k/v activations to [-clip_qkv, clip_qkv] after the
    # projections (and qk-norm), before the head reshape
    clip_qkv: float | None = None
    # 'pre' = Llama pre-norm blocks; 'post' = OLMo-2 reordering
    # (x + norm(block(x)) with NO input norms); 'parallel' = Cohere/Phi's
    # single input norm feeding attention AND mlp, summed into one residual
    # add; 'parallel2' = GPT-NeoX's TWO norms (input_layernorm ->
    # attention, post_attention_layernorm -> mlp) over the SAME block
    # input, one residual join; 'sandwich' = GLM-4's four norms (input
    # norm AND output norm around both the attention and the mlp)
    norm_scheme: Literal["pre", "post", "parallel", "parallel2", "sandwich"] = "pre"
    # exact (erf) vs tanh-approximate gelu for mlp_type='gelu'
    # (Starcoder2/Phi use tanh; GPT-NeoX's 'gelu' is exact)
    gelu_approximate: bool = True
    # GPT-NeoX checkpoint naming (gpt_neox. prefix, fused interleaved
    # query_key_value, embed_in/embed_out) — needed explicitly for the
    # use_parallel_residual=False variant, whose pre-norm graph would
    # otherwise be indistinguishable from Starcoder2 naming
    neox_naming: bool = False
    # Starcoder2: biased LayerNorm instead of RMSNorm (rms_norm_eps doubles
    # as its epsilon), and a non-gated c_fc -> gelu_tanh -> c_proj MLP.
    # 'layernorm_nobias' is Cohere's mean-centered weight-only norm;
    # 'layernorm1p' is Nemotron's zero-centered (1 + w) biased LayerNorm.
    # 'relu2' is Nemotron's non-gated up_proj -> relu^2 -> down_proj MLP.
    # 'xielu' is Apertus' non-gated up -> xIELU -> down MLP with two
    # learnable activation scalars per layer.
    # 'layernorm_nonparam' is OLMo-1's fully non-parametric F.layer_norm
    # (no weight, no bias — zero norm keys in the checkpoint)
    norm_type: Literal[
        "rmsnorm", "layernorm", "layernorm_nobias", "layernorm1p",
        "layernorm_nonparam",
    ] = "rmsnorm"
    mlp_type: Literal["swiglu", "gelu", "relu2", "xielu"] = "swiglu"
    # Cohere/GLM/Ernie: interleaved (GPT-J) rope pairing; Cohere also has a
    # multiplicative logit scale. fused_gate_up marks GLM-style checkpoints
    # whose HF files store gate|up as ONE fused tensor (split/re-fused at
    # the conversion boundary; the module always keeps them separate).
    rope_interleaved: bool = False
    logit_scale: float | None = None
    fused_gate_up: bool = False
    # GPT-2: learned absolute position embeddings (wpe) instead of rotary
    position_embedding_type: Literal["rope", "learned"] = "rope"
    # SmolLM3 NoPE: per-layer rope flags, HF spelling (1 = rotate, 0 = NoPE)
    no_rope_layers: list[int] | None = None
    # Phi-1/1.5/2: rotate only the first fraction of each head's dims
    # (rope tables span int(partial_rotary_factor * head_dim)), and the
    # untied lm_head carries a bias
    partial_rotary_factor: float = 1.0
    lm_head_bias: bool = False
    # Granite (IBM) scalar multipliers; the defaults are the Llama identity
    # values. attention_multiplier None = the standard 1/sqrt(head_dim).
    embedding_multiplier: float = 1.0
    attention_multiplier: float | None = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # --- mixture of experts (Mixtral / Qwen2-MoE / Qwen3-MoE); None = dense
    num_experts: int | None = None
    num_experts_per_tok: int = 2
    moe_intermediate_size: int | None = None
    norm_topk_prob: bool = True
    shared_expert_intermediate_size: int | None = None  # Qwen2-MoE
    router_aux_loss_coef: float = 0.001
    # conversion/export naming: 'qwen' (mlp.experts.{i}.gate_proj),
    # 'mixtral' (block_sparse_moe.experts.{i}.w1/w3/w2), or 'granite'
    # (block_sparse_moe.input_linear [E, 2I, H] fused gate/up stacks +
    # router.layer)
    moe_style: Literal["qwen", "mixtral", "granite"] = "qwen"
    # router selection: plain softmax top-k, or Phi-3.5-MoE's SparseMixer
    # (sequential argmax picks weighted by a band-masked softmax —
    # models/moe.py:sparsemixer_topk; requires top_k=2)
    moe_router_impl: Literal["softmax", "sparsemixer"] = "softmax"
    router_jitter_eps: float = 0.01  # SparseMixer masking band half-width
    # qwen2-moe gates the shared expert with a per-token sigmoid;
    # granitemoeshared runs it always-on (no gate parameter)
    shared_expert_gated: bool = True
    # 'ragged' = dropless grouped matmul (lax.ragged_dot, the TPU training
    # path); 'dense' = every expert on every token (exact, for parity
    # tests); 'bucketed' = fixed per-expert capacity buckets + ONE dense
    # batched matmul — trades token drops under imbalance (surfaced by the
    # ep_dropped_rows metric) for fully-dense MXU work where ragged_dot's
    # lowering underperforms (no chip measurement yet: PERF.md section 7)
    moe_impl: Literal["auto", "dense", "ragged", "bucketed"] = "auto"
    # per-rank buffer slack for the expert-parallel dispatch: capacity =
    # ceil(T*K/ep * factor) rows (clamped to T*K); routing beyond it is
    # dropped, so raise this if EP training shows imbalance-driven drops
    ep_capacity_factor: float = 2.0
    # per-EXPERT bucket slack for moe_impl='bucketed': capacity =
    # ceil(T*K/E * factor) rows per expert (clamped to T*K); 1.0 = exactly
    # balanced, larger absorbs imbalance at padding cost
    moe_capacity_factor: float = 1.25

    enable_gradient_checkpointing: bool = False
    recompute_granularity: Literal["full", "selective"] = "full"

    # TPU-native knobs
    scan_layers: bool = True
    attention_impl: Literal["auto", "xla", "pallas"] = "auto"
    # context parallelism: shard the sequence axis and run ring attention
    # over it (requires a mesh with sequence_parallel_size > 1); goes beyond
    # the reference, which reaches long context via TP+SP only (SURVEY.md §5.7)
    ring_attention: bool = False
    # GPipe pipeline parallelism (models/pipeline.py): split the scanned
    # stack into this many stages over the 'pipe' mesh axis (mesh
    # pipeline_parallel_size must match). Beyond the reference, which has
    # no PP. Changes the layer-stack param layout to [S, L/S, ...]
    pipeline_stages: int = 1
    # microbatches per step (defaults to pipeline_stages); bubble fraction
    # is (S-1)/(microbatches+S-1)
    pipeline_microbatches: int | None = None

    @model_validator(mode="after")
    def _validate(self) -> "LlamaConfig":
        if self.num_attention_heads % self.num_key_value_heads != 0:
            raise ValueError(
                f"num_attention_heads ({self.num_attention_heads}) must be divisible "
                f"by num_key_value_heads ({self.num_key_value_heads})"
            )
        if self.attention_out_bias is None:
            self.attention_out_bias = self.attention_bias
        if self.attention_dropout != 0.0:
            # fail loudly rather than silently training without the dropout a
            # user (or an HF config) asked for
            raise ValueError("attention_dropout is not supported; set it to 0.0")
        if self.num_experts is not None:
            if self.mlp_type != "swiglu":
                raise ValueError("MoE layers only support the swiglu mlp_type")
            if (
                self.moe_style == "granite"
                and self.shared_expert_intermediate_size
                and self.shared_expert_gated
            ):
                # the granite conversion layout has no gate tensor; a gated
                # shared expert would silently drop its weight on export
                raise ValueError(
                    "moe_style='granite' shared experts are always-on; set "
                    "shared_expert_gated=False (granitemoeshared has no "
                    "shared gate parameter)"
                )
            if self.moe_intermediate_size is None:
                raise ValueError("num_experts requires moe_intermediate_size")
            if not 0 < self.num_experts_per_tok <= self.num_experts:
                raise ValueError(
                    f"num_experts_per_tok ({self.num_experts_per_tok}) must be "
                    f"in [1, num_experts={self.num_experts}]"
                )
        if self.layer_types is not None:
            if len(self.layer_types) != self.num_hidden_layers:
                raise ValueError(
                    f"layer_types has {len(self.layer_types)} entries for "
                    f"{self.num_hidden_layers} layers"
                )
            bad = set(self.layer_types) - {"sliding_attention", "full_attention"}
            if bad:
                raise ValueError(
                    f"unknown layer_types entries {sorted(bad)}; expected "
                    "'sliding_attention' or 'full_attention'"
                )
            if "sliding_attention" in self.layer_types and not self.sliding_window:
                raise ValueError("sliding layer_types require sliding_window")
            # per-layer windows/ropes break the uniform scanned body
            if self.scan_layers and "scan_layers" in self.model_fields_set:
                raise ValueError(
                    "layer_types requires looped layers; set scan_layers=False"
                )
            self.scan_layers = False
            # back-compat: before dual_local_rope existed, layer_types +
            # rope_scaling implied OLMo-3 dual tables; preserve that for
            # hand-written configs carrying the OLMo-3 signature (post-norm)
            # unless the flag was set explicitly
            if (
                "dual_local_rope" not in self.model_fields_set
                and self.rope_scaling
                and self.norm_scheme == "post"
            ):
                self.dual_local_rope = True
        if self.no_rope_layers is not None:
            if self.position_embedding_type == "learned":
                raise ValueError(
                    "no_rope_layers is meaningless with learned positions"
                )
            if len(self.no_rope_layers) != self.num_hidden_layers:
                raise ValueError(
                    f"no_rope_layers has {len(self.no_rope_layers)} entries "
                    f"for {self.num_hidden_layers} layers"
                )
            # per-layer rope on/off breaks the uniform scanned body
            if self.scan_layers and "scan_layers" in self.model_fields_set:
                raise ValueError(
                    "no_rope_layers requires looped layers; set "
                    "scan_layers=False"
                )
            self.scan_layers = False
        if self.pipeline_stages > 1:
            if not self.scan_layers:
                raise ValueError(
                    "pipeline_stages > 1 requires scan_layers=True (stages "
                    "are a leading axis over the scanned stack)"
                )
            if self.num_hidden_layers % self.pipeline_stages != 0:
                raise ValueError(
                    f"num_hidden_layers {self.num_hidden_layers} must split "
                    f"evenly over pipeline_stages {self.pipeline_stages}"
                )
            if self.position_embedding_type == "learned":
                raise ValueError(
                    "pipeline_stages > 1 requires rotary positions"
                )
            if self.ring_attention:
                raise ValueError(
                    "pipeline_stages > 1 does not compose with "
                    "ring_attention (the ring's shard_map cannot sit under "
                    "the stage vmap); shard long sequences with "
                    "tensor/sequence-parallel attention instead"
                )
        self.rope_config  # construct to trigger RoPEConfig validation
        return self

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    def cache_specs(self) -> tuple[KVCacheSpec, None]:
        """Every layer caches keys and values (`BaseModelConfig.cache_specs`)."""
        return (
            KVCacheSpec(self.num_hidden_layers, self.num_key_value_heads, self.resolved_head_dim),
            None,
        )

    @property
    def rope_config(self) -> RoPEConfig:
        from llm_training_tpu.ops.rope_utils import rope_config_from_hf

        return rope_config_from_hf(
            self.rope_scaling, self.rope_theta,
            # Phi: tables span only the rotated fraction of each head
            int(self.resolved_head_dim * self.partial_rotary_factor),
            self.max_position_embeddings,
        )

    @property
    def local_rope_config(self) -> RoPEConfig:
        """OLMo-3 sliding layers: same theta, NEVER scaled."""
        from llm_training_tpu.ops.rope_utils import rope_config_from_hf

        return rope_config_from_hf(
            None, self.rope_theta,
            int(self.resolved_head_dim * self.partial_rotary_factor),
            self.max_position_embeddings,
        )

    def layer_sliding_window(self, layer_idx: int) -> int | None:
        if self.layer_types is None:
            return self.sliding_window
        if self.layer_types[layer_idx] == "sliding_attention":
            return self.sliding_window
        return None
