"""GigaChat 3.5 <-> HuggingFace: the CONFIG converts, a state dict does not.

The public `config.json` (ai-sage/GigaChat3.5-432B-A28B) gives the shapes
under the names this family's config keeps. The checkpoint's tensor names and
layouts (how q, k and v lie in the delta-rule block's fused projection, the
norm's parameter) are not known to this repo: no weight map is written from a
guess, and `params_from_hf` / `params_to_hf` say so. Train from a seed, or add
the map beside the names once they are known.
"""

from __future__ import annotations

from typing import Any, Mapping

from llm_training_tpu.models.gigachat35.config import GigaChat35Config

_NO_WEIGHT_MAP = (
    "gigachat3_5: no HuggingFace weight map (the checkpoint's tensor names "
    "are not known to this repo); the config converts, a state dict does not"
)
# the source's keys this family's config carries under the same name
_SOURCE_KEYS = (
    "vocab_size", "max_position_embeddings", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_hidden_layers", "num_attention_heads", "n_shared_experts",
    "n_routed_experts", "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "n_group", "topk_group",
    "num_experts_per_tok", "first_k_dense_replace", "norm_topk_prob", "rope_interleave",
    "rms_norm_eps", "rope_theta", "rope_scaling", "attention_bias", "layernorm_gating_weight",
    "gated_attention", "use_mla_scaling_factor", "full_attention_layers",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_num_key_heads", "linear_num_value_heads", "linear_sigmoid_gate_scale",
    "linear_attn_o_norm_eps", "swiglu_limit", "tie_word_embeddings", "num_nextn_predict_layers",
    "initializer_range", "pad_token_id", "bos_token_id", "eos_token_id",
)
# keys whose one implemented value is the published one
_FIXED = {
    "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "use_shared_expert_sigmoid": False, "nextn_is_sparse": False, "hidden_act": "silu",
}


def params_from_hf(state_dict: Mapping[str, Any], config: GigaChat35Config, leaf_fn: Any = None):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def params_to_hf(params: Mapping, config: GigaChat35Config):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def config_to_hf(config: GigaChat35Config, torch_dtype: str = "bfloat16") -> dict[str, Any]:
    out = {key: getattr(config, key) for key in _SOURCE_KEYS}
    out["full_attention_layers"] = [i for i, full in enumerate(config.layer_kinds) if full]
    return {"model_type": "gigachat3_5", **out, **_FIXED, "torch_dtype": torch_dtype}


def config_from_hf(hf_config: Mapping[str, Any] | Any, **overrides: Any) -> GigaChat35Config:
    get = hf_config.get if isinstance(hf_config, Mapping) else lambda k, d=None: getattr(hf_config, k, d)
    for key, value in _FIXED.items():
        if get(key) not in (None, value):
            raise NotImplementedError(f"gigachat3_5 with {key}={get(key)!r} (implemented: {value!r})")
    kwargs = {key: get(key) for key in _SOURCE_KEYS if get(key) is not None}
    kwargs.update(overrides)
    return GigaChat35Config(**kwargs)
