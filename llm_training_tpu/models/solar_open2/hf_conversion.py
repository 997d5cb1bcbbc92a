"""Solar-Open2 <-> HuggingFace: the CONFIG converts, a state dict does not.

The public `config.json` (upstage/Solar-Open2-250B) gives the shapes; no
list of its checkpoint's tensor names is available to this repo, so there is
no weight map here and none is invented: `params_from_hf` / `params_to_hf`
say so. Train from a seed, or add the map beside the names once they are
known.
"""

from __future__ import annotations

from typing import Any, Mapping

from llm_training_tpu.models.solar_open2.config import SolarOpen2Config

_NO_WEIGHT_MAP = (
    "solar_open2: no HuggingFace weight map (the checkpoint's tensor names "
    "are not known to this repo); the config converts, a state dict does not"
)


def params_from_hf(state_dict: Mapping[str, Any], config: SolarOpen2Config, leaf_fn: Any = None):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def params_to_hf(params: Mapping, config: SolarOpen2Config):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def config_to_hf(config: SolarOpen2Config, torch_dtype: str = "bfloat16") -> dict[str, Any]:
    return {
        "model_type": "solar_open2",
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "num_hidden_layers": config.num_hidden_layers,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "head_dim": config.head_dim,
        "partial_rotary_factor": config.partial_rotary_factor,
        "use_rope": config.use_rope,
        "rope_theta": config.rope_theta,
        "gqa_interval": config.gqa_interval,
        "gqa_layers": [i for i, gqa in enumerate(config.layer_kinds) if gqa],
        "use_gqa_gate": config.use_gqa_gate,
        "linear_attn_config": {
            "short_conv_kernel_size": config.linear_conv_kernel_dim,
            "head_dim": config.linear_head_dim,
            "num_heads": config.linear_num_heads,
            "num_kv_heads": None,
        },
        "kda_use_full_proj": config.kda_use_full_proj,
        "kda_allow_neg_eigval": config.kda_allow_neg_eigval,
        "n_routed_experts": config.n_routed_experts,
        "n_shared_experts": config.n_shared_experts,
        "num_experts_per_tok": config.num_experts_per_tok,
        "moe_intermediate_size": config.moe_intermediate_size,
        "norm_topk_prob": config.norm_topk_prob,
        "routed_scaling_factor": config.routed_scaling_factor,
        "first_k_dense_replace": config.first_k_dense_replace,
        "max_position_embeddings": config.max_position_embeddings,
        "initializer_range": config.initializer_range,
        "rms_norm_eps": config.rms_norm_eps,
        "pad_token_id": config.pad_token_id,
        "bos_token_id": config.bos_token_id,
        "eos_token_id": config.eos_token_id,
        "tie_word_embeddings": config.tie_word_embeddings,
        "torch_dtype": torch_dtype,
    }


def config_from_hf(hf_config: Any, **overrides: Any) -> SolarOpen2Config:
    get = (lambda k, d=None: hf_config.get(k, d)) if isinstance(hf_config, dict) else (
        lambda k, d=None: getattr(hf_config, k, d)
    )
    linear = get("linear_attn_config") or {}
    if linear.get("num_kv_heads") not in (None, linear.get("num_heads")):
        raise ValueError("linear_attn_config.num_kv_heads other than num_heads is not supported")
    published = dict(
        vocab_size=get("vocab_size"),
        hidden_size=get("hidden_size"),
        intermediate_size=get("intermediate_size"),
        num_hidden_layers=get("num_hidden_layers"),
        num_attention_heads=get("num_attention_heads"),
        num_key_value_heads=get("num_key_value_heads"),
        head_dim=get("head_dim"),
        partial_rotary_factor=get("partial_rotary_factor"),
        use_rope=get("use_rope"),
        rope_theta=get("rope_theta"),
        gqa_interval=get("gqa_interval"),
        gqa_layers=get("gqa_layers"),
        use_gqa_gate=get("use_gqa_gate"),
        linear_num_heads=linear.get("num_heads"),
        linear_head_dim=linear.get("head_dim"),
        linear_conv_kernel_dim=linear.get("short_conv_kernel_size"),
        kda_use_full_proj=get("kda_use_full_proj"),
        kda_allow_neg_eigval=get("kda_allow_neg_eigval"),
        n_routed_experts=get("n_routed_experts"),
        n_shared_experts=get("n_shared_experts"),
        num_experts_per_tok=get("num_experts_per_tok"),
        moe_intermediate_size=get("moe_intermediate_size"),
        norm_topk_prob=get("norm_topk_prob"),
        routed_scaling_factor=get("routed_scaling_factor"),
        first_k_dense_replace=get("first_k_dense_replace"),
        max_position_embeddings=get("max_position_embeddings"),
        initializer_range=get("initializer_range"),
        rms_norm_eps=get("rms_norm_eps"),
        pad_token_id=get("pad_token_id"),
        bos_token_id=get("bos_token_id"),
        eos_token_id=get("eos_token_id"),
        tie_word_embeddings=get("tie_word_embeddings"),
    )
    # a key the source leaves out keeps this family's default
    given = {k: v for k, v in published.items() if v is not None}
    return SolarOpen2Config(**{**given, **overrides})
