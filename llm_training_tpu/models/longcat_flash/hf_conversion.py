"""LongCat-Flash <-> HuggingFace: the CONFIG converts, a state dict does not.

The public `config.json` (meituan-longcat/LongCat-Flash-Omni) gives the
language model's shapes under the names this family's config keeps; no list
of its checkpoint's tensor names is available to this repo, so there is no
weight map here and none is invented: `params_from_hf` / `params_to_hf` say
so. The Omni release's audio and vision encoders and codec decoder are not
modelled. Train from a seed, or add the map beside the names once they are
known.
"""

from __future__ import annotations

from typing import Any, Mapping

from llm_training_tpu.models.longcat_flash.config import LongcatFlashConfig

_NO_WEIGHT_MAP = (
    "longcat_flash: no HuggingFace weight map (the checkpoint's tensor names "
    "are not known to this repo); the config converts, a state dict does not"
)
# the source's keys this family's config carries under the same name
_SOURCE_KEYS = (
    "attention_bias", "vocab_size", "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_layers", "num_attention_heads", "kv_lora_rank", "q_lora_rank", "qk_rope_head_dim",
    "v_head_dim", "qk_nope_head_dim", "mla_scale_q_lora", "mla_scale_kv_lora",
    "routed_scaling_factor", "n_routed_experts", "max_position_embeddings", "rms_norm_eps",
    "rope_theta", "attention_method", "zero_expert_num", "zero_expert_type", "moe_topk",
)


def params_from_hf(state_dict: Mapping[str, Any], config: LongcatFlashConfig, leaf_fn: Any = None):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def params_to_hf(params: Mapping, config: LongcatFlashConfig):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def config_to_hf(config: LongcatFlashConfig, torch_dtype: str = "bfloat16") -> dict[str, Any]:
    return {
        "model_type": "longcat_flash",
        **{key: getattr(config, key) for key in _SOURCE_KEYS},
        "torch_dtype": torch_dtype,
    }


def config_from_hf(hf_config: Mapping[str, Any] | Any, **overrides: Any) -> LongcatFlashConfig:
    get = hf_config.get if isinstance(hf_config, Mapping) else lambda k, d=None: getattr(hf_config, k, d)
    kwargs = {key: get(key) for key in _SOURCE_KEYS if get(key) is not None}
    kwargs.update(overrides)
    return LongcatFlashConfig(**kwargs)
