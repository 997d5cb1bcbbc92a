"""Olmo-Hybrid <-> HuggingFace: the CONFIG converts, a state dict does not.

The public `config.json` (allenai/Olmo-Hybrid-7B) gives the shapes under the
names this family's config keeps. The checkpoint's tensor names (whether the
three convolutions are fused, what the gates' projections are called) are
not known to this repo: no weight map is written from a guess, and
`params_from_hf` / `params_to_hf` say so. Train from a seed, or add the map
beside the names once they are known.
"""

from __future__ import annotations

from typing import Any, Mapping

from llm_training_tpu.models.olmo_hybrid.config import FULL, LINEAR, OlmoHybridConfig

_NO_WEIGHT_MAP = (
    "olmo_hybrid: no HuggingFace weight map (the checkpoint's tensor names "
    "are not known to this repo); the config converts, a state dict does not"
)
# the source's keys this family's config carries under the same name
_SOURCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "hidden_act",
    "max_position_embeddings", "attention_bias", "rms_norm_eps", "tie_word_embeddings",
    "initializer_range", "layer_types", "linear_num_key_heads", "linear_num_value_heads",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    "linear_allow_neg_eigval", "pad_token_id", "bos_token_id", "eos_token_id",
)


def params_from_hf(state_dict: Mapping[str, Any], config: OlmoHybridConfig, leaf_fn: Any = None):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def params_to_hf(params: Mapping, config: OlmoHybridConfig):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def config_to_hf(config: OlmoHybridConfig, torch_dtype: str = "bfloat16") -> dict[str, Any]:
    out = {key: getattr(config, key) for key in _SOURCE_KEYS}
    out["layer_types"] = [FULL if full else LINEAR for full in config.layer_kinds]
    return {
        "model_type": "olmo_hybrid", **out,
        "rope_parameters": {"rope_theta": config.rope_theta}, "torch_dtype": torch_dtype,
    }


def config_from_hf(hf_config: Mapping[str, Any] | Any, **overrides: Any) -> OlmoHybridConfig:
    get = hf_config.get if isinstance(hf_config, Mapping) else lambda k, d=None: getattr(hf_config, k, d)
    kwargs = {key: get(key) for key in _SOURCE_KEYS if get(key) is not None}
    rope = get("rope_parameters") or {}
    theta = rope.get("rope_theta") if isinstance(rope, Mapping) else getattr(rope, "rope_theta", None)
    if theta is not None:
        kwargs["rope_theta"] = theta  # refused by the config: published null
    kwargs.update(overrides)
    return OlmoHybridConfig(**kwargs)
