"""AFMoE <-> HuggingFace: the CONFIG converts, a state dict does not.

The public `config.json` (arcee-ai/Trinity-Mini) gives the shapes under the
names this family's config keeps. The checkpoint's tensor names are known to
this repo only from memory of the family's modelling code, not from a list:
no weight map is written from that, and `params_from_hf` / `params_to_hf` say
so. Train from a seed, or add the map beside the names once they are known.
"""

from __future__ import annotations

from typing import Any, Mapping

from llm_training_tpu.models.afmoe.config import AfmoeConfig

_NO_WEIGHT_MAP = (
    "afmoe: no HuggingFace weight map (the checkpoint's tensor names are not "
    "known to this repo for certain); the config converts, a state dict does not"
)
# the source's keys this family's config carries under the same name
_SOURCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "hidden_act", "max_position_embeddings", "rms_norm_eps", "rope_theta",
    "rope_scaling", "tie_word_embeddings", "mup_enabled", "sliding_window",
    "global_attn_every_n_layers", "layer_types", "num_experts", "num_experts_per_tok",
    "num_shared_experts", "score_func", "route_norm", "route_scale", "num_expert_groups",
    "num_limited_groups", "load_balance_coeff",
)


def params_from_hf(state_dict: Mapping[str, Any], config: AfmoeConfig, leaf_fn: Any = None):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def params_to_hf(params: Mapping, config: AfmoeConfig):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def config_to_hf(config: AfmoeConfig, torch_dtype: str = "bfloat16") -> dict[str, Any]:
    out = {key: getattr(config, key) for key in _SOURCE_KEYS}
    if out["layer_types"] is None:
        out["layer_types"] = [
            "sliding_attention" if window else "full_attention"
            for window, _ in config.layer_kinds
        ]
    # the source gives the groups under both pairs of names
    return {
        "model_type": "afmoe", **out,
        "n_group": config.num_expert_groups, "topk_group": config.num_limited_groups,
        "torch_dtype": torch_dtype,
    }


def config_from_hf(hf_config: Mapping[str, Any] | Any, **overrides: Any) -> AfmoeConfig:
    get = hf_config.get if isinstance(hf_config, Mapping) else lambda k, d=None: getattr(hf_config, k, d)
    kwargs = {key: get(key) for key in _SOURCE_KEYS if get(key) is not None}
    if get("rope_scaling") is not None:
        raise ValueError("afmoe with rope_scaling is not implemented (published: null)")
    for key, ours in (("n_group", "num_expert_groups"), ("topk_group", "num_limited_groups")):
        if get(key) is not None and get(key) != kwargs.get(ours, 1):
            raise ValueError(f"afmoe: {key}={get(key)} disagrees with {ours}")
    kwargs.update(overrides)
    return AfmoeConfig(**kwargs)
