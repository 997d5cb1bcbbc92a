"""Phi-4-mini-flash <-> HuggingFace: the CONFIG converts, a state dict does not.

The public `config.json` (microsoft/Phi-4-mini-flash-reasoning) gives the
shapes under the names this family's config keeps. The checkpoint's tensor
names and layouts (the fused `Wqkv` and `fc1`, how a pair of heads lies in
them) are not known to this repo: no weight map is written from a guess, and
`params_from_hf` / `params_to_hf` say so. Train from a seed, or add the map
beside the names once they are known.
"""

from __future__ import annotations

from typing import Any, Mapping

from llm_training_tpu.models.phi4flash.config import Phi4FlashConfig

_NO_WEIGHT_MAP = (
    "phi4flash: no HuggingFace weight map (the checkpoint's tensor names "
    "are not known to this repo); the config converts, a state dict does not"
)
# the source's keys this family's config carries under the same name
_SOURCE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "hidden_act", "max_position_embeddings",
    "sliding_window", "mb_per_layer", "layer_norm_eps", "tie_word_embeddings", "mlp_bias",
    "lm_head_bias", "initializer_range", "pad_token_id", "bos_token_id", "eos_token_id",
)


def params_from_hf(state_dict: Mapping[str, Any], config: Phi4FlashConfig, leaf_fn: Any = None):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def params_to_hf(params: Mapping, config: Phi4FlashConfig):
    raise NotImplementedError(_NO_WEIGHT_MAP)


def config_to_hf(config: Phi4FlashConfig, torch_dtype: str = "bfloat16") -> dict[str, Any]:
    out = {key: getattr(config, key) for key in _SOURCE_KEYS}
    return {"model_type": "phi4flash", **out, "torch_dtype": torch_dtype}


def config_from_hf(hf_config: Mapping[str, Any] | Any, **overrides: Any) -> Phi4FlashConfig:
    get = hf_config.get if isinstance(hf_config, Mapping) else lambda k, d=None: getattr(hf_config, k, d)
    kwargs = {key: get(key) for key in _SOURCE_KEYS if get(key) is not None}
    kwargs.update(overrides)
    return Phi4FlashConfig(**kwargs)
