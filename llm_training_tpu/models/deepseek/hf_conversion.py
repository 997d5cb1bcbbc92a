"""DeepSeek V2/V3 <-> HuggingFace state-dict conversion.

Capability parity: reference `hf_compat_model.py:96-119` applied to the
DeepSeek family (which the reference reaches only through `HFCausalLM`'s
torch wrapping, `hf_causal_lm.py:22`). The dense prefix is looped
(`layers_{i}` keys); the uniform MoE suffix is scanned (`moe_layers/layer`
keys with a leading depth axis). Per-expert HF weights stack into ONE
[E, in, out] parameter per projection ([L_s, E, in, out] under the scan).

`model_type: pangu_ultra_moe` (openPangu-Ultra-MoE) is the V3 layout with two
more norms a layer (`pre_mlp_layernorm`, `post_mlp_layernorm`), which convert.
Its multi-token-prediction layer (index `num_hidden_layers`: `eh_proj`,
`enorm`, `hnorm`, a decoder layer, a `shared_head`) is NOT mapped: this repo
shares the final norm and the head with the stack where the checkpoint keeps
a copy, and which copy a load should trust is not this module's to guess. A
config with `num_nextn_predict_layers`, or one that holds a share of the
experts, refuses a state dict with a message.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from llm_training_tpu.models.deepseek.config import DeepseekConfig
from llm_training_tpu.models.llama.hf_conversion import (
    _get_path,
    _set_path,
    _to_numpy,
)
from llm_training_tpu.models.moe_scan_io import layers_from_hf, layers_to_hf

_ATTN_COMMON = [
    (("self_attn", "kv_a_proj_with_mqa", "kernel"), "self_attn.kv_a_proj_with_mqa.weight", True),
    (("self_attn", "kv_a_layernorm", "weight"), "self_attn.kv_a_layernorm.weight", False),
    (("self_attn", "kv_b_proj", "kernel"), "self_attn.kv_b_proj.weight", True),
    (("self_attn", "o_proj", "kernel"), "self_attn.o_proj.weight", True),
    (("input_layernorm", "weight"), "input_layernorm.weight", False),
    (("post_attention_layernorm", "weight"), "post_attention_layernorm.weight", False),
]

_Q_FULL = [(("self_attn", "q_proj", "kernel"), "self_attn.q_proj.weight", True)]
_Q_LORA = [
    (("self_attn", "q_a_proj", "kernel"), "self_attn.q_a_proj.weight", True),
    (("self_attn", "q_a_layernorm", "weight"), "self_attn.q_a_layernorm.weight", False),
    (("self_attn", "q_b_proj", "kernel"), "self_attn.q_b_proj.weight", True),
]

_DENSE_MLP = [
    (("mlp", "gate_proj", "kernel"), "mlp.gate_proj.weight", True),
    (("mlp", "up_proj", "kernel"), "mlp.up_proj.weight", True),
    (("mlp", "down_proj", "kernel"), "mlp.down_proj.weight", True),
]

_SHARED_MLP = [
    (("mlp", "shared_experts", "gate_proj", "kernel"), "mlp.shared_experts.gate_proj.weight", True),
    (("mlp", "shared_experts", "up_proj", "kernel"), "mlp.shared_experts.up_proj.weight", True),
    (("mlp", "shared_experts", "down_proj", "kernel"), "mlp.shared_experts.down_proj.weight", True),
]

_EXPERT_PROJS = ("gate_proj", "up_proj", "down_proj")


_ATTN_BIASES = [
    # HF gates these three on attention_bias (q_b/kv_b/q full stay bias-free)
    (("self_attn", "kv_a_proj_with_mqa", "bias"), "self_attn.kv_a_proj_with_mqa.bias", False),
    (("self_attn", "o_proj", "bias"), "self_attn.o_proj.bias", False),
]

_Q_LORA_BIAS = [(("self_attn", "q_a_proj", "bias"), "self_attn.q_a_proj.bias", False)]


_SANDWICH_NORMS = [
    (("pre_mlp_layernorm", "weight"), "pre_mlp_layernorm.weight", False),
    (("post_mlp_layernorm", "weight"), "post_mlp_layernorm.weight", False),
]


def _refuse_unmapped(config: DeepseekConfig) -> None:
    if config.num_nextn_predict_layers:
        raise NotImplementedError(
            "deepseek: the multi-token-prediction layer's tensors (layer "
            f"{config.num_hidden_layers}: eh_proj, enorm, hnorm, shared_head) are not mapped; "
            "convert with num_nextn_predict_layers=0"
        )
    if config.experts_held is not None:
        raise NotImplementedError(
            "deepseek: a share of the experts (experts_held) is no whole checkpoint; "
            "the config converts, a state dict does not"
        )


def _layer_params(config: DeepseekConfig, i: int) -> list:
    params = list(_ATTN_COMMON)
    if config.sandwich_norm:
        params += _SANDWICH_NORMS
    params += _Q_FULL if config.q_lora_rank is None else _Q_LORA
    if config.attention_bias:
        params += _ATTN_BIASES
        if config.q_lora_rank is not None:
            params += _Q_LORA_BIAS
    if not config.layer_is_moe(i):
        params += _DENSE_MLP
    else:
        params += _SHARED_MLP
        params.append((("mlp", "gate_kernel"), "mlp.gate.weight", True))
        if config.version == 3:
            params.append(
                (("mlp", "e_score_correction_bias"), "mlp.gate.e_score_correction_bias", False)
            )
    return params


def params_from_hf(
    state_dict: Mapping[str, Any], config: DeepseekConfig, leaf_fn: Any = None
) -> dict:
    _refuse_unmapped(config)
    params: dict = {}
    sd = {k.removeprefix("model."): v for k, v in state_dict.items()}

    def put(path: tuple[str, ...], value: np.ndarray) -> None:
        _set_path(params, path, leaf_fn(path, value) if leaf_fn else value)

    put(("embed_tokens", "embedding"), _to_numpy(sd["embed_tokens.weight"]))
    put(("norm", "weight"), _to_numpy(sd["norm.weight"]))
    if not config.tie_word_embeddings:
        put(("lm_head", "kernel"), _to_numpy(sd["lm_head.weight"]).T)

    def expert_parts(sd, i):
        return {
            ("mlp", f"experts_{proj}"): lambda proj=proj: np.stack([
                _to_numpy(sd[f"layers.{i}.mlp.experts.{e}.{proj}.weight"]).T
                for e in range(config.n_routed_experts)
            ])
            for proj in _EXPERT_PROJS
        }

    layers_from_hf(sd, config, put, _layer_params, expert_parts)
    return {"params": params}


def params_to_hf(params: Mapping, config: DeepseekConfig) -> dict[str, np.ndarray]:
    import flax.linen as nn

    _refuse_unmapped(config)
    p = params.get("params", params)
    p = nn.meta.unbox(p)
    out: dict[str, np.ndarray] = {}
    out["model.embed_tokens.weight"] = np.asarray(_get_path(p, ("embed_tokens", "embedding")))
    out["model.norm.weight"] = np.asarray(_get_path(p, ("norm", "weight")))
    if not config.tie_word_embeddings:
        out["lm_head.weight"] = np.asarray(_get_path(p, ("lm_head", "kernel"))).T

    def expert_out(get, i, out):
        for proj in _EXPERT_PROJS:
            stacked = get(("mlp", f"experts_{proj}"))  # [E, in, out]
            for e in range(config.n_routed_experts):
                out[f"model.layers.{i}.mlp.experts.{e}.{proj}.weight"] = stacked[e].T

    layers_to_hf(p, config, out, _layer_params, expert_out)
    return out


def config_to_hf(config: DeepseekConfig, torch_dtype: str = "bfloat16") -> dict[str, Any]:
    v3 = config.version == 3
    # pangu_ultra_moe is the one model_type with the four norms
    pangu = {
        "architectures": ["PanguUltraMoEForCausalLM"], "model_type": "pangu_ultra_moe",
        "sandwich_norm": True, "num_nextn_predict_layers": config.num_nextn_predict_layers,
    } if config.sandwich_norm else {}
    return {
        "architectures": ["DeepseekV3ForCausalLM" if v3 else "DeepseekV2ForCausalLM"],
        "model_type": "deepseek_v3" if v3 else "deepseek_v2",
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "moe_intermediate_size": config.moe_intermediate_size,
        "num_hidden_layers": config.num_hidden_layers,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_attention_heads,
        "q_lora_rank": config.q_lora_rank,
        "kv_lora_rank": config.kv_lora_rank,
        "qk_rope_head_dim": config.qk_rope_head_dim,
        "qk_nope_head_dim": config.qk_nope_head_dim,
        "v_head_dim": config.v_head_dim,
        "n_routed_experts": config.n_routed_experts,
        "n_shared_experts": config.n_shared_experts,
        "num_experts_per_tok": config.num_experts_per_tok,
        "first_k_dense_replace": config.first_k_dense_replace,
        "norm_topk_prob": config.norm_topk_prob,
        "routed_scaling_factor": config.routed_scaling_factor,
        "n_group": config.n_group,
        "topk_group": config.topk_group,
        "hidden_act": "silu",
        "max_position_embeddings": config.max_position_embeddings,
        "initializer_range": config.initializer_range,
        "rms_norm_eps": config.rms_norm_eps,
        "pad_token_id": config.pad_token_id,
        "bos_token_id": config.bos_token_id,
        "eos_token_id": config.eos_token_id,
        "tie_word_embeddings": config.tie_word_embeddings,
        "rope_theta": config.rope_theta,
        "rope_scaling": config.rope_scaling,
        "attention_bias": config.attention_bias,
        "attention_dropout": config.attention_dropout,
        "use_cache": True,
        "torch_dtype": torch_dtype,
        **(
            {"rope_interleave": config.rope_interleave}
            if v3
            else {"topk_method": config.topk_method}
        ),
        **pangu,
    }


def config_from_hf(hf_config: Any, **overrides: Any) -> DeepseekConfig:
    get = (lambda k, d=None: hf_config.get(k, d)) if isinstance(hf_config, dict) else (
        lambda k, d=None: getattr(hf_config, k, d)
    )
    model_type = get("model_type")
    # kimi_k2 (Moonshot Kimi-K2) ships the DeepSeek-V3 graph and key layout
    # verbatim under its own model_type
    version = 3 if model_type in ("deepseek_v3", "kimi_k2", "pangu_ultra_moe") else 2
    if version == 2 and get("topk_method", "greedy") not in (
        "greedy", "group_limited_greedy"
    ):
        raise ValueError(f"unsupported topk_method {get('topk_method')!r}")
    return DeepseekConfig(**{**dict(
        version=version,
        vocab_size=get("vocab_size"),
        hidden_size=get("hidden_size"),
        intermediate_size=get("intermediate_size"),
        moe_intermediate_size=get("moe_intermediate_size"),
        num_hidden_layers=get("num_hidden_layers"),
        num_attention_heads=get("num_attention_heads"),
        max_position_embeddings=get("max_position_embeddings"),
        initializer_range=get("initializer_range", 0.02),
        rms_norm_eps=get("rms_norm_eps", 1e-6),
        pad_token_id=get("pad_token_id"),
        bos_token_id=get("bos_token_id", 0),
        eos_token_id=get("eos_token_id", 1),
        tie_word_embeddings=get("tie_word_embeddings", False),
        rope_theta=get("rope_theta", 10000.0),
        rope_scaling=get("rope_scaling"),
        # V2's complex-pair rotation IS the interleaved layout; V3 makes it
        # an explicit flag
        rope_interleave=get("rope_interleave", True),
        attention_bias=get("attention_bias", False),
        attention_dropout=get("attention_dropout", 0.0),
        q_lora_rank=get("q_lora_rank"),
        kv_lora_rank=get("kv_lora_rank", 512),
        qk_rope_head_dim=get("qk_rope_head_dim", 64),
        qk_nope_head_dim=get("qk_nope_head_dim", 128),
        v_head_dim=get("v_head_dim", 128),
        n_routed_experts=get("n_routed_experts"),
        n_shared_experts=get("n_shared_experts", 1),
        num_experts_per_tok=get("num_experts_per_tok", 8),
        first_k_dense_replace=get("first_k_dense_replace", 0),
        norm_topk_prob=get("norm_topk_prob", True),
        routed_scaling_factor=get("routed_scaling_factor", 1.0),
        n_group=get("n_group"),
        topk_group=get("topk_group"),
        topk_method=get("topk_method", "greedy") if version == 2 else "greedy",
        sandwich_norm=bool(get("sandwich_norm", False)),
        # DeepSeek-V3's and Kimi-K2's configs name a module too; their
        # checkpoints' extra layer was never loaded here, and is not now
        num_nextn_predict_layers=(
            get("num_nextn_predict_layers", 0) if model_type == "pangu_ultra_moe" else 0
        ),
    ), **overrides})
