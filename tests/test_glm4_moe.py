"""GLM-4.5 (glm4_moe): GQA + DeepSeek-V3-style noaux MoE, HF parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models.glm4_moe import Glm4Moe, Glm4MoeConfig
from llm_training_tpu.models.glm4_moe.hf_conversion import (
    config_from_hf,
    config_to_hf,
    params_from_hf,
    params_to_hf,
)

TINY = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=112,
    moe_intermediate_size=32,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    max_position_embeddings=64,
    n_routed_experts=8,
    n_shared_experts=1,
    num_experts_per_tok=2,
    first_k_dense_replace=1,
    n_group=4,
    topk_group=2,
    routed_scaling_factor=1.5,
    compute_dtype="float32",
)


def _hf_tiny(**extra):
    torch = pytest.importorskip("torch")
    from transformers import Glm4MoeConfig as HFConfig
    from transformers import Glm4MoeForCausalLM

    kwargs = dict(TINY)
    kwargs.pop("compute_dtype")
    kwargs.update(attn_implementation="eager", **extra)
    hf_config = HFConfig(**kwargs)
    torch.manual_seed(0)
    return Glm4MoeForCausalLM(hf_config).eval(), hf_config


@pytest.mark.parametrize("use_qk_norm,attention_bias",
                         [(False, False), (True, True)])
def test_logits_parity_with_hf(use_qk_norm, attention_bias):
    """GQA with partial (half-rotation) rotary + the V3-style sigmoid
    router with a LIVE noaux bias; layer 0 dense, layer 1 MoE with shared
    expert."""
    torch = pytest.importorskip("torch")
    # attention_bias=True mirrors the released GLM-4.5 checkpoints:
    # q/k/v biased, o_proj bias-free
    hf_model, hf_config = _hf_tiny(
        use_qk_norm=use_qk_norm, attention_bias=attention_bias
    )
    sd = hf_model.state_dict()
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd  # dense prefix
    if attention_bias:
        assert "model.layers.0.self_attn.q_proj.bias" in sd
        assert "model.layers.0.self_attn.o_proj.bias" not in sd
    with torch.no_grad():
        sd["model.layers.1.mlp.gate.e_score_correction_bias"].copy_(
            torch.linspace(-0.2, 0.2, 8)
        )

    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    assert cfg.use_qk_norm == use_qk_norm and cfg.routed_scaling_factor == 1.5
    params = params_from_hf(sd, cfg)
    model = Glm4Moe(cfg)

    ids = np.random.default_rng(95).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=4e-4, atol=4e-4)


def test_hf_round_trip():
    hf_model, hf_config = _hf_tiny(use_qk_norm=True)
    cfg = config_from_hf(hf_config)
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    assert set(back) == set(sd)
    for key in sd:
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


def test_config_round_trip():
    cfg = Glm4MoeConfig(**TINY)
    hf = config_to_hf(cfg)
    assert hf["model_type"] == "glm4_moe"
    cfg2 = config_from_hf(hf, compute_dtype="float32")
    assert cfg2.model_dump() == cfg.model_dump()


@pytest.mark.slow
def test_e2e_fit_decreases_loss():
    from conftest import fit_losses

    losses = fit_losses(
        "llm_training_tpu.models.Glm4Moe",
        dict(TINY, enable_gradient_checkpointing=True, moe_impl="dense"),
        max_steps=20, lr=3e-3,
    )
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_logits_parity_with_hf_dots1():
    """dots1 routes to the Glm4Moe module: the same V3-style noaux MoE with
    full-rotary attention, ALWAYS-ON per-head qk-norm, one bias flag
    covering o_proj too, and a qwen2-style per-layer sliding pattern."""
    torch = pytest.importorskip("torch")
    from transformers import Dots1Config, Dots1ForCausalLM

    kwargs = dict(TINY)
    kwargs.pop("compute_dtype")
    hf_config = Dots1Config(
        **kwargs, attention_bias=True, sliding_window=8,
        max_window_layers=1,  # layer 0 full, layer 1 sliding
        attn_implementation="eager",
    )
    assert hf_config.layer_types == ["full_attention", "sliding_attention"]
    torch.manual_seed(0)
    hf_model = Dots1ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.o_proj.bias" in sd
    assert "model.layers.0.self_attn.q_norm.weight" in sd
    # salt zero-init biases + the noaux bias so both are LIVE
    with torch.no_grad():
        for k, v in sd.items():
            if k.endswith(".bias"):
                v.copy_(torch.linspace(-0.2, 0.2, v.numel()))

    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    assert cfg.hf_flavor == "dots1" and cfg.use_qk_norm
    assert cfg.partial_rotary_factor == 1.0 and cfg.attention_out_bias
    assert cfg.layer_types == ["full_attention", "sliding_attention"]
    # the MoE suffix (layer 1) is uniformly sliding, so it still scans;
    # only a MIXED suffix forces the loop
    assert cfg.num_scanned_layers == 1
    params = params_from_hf(sd, cfg)
    model = Glm4Moe(cfg)

    ids = np.random.default_rng(60).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-4, atol=3e-4)


def test_dots1_config_round_trip():
    cfg = Glm4MoeConfig(
        **{**TINY, "partial_rotary_factor": 1.0}, use_qk_norm=True,
        attention_bias=True, attention_out_bias=True,
        sliding_window=8, layer_types=["full_attention", "sliding_attention"],
        hf_flavor="dots1",
    )
    hf = config_to_hf(cfg)
    assert hf["model_type"] == "dots1"
    cfg2 = config_from_hf(hf, compute_dtype="float32")
    assert cfg2.model_dump() == cfg.model_dump()


def test_glm4_moe_export_refuses_dots_features():
    cfg = Glm4MoeConfig(**TINY, sliding_window=8,
                        layer_types=["sliding_attention", "sliding_attention"])
    with pytest.raises(ValueError, match="dots1"):
        config_to_hf(cfg)
