"""MiniMax: hybrid lightning attention + mixtral MoE, HF parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models.minimax import MiniMax, MiniMaxConfig
from llm_training_tpu.models.minimax.hf_conversion import (
    config_from_hf,
    config_to_hf,
    params_from_hf,
    params_to_hf,
)

TINY = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=48,
    moe_intermediate_size=48,
    num_hidden_layers=4,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    max_position_embeddings=128,
    block_size=16,
    layer_types=["linear_attention", "full_attention",
                 "linear_attention", "full_attention"],
    num_experts=4,
    num_experts_per_tok=2,
    linear_attn_alpha_factor=1.0,
    linear_attn_beta_factor=1.0,
    compute_dtype="float32",
)


def _hf_tiny(**extra):
    torch = pytest.importorskip("torch")
    from transformers import MiniMaxConfig as HFConfig
    from transformers import MiniMaxForCausalLM

    kwargs = dict(
        vocab_size=128, hidden_size=64, intermediate_size=48,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=128, block_size=16,
        layer_types=["linear_attention", "full_attention",
                     "linear_attention", "full_attention"],
        num_local_experts=4, num_experts_per_tok=2,
        attn_implementation="eager",
    )
    kwargs.update(extra)
    hf_config = HFConfig(**kwargs)
    torch.manual_seed(0)
    return MiniMaxForCausalLM(hf_config).eval(), hf_config


@pytest.mark.parametrize("seq", [12, 40])
def test_logits_parity_with_hf(seq):
    """Hybrid stack vs HF eager: seq 12 fits one lightning block (16); seq
    40 spans three, exercising the cross-block KV state and decay."""
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.qkv_proj.weight" in sd
    assert "model.layers.1.self_attn.q_proj.weight" in sd
    assert "model.layers.0.block_sparse_moe.experts.0.w1.weight" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    assert cfg.layer_is_linear(0) and not cfg.layer_is_linear(1)
    assert cfg.moe_style == "mixtral"
    params = params_from_hf(sd, cfg)
    model = MiniMax(cfg)

    ids = np.random.default_rng(80).integers(0, 128, (2, seq))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=4e-4, atol=4e-4)


def test_residual_factors_are_live():
    """Non-unit alpha/beta residual combiners must change the graph and
    still match HF."""
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny(
        linear_attn_alpha_factor=0.7, linear_attn_beta_factor=1.3,
        full_attn_alpha_factor=0.9, full_attn_beta_factor=1.1,
        mlp_alpha_factor=0.8, mlp_beta_factor=1.2,
    )
    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    assert cfg.linear_attn_alpha_factor == 0.7 and cfg.mlp_beta_factor == 1.2
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = MiniMax(cfg)
    ids = np.random.default_rng(81).integers(0, 128, (2, 20))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=4e-4, atol=4e-4)


def test_hf_round_trip():
    hf_model, hf_config = _hf_tiny()
    cfg = config_from_hf(hf_config)
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    assert set(back) == set(sd)
    for key in sd:
        if any(b in key for b in ("decay", "slope_rate")):
            # deterministic buffers recomputed at export: numpy and torch
            # exp() differ in the last ulp
            np.testing.assert_allclose(back[key], sd[key], rtol=1e-6, err_msg=key)
        else:
            np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


def test_config_round_trip():
    cfg = MiniMaxConfig(**TINY)
    hf = config_to_hf(cfg)
    assert hf["model_type"] == "minimax"
    cfg2 = config_from_hf(hf, compute_dtype="float32")
    assert cfg2.model_dump() == cfg.model_dump()


@pytest.mark.slow
def test_e2e_fit_decreases_loss():
    from conftest import fit_losses

    losses = fit_losses(
        "llm_training_tpu.models.MiniMax",
        dict(TINY, enable_gradient_checkpointing=True, moe_impl="dense"),
        max_steps=20, lr=3e-3,
    )
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
