"""HunYuan V1 MoE: post-rope qk-norm + softmax top-k MoE, HF parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models.hunyuan_moe import HunYuanMoe, HunYuanMoeConfig
from llm_training_tpu.models.hunyuan_moe.hf_conversion import (
    config_from_hf,
    config_to_hf,
    params_from_hf,
    params_to_hf,
)

TINY = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=48,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    max_position_embeddings=64,
    num_experts=4,
    moe_topk=2,
    compute_dtype="float32",
)


def _hf_tiny(**extra):
    torch = pytest.importorskip("torch")
    from transformers import HunYuanMoEV1Config as HFConfig
    from transformers import HunYuanMoEV1ForCausalLM

    kwargs = dict(TINY)
    kwargs.pop("compute_dtype")
    kwargs.update(attn_implementation="eager", **extra)
    hf_config = HFConfig(**kwargs)
    torch.manual_seed(0)
    return HunYuanMoEV1ForCausalLM(hf_config).eval(), hf_config


def test_logits_parity_with_hf():
    """Post-rope per-head qk-norm + softmax top-k router + gate-free shared
    MLP (HF keys: gate.wg, shared_mlp)."""
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.gate.wg.weight" in sd
    assert "model.layers.0.mlp.shared_mlp.gate_proj.weight" in sd
    assert "model.layers.0.self_attn.query_layernorm.weight" in sd
    with torch.no_grad():  # post-rope ordering live
        for k, v in sd.items():
            if "layernorm.weight" in k and "self_attn" in k:
                v.copy_(torch.linspace(0.5, 1.5, v.numel()))

    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    params = params_from_hf(sd, cfg)
    model = HunYuanMoe(cfg)

    ids = np.random.default_rng(99).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=4e-4, atol=4e-4)


def test_scan_and_loop_layers_agree():
    cfg_s = HunYuanMoeConfig(**TINY, scan_layers=True, moe_impl="dense")
    cfg_l = HunYuanMoeConfig(**TINY, scan_layers=False, moe_impl="dense")
    hf_model, hf_config = _hf_tiny()
    sd = hf_model.state_dict()
    ps = params_from_hf(sd, cfg_s)
    pl = params_from_hf(sd, cfg_l)
    ids = jnp.asarray(np.random.default_rng(100).integers(0, 128, (1, 16)))
    out_s = jax.jit(HunYuanMoe(cfg_s).apply)(ps, ids).logits
    out_l = jax.jit(HunYuanMoe(cfg_l).apply)(pl, ids).logits
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_l), rtol=2e-5, atol=2e-5)


def test_hf_round_trip():
    hf_model, hf_config = _hf_tiny()
    cfg = config_from_hf(hf_config)
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    assert set(back) == set(sd)
    for key in sd:
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


def test_config_round_trip():
    cfg = HunYuanMoeConfig(**TINY)
    hf = config_to_hf(cfg)
    assert hf["model_type"] == "hunyuan_v1_moe"
    cfg2 = config_from_hf(hf, compute_dtype="float32")
    assert cfg2.model_dump() == cfg.model_dump()


@pytest.mark.slow
def test_e2e_fit_decreases_loss():
    from conftest import fit_losses

    losses = fit_losses(
        "llm_training_tpu.models.HunYuanMoe",
        dict(TINY, enable_gradient_checkpointing=True, moe_impl="dense"),
        max_steps=20, lr=3e-3,
    )
    assert np.mean(losses[-3:]) < np.mean(losses[:3])
