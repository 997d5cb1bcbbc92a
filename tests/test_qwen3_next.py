"""Qwen3-Next: hybrid gated DeltaNet + gated attention, HF parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
from llm_training_tpu.models.qwen3_next.hf_conversion import (
    config_from_hf,
    config_to_hf,
    params_from_hf,
    params_to_hf,
)

TINY = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=112,
    num_hidden_layers=4,  # 3 linear + 1 full
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    max_position_embeddings=128,
    linear_num_key_heads=2,
    linear_num_value_heads=4,
    linear_key_head_dim=16,
    linear_value_head_dim=16,
    num_experts=4,
    num_experts_per_tok=2,
    moe_intermediate_size=32,
    shared_expert_intermediate_size=48,
    compute_dtype="float32",
)


def _hf_tiny(**extra):
    torch = pytest.importorskip("torch")
    from transformers import Qwen3NextConfig as HFConfig
    from transformers import Qwen3NextForCausalLM

    kwargs = dict(TINY)
    kwargs.pop("compute_dtype")
    kwargs.update(attn_implementation="eager", **extra)
    hf_config = HFConfig(**kwargs)
    torch.manual_seed(0)
    return Qwen3NextForCausalLM(hf_config).eval(), hf_config


@pytest.mark.parametrize("seq", [24, 80])
def test_logits_parity_with_hf(seq):
    """Hybrid stack vs HF eager: seq 24 fits one delta chunk; seq 80 spans
    two, exercising the cross-chunk recurrent state."""
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny()
    sd = hf_model.state_dict()
    assert "model.layers.0.linear_attn.in_proj_qkvz.weight" in sd
    assert "model.layers.3.self_attn.q_proj.weight" in sd
    assert "model.layers.0.mlp.shared_expert_gate.weight" in sd
    # make the decay/write dynamics non-trivial
    with torch.no_grad():
        for i in (0, 1, 2):
            sd[f"model.layers.{i}.linear_attn.A_log"].copy_(
                torch.linspace(-1.0, 1.0, 4)
            )
            sd[f"model.layers.{i}.linear_attn.dt_bias"].copy_(
                torch.linspace(-0.5, 0.5, 4)
            )

    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    assert cfg.layer_is_linear(0) and not cfg.layer_is_linear(3)
    params = params_from_hf(sd, cfg)
    model = Qwen3Next(cfg)

    ids = np.random.default_rng(70).integers(0, 128, (2, seq))
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
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


def test_config_round_trip():
    cfg = Qwen3NextConfig(**TINY)
    hf = config_to_hf(cfg)
    assert hf["model_type"] == "qwen3_next"
    cfg2 = config_from_hf(hf, compute_dtype="float32")
    a, b = cfg.model_dump(), cfg2.model_dump()
    a.pop("layer_types"), b.pop("layer_types")
    assert a == b


@pytest.mark.slow
def test_e2e_fit_decreases_loss():
    from conftest import fit_losses

    losses = fit_losses(
        "llm_training_tpu.models.Qwen3Next",
        dict(TINY, enable_gradient_checkpointing=True, moe_impl="dense",
             delta_chunk_size=16),
        max_steps=20, lr=3e-3,
    )
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


@pytest.mark.slow
def test_hf_causal_lm_loads_qwen3_next_checkpoint(tmp_path):
    """End-to-end: HF checkpoint dir -> HFCausalLM router -> Qwen3Next
    (hybrid) -> streamed weights -> logits parity."""
    torch = pytest.importorskip("torch")
    from llm_training_tpu.models import HFCausalLM, HFCausalLMConfig
    from llm_training_tpu.models.hf_io import load_pretrained_params

    hf_model, _ = _hf_tiny()
    hf_model.save_pretrained(tmp_path / "q3n", safe_serialization=True)

    model = HFCausalLM(HFCausalLMConfig(
        hf_path=str(tmp_path / "q3n"), compute_dtype="float32",
        moe_impl="dense",
    ))
    assert isinstance(model, Qwen3Next)
    params = load_pretrained_params(model.config, tmp_path / "q3n")

    ids = np.random.default_rng(71).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(jax.tree.map(jnp.asarray, params), jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=4e-4, atol=4e-4)
