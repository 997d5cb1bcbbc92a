"""Ernie 4.5 MoE: aux-free softmax routing + interleaved rope, HF parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models.ernie45_moe import Ernie45Moe, Ernie45MoeConfig
from llm_training_tpu.models.ernie45_moe.hf_conversion import (
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
    moe_num_experts=8,
    moe_k=2,
    moe_num_shared_experts=1,
    moe_layer_start_index=1,
    use_bias=True,
    tie_word_embeddings=True,
    compute_dtype="float32",
)


def _hf_tiny(**extra):
    torch = pytest.importorskip("torch")
    from transformers import Ernie4_5_MoeConfig as HFConfig
    from transformers import Ernie4_5_MoeForCausalLM

    kwargs = dict(TINY)
    kwargs.pop("compute_dtype")
    kwargs.update(attn_implementation="eager", **extra)
    hf_config = HFConfig(**kwargs)
    torch.manual_seed(0)
    return Ernie4_5_MoeForCausalLM(hf_config).eval(), hf_config


def test_logits_parity_with_hf():
    """Softmax router with a LIVE aux-free selection bias (biasing selection
    only, not the combine weights), gate-free shared expert, dense prefix,
    interleaved rope, use_bias over q/k/v/o."""
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny()
    sd = hf_model.state_dict()
    assert "model.layers.1.mlp.moe_statics.e_score_correction_bias" in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd  # dense prefix
    assert "model.layers.0.self_attn.o_proj.bias" in sd  # use_bias covers o
    assert "model.layers.1.mlp.shared_experts.gate_proj.weight" in sd
    with torch.no_grad():
        sd["model.layers.1.mlp.moe_statics.e_score_correction_bias"].copy_(
            torch.linspace(-0.2, 0.2, 8).reshape(1, -1)
        )

    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    assert not cfg.layer_is_moe(0) and cfg.layer_is_moe(1)
    params = params_from_hf(sd, cfg)
    model = Ernie45Moe(cfg)

    ids = np.random.default_rng(96).integers(0, 128, (2, 24))
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
    cfg = Ernie45MoeConfig(**TINY)
    hf = config_to_hf(cfg)
    assert hf["model_type"] == "ernie4_5_moe"
    cfg2 = config_from_hf(hf, compute_dtype="float32")
    assert cfg2.model_dump() == cfg.model_dump()


@pytest.mark.slow
def test_e2e_fit_decreases_loss():
    from conftest import fit_losses

    losses = fit_losses(
        "llm_training_tpu.models.Ernie45Moe",
        dict(TINY, enable_gradient_checkpointing=True, moe_impl="dense"),
        max_steps=20, lr=3e-3,
    )
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


def test_clm_fused_loss_applies_tied_head_bias():
    """The fused-CE path must add the standalone lm_head bias that rides on
    TIED embeddings (the sibling-bias heuristic cannot see it)."""
    from llm_training_tpu.lms import CLM, CLMConfig

    cfg = Ernie45MoeConfig(**TINY)
    model = Ernie45Moe(cfg)
    ids = jnp.asarray(np.random.default_rng(97).integers(1, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(14), ids)
    # salt the zero-init head bias so it is LIVE
    import flax.linen as fnn
    leaf = params["params"]["lm_head_bias"]
    noise = jnp.asarray(np.random.default_rng(98).normal(0, 0.5, 128), jnp.float32)
    params["params"]["lm_head_bias"] = (
        leaf.replace_boxed(noise) if isinstance(leaf, fnn.Partitioned) else noise
    )

    objective = CLM(CLMConfig(), model=model)
    loss, _ = objective.loss_and_metrics(params, {"input_ids": ids}, train=False)

    logits = jax.jit(model.apply)(params, ids).logits
    shifted = np.full(ids.shape, -100)
    shifted[:, :-1] = np.asarray(ids)[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    rows = [
        -logp[b, t, shifted[b, t]]
        for b in range(ids.shape[0]) for t in range(ids.shape[1] - 1)
    ]
    np.testing.assert_allclose(float(loss), np.mean(rows), rtol=1e-5)
