"""Phi-3: HF logits parity (incl. fused qkv/gate_up split), sliding window,
longrope factor defaulting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models import Phi3, Phi3Config
from llm_training_tpu.models.phi3.hf_conversion import (
    config_from_hf,
    params_from_hf,
    params_to_hf,
)

TINY = dict(
    vocab_size=160,
    hidden_size=64,
    intermediate_size=96,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=64,
)


def _hf_tiny_phi3(**kwargs):
    torch = pytest.importorskip("torch")
    from transformers import Phi3Config as HFPhi3Config, Phi3ForCausalLM

    hf_config = HFPhi3Config(
        **TINY,
        attn_implementation="eager",
        bos_token_id=1, eos_token_id=2, pad_token_id=0,
        **kwargs,
    )
    torch.manual_seed(0)
    return Phi3ForCausalLM(hf_config).eval(), hf_config


def test_logits_parity_with_hf():
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny_phi3()
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Phi3(cfg)

    ids = np.random.default_rng(0).integers(0, TINY["vocab_size"], (2, 12))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_hf_round_trip_fused():
    hf_model, hf_config = _hf_tiny_phi3()
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    assert set(back) == set(sd)
    for key in sd:
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


@pytest.mark.slow
def test_sliding_window_changes_output():
    cfg_full = Phi3Config(**TINY, compute_dtype="float32")
    cfg_win = Phi3Config(**TINY, compute_dtype="float32", sliding_window=4)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 160, (1, 16)))
    model = Phi3(cfg_full)
    params = jax.jit(model.init)(jax.random.key(0), ids)
    out_full = jax.jit(model.apply)(params, ids)
    out_win = jax.jit(Phi3(cfg_win).apply)(params, ids)
    # early positions (< window) identical, late positions differ
    np.testing.assert_allclose(out_full.logits[:, :4], out_win.logits[:, :4], rtol=1e-5)
    assert np.abs(np.asarray(out_full.logits[:, -1]) - np.asarray(out_win.logits[:, -1])).max() > 1e-3


def test_longrope_factor_defaulting():
    dim = (TINY["hidden_size"] // TINY["num_attention_heads"]) // 2
    cfg = Phi3Config(
        **{**TINY, "max_position_embeddings": 8192},
        original_max_position_embeddings=64,
        rope_scaling={
            "rope_type": "longrope",
            "short_factor": [1.0] * dim,
            "long_factor": [4.0] * dim,
        },
    )
    rope = cfg.rope_config
    assert rope.type == "longrope"
    assert rope.scaling["factor"] == 8192 / 64
    assert rope.max_position_embeddings == 64  # frequencies against original window

    with pytest.raises(ValueError, match="original_max_position_embeddings"):
        Phi3Config(
            **TINY,
            rope_scaling={
                "rope_type": "longrope",
                "short_factor": [1.0] * dim,
                "long_factor": [4.0] * dim,
            },
        )


def test_longrope_short_long_parity_with_hf():
    """HF selects short_factor for seq <= original_max and long_factor above;
    our seq_len-aware frequency computation must match both regimes."""
    torch = pytest.importorskip("torch")
    dim = (TINY["hidden_size"] // TINY["num_attention_heads"]) // 2
    rope_scaling = {  # HF Phi3Config validator wants the legacy 'type' key
        "type": "longrope",
        "short_factor": [1.0 + 0.05 * i for i in range(dim)],
        "long_factor": [2.0 + 0.1 * i for i in range(dim)],
    }
    hf_model, hf_config = _hf_tiny_phi3(  # TINY already has max_position=64
        original_max_position_embeddings=16,
        rope_scaling=rope_scaling,
    )
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Phi3(cfg)

    for seq in (12, 32):  # short regime (<=16) and long regime (>16)
        ids = np.random.default_rng(seq).integers(0, TINY["vocab_size"], (1, seq))
        with torch.no_grad():
            hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
        ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
        np.testing.assert_allclose(
            np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4,
            err_msg=f"seq={seq}",
        )


@pytest.mark.slow
def test_attention_compute_dtype():
    cfg = Phi3Config(**TINY, compute_dtype="bfloat16", attention_compute_dtype="float32")
    ids = jnp.ones((1, 8), jnp.int32)
    model = Phi3(cfg)
    params = jax.jit(model.init)(jax.random.key(0), ids)
    out = jax.jit(model.apply)(params, ids)
    assert out.logits.dtype == jnp.bfloat16  # cast back after attention
