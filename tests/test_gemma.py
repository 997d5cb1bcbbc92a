"""Gemma 1/2: shapes, config validation, HF logits parity, state-dict round
trip (incl. the gemma-2 (sliding, full) scan pairing), and HFCausalLM routing.

Gemma-2's numerics are exactly the ones that silently break: (1+w) RMSNorm
with fp32 pre-downcast multiply, sqrt(hidden) embedding scaling, sandwich
norms, attention/final logit soft-capping, query_pre_attn_scalar scale, and
sliding window on even layer indices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models import Gemma, GemmaConfig
from llm_training_tpu.models.gemma.hf_conversion import (
    config_from_hf,
    params_from_hf,
    params_to_hf,
)
from llm_training_tpu.models.hf_io import model_class_for_hf

TINY_V1 = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=112,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=1,
    head_dim=16,
    max_position_embeddings=64,
    compute_dtype="float32",
)

TINY_V2 = dict(
    version=2,
    vocab_size=128,
    hidden_size=64,
    intermediate_size=112,
    num_hidden_layers=4,
    num_attention_heads=4,
    num_key_value_heads=2,
    head_dim=16,
    max_position_embeddings=64,
    query_pre_attn_scalar=24,
    attn_logit_softcapping=50.0,
    final_logit_softcapping=30.0,
    sliding_window=8,
    compute_dtype="float32",
)


@pytest.mark.slow
def test_forward_shapes():
    cfg = GemmaConfig(**TINY_V1)
    model = Gemma(cfg)
    ids = jnp.ones((2, 10), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), ids)
    out = jax.jit(model.apply)(params, ids, return_last_hidden_states=True)
    assert out.logits.shape == (2, 10, 128)
    assert out.last_hidden_states.shape == (2, 10, 64)


def test_v1_rejects_v2_features():
    with pytest.raises(ValueError, match="version=2"):
        GemmaConfig(**{**TINY_V1, "attn_logit_softcapping": 50.0})


def test_v2_scan_needs_even_layers():
    with pytest.raises(ValueError, match="even"):
        GemmaConfig(**{**TINY_V2, "num_hidden_layers": 3})


def test_routing():
    assert model_class_for_hf({"model_type": "gemma"}).endswith("Gemma")
    assert model_class_for_hf({"model_type": "gemma2"}).endswith("Gemma")


# ------------------------------------------------------------ HF parity


def _hf_tiny_gemma1():
    torch = pytest.importorskip("torch")
    from transformers import GemmaConfig as HFGemmaConfig
    from transformers import GemmaForCausalLM

    hf_config = HFGemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, max_position_embeddings=64,
        hidden_act="gelu_pytorch_tanh", hidden_activation="gelu_pytorch_tanh",
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    return GemmaForCausalLM(hf_config).eval(), hf_config


def _hf_tiny_gemma2():
    torch = pytest.importorskip("torch")
    from transformers import Gemma2Config as HFGemma2Config
    from transformers import Gemma2ForCausalLM

    hf_config = HFGemma2Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        query_pre_attn_scalar=24,
        attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
        sliding_window=8,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    return Gemma2ForCausalLM(hf_config).eval(), hf_config


def test_logits_parity_with_hf_gemma1():
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny_gemma1()
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.version == 1
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Gemma(cfg)

    ids = np.random.default_rng(7).integers(0, 128, (2, 16))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_gemma2():
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny_gemma2()
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.version == 2
    assert cfg.attn_logit_softcapping == 50.0
    assert cfg.sliding_window == 8
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Gemma(cfg)

    # 24 > sliding_window so local attention actually truncates
    ids = np.random.default_rng(8).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_gemma2_sliding_layers_are_even_indices():
    """HF gemma-2 applies the window on even layer indices; the scanned
    (sliding, full) pairing must agree with the HF per-layer layout."""
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny_gemma2()
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert [cfg.layer_sliding_window(i) for i in range(4)] == [8, None, 8, None]


# ------------------------------------------------------------ round trips


@pytest.mark.parametrize("tiny", [TINY_V1, TINY_V2], ids=["v1", "v2"])
def test_hf_round_trip(tiny):
    pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny_gemma1() if tiny is TINY_V1 else _hf_tiny_gemma2()
    cfg = config_from_hf(hf_config)
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()
          if k != "lm_head.weight"}  # tied: HF materializes it, we never store it
    assert set(back) == set(sd)
    for key in sd:
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


@pytest.mark.slow
def test_hf_causal_lm_loads_gemma2_checkpoint(tmp_path):
    """End-to-end: HF checkpoint dir -> HFCausalLM router -> Gemma module ->
    streamed weights -> logits parity (the reference's `HFCausalLM` wrapping
    of a Gemma checkpoint, `hf_causal_lm.py:22`)."""
    torch = pytest.importorskip("torch")
    from llm_training_tpu.models import HFCausalLM, HFCausalLMConfig
    from llm_training_tpu.models.hf_io import load_pretrained_params

    hf_model, _ = _hf_tiny_gemma2()
    hf_model.save_pretrained(tmp_path / "gemma2", safe_serialization=True)

    model = HFCausalLM(
        HFCausalLMConfig(hf_path=str(tmp_path / "gemma2"), compute_dtype="float32")
    )
    assert isinstance(model, Gemma)
    assert model.config.pre_trained_weights == str(tmp_path / "gemma2")
    params = load_pretrained_params(model.config, tmp_path / "gemma2")

    ids = np.random.default_rng(10).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(jax.tree.map(jnp.asarray, params), jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_scan_and_loop_layers_agree_v2():
    """The paired scan layout must compute the same function as the plain
    per-layer loop (which follows HF layer order directly)."""
    pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny_gemma2()
    ids = jnp.asarray(np.random.default_rng(9).integers(0, 128, (2, 24)))

    cfg_scan = config_from_hf(hf_config, compute_dtype="float32", scan_layers=True)
    cfg_loop = config_from_hf(hf_config, compute_dtype="float32", scan_layers=False)
    out_scan = Gemma(cfg_scan).apply(params_from_hf(hf_model.state_dict(), cfg_scan), ids)
    out_loop = Gemma(cfg_loop).apply(params_from_hf(hf_model.state_dict(), cfg_loop), ids)
    np.testing.assert_allclose(out_scan.logits, out_loop.logits, rtol=2e-5, atol=1e-5)


def test_logits_parity_with_hf_gemma3():
    """Gemma3 text: per-head zero-centered qk-norm, the 5:1 layer_types
    sliding/full pattern, and DUAL rotary tables (local theta for sliding
    layers, scaled global theta for full layers)."""
    torch = pytest.importorskip("torch")
    from transformers import Gemma3TextConfig, Gemma3ForCausalLM

    hf_config = Gemma3TextConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        query_pre_attn_scalar=24, sliding_window=8,
        sliding_window_pattern=3,  # layers 0,1 sliding; 2 full; 3 sliding
        rope_theta=1000000.0, rope_local_base_freq=10000.0,
        rope_scaling={"rope_type": "linear", "factor": 8.0},
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = Gemma3ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.q_norm.weight" in sd
    assert sd["model.layers.0.self_attn.q_norm.weight"].shape == (16,)  # per-head
    assert "model.layers.0.pre_feedforward_layernorm.weight" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.version == 3 and cfg.use_qk_norm
    assert cfg.layer_types == list(hf_config.layer_types)
    assert not cfg.scan_layers  # aperiodic pattern -> looped layers
    # the pattern must mix both kinds or the dual-rope path goes untested
    assert {"sliding_attention", "full_attention"} <= set(cfg.layer_types)
    params = params_from_hf(sd, cfg)
    model = Gemma(cfg)

    # 24 > sliding_window so local attention actually truncates
    ids = np.random.default_rng(9).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_gemma3_export_guards():
    """gemma3_text exports must carry an explicit layer_types list (HF
    re-derives a 5:1 sliding pattern from null) and refuse qk-norm-off
    configs (HF builds the norms unconditionally)."""
    import pytest as _pytest

    from llm_training_tpu.models.gemma.hf_conversion import config_to_hf

    hf = config_to_hf(GemmaConfig(
        version=3, vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, query_pre_attn_scalar=24,
    ))
    assert hf["layer_types"] == ["full_attention"] * 2
    with _pytest.raises(ValueError, match="use_qk_norm"):
        config_to_hf(GemmaConfig(
            version=3, vocab_size=128, hidden_size=64, intermediate_size=112,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, use_qk_norm=False,
        ))


def test_clm_fused_loss_applies_final_softcap():
    """The CLM fused-CE path must apply Gemma-2's final_logit_softcapping —
    the loss computed without logits must equal CE over the (capped)
    compute_logits output."""
    from llm_training_tpu.lms import CLM, CLMConfig

    cfg = GemmaConfig(
        version=2, vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, query_pre_attn_scalar=24,
        final_logit_softcapping=5.0, compute_dtype="float32",
    )
    model = Gemma(cfg)
    ids = jnp.asarray(np.random.default_rng(21).integers(1, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(6), ids)

    objective = CLM(CLMConfig(), model=model)
    loss, _ = objective.loss_and_metrics(params, {"input_ids": ids}, train=False)

    logits = jax.jit(model.apply)(params, ids).logits  # capped by compute_logits
    shifted = np.full(ids.shape, -100)
    shifted[:, :-1] = np.asarray(ids)[:, 1:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    rows = []
    for b in range(ids.shape[0]):
        for t in range(ids.shape[1] - 1):
            rows.append(-logp[b, t, shifted[b, t]])
    np.testing.assert_allclose(float(loss), np.mean(rows), rtol=1e-5)
