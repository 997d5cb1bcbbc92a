"""DeepSeek V2/V3: MLA attention, grouped MoE routing, HF parity + round trip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models.deepseek import Deepseek, DeepseekConfig
from llm_training_tpu.models.deepseek.hf_conversion import (
    config_from_hf,
    config_to_hf,
    params_from_hf,
    params_to_hf,
)

TINY = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=112,
    moe_intermediate_size=48,
    num_hidden_layers=2,
    num_attention_heads=4,
    max_position_embeddings=64,
    q_lora_rank=24,
    kv_lora_rank=32,
    qk_rope_head_dim=16,
    qk_nope_head_dim=32,
    v_head_dim=32,
    n_routed_experts=8,
    n_shared_experts=2,
    num_experts_per_tok=2,
    first_k_dense_replace=1,
    compute_dtype="float32",
)


def _hf_tiny(cls_name, **extra):
    torch = pytest.importorskip("torch")
    import transformers

    config_cls = getattr(transformers, cls_name + "Config")
    model_cls = getattr(transformers, cls_name + "ForCausalLM")
    kwargs = dict(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        moe_intermediate_size=48, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, max_position_embeddings=64,
        q_lora_rank=24, kv_lora_rank=32, qk_rope_head_dim=16,
        qk_nope_head_dim=32, v_head_dim=32, n_routed_experts=8,
        n_shared_experts=2, num_experts_per_tok=2, first_k_dense_replace=1,
        attn_implementation="eager",
    )
    kwargs.update(extra)
    hf_config = config_cls(**kwargs)
    torch.manual_seed(0)
    return model_cls(hf_config).eval(), hf_config


def _parity(hf_model, hf_config, seed):
    torch = pytest.importorskip("torch")
    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Deepseek(cfg)
    ids = np.random.default_rng(seed).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-4, atol=3e-4)
    return cfg, params, model


def test_logits_parity_with_hf_deepseek_v3():
    """V3: MLA + sigmoid router with e_score_correction_bias and top-2-sum
    group selection; layer 0 dense (first_k_dense_replace=1), layer 1 MoE
    with 2 shared experts."""
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny(
        "DeepseekV3", n_group=4, topk_group=2, routed_scaling_factor=2.5,
        norm_topk_prob=True, rope_interleave=True,
    )
    sd = hf_model.state_dict()
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd  # dense prefix
    assert "model.layers.1.mlp.experts.7.down_proj.weight" in sd
    # make the noaux bias actually change the selection
    with torch.no_grad():
        sd["model.layers.1.mlp.gate.e_score_correction_bias"].copy_(
            torch.linspace(-0.2, 0.2, 8)
        )
    cfg, _, _ = _parity(hf_model, hf_config, seed=30)
    assert cfg.version == 3 and cfg.rope_interleave
    assert cfg.routed_scaling_factor == 2.5 and cfg.n_group == 4


def test_kimi_k2_routes_as_deepseek_v3():
    """Kimi-K2 ships the DeepSeek-V3 graph/key layout verbatim under
    `model_type: kimi_k2`: the router must select the Deepseek family and
    the conversion must run in v3 mode, with logits parity against the HF
    DeepseekV3 reference the checkpoint structure matches."""
    torch = pytest.importorskip("torch")
    from llm_training_tpu.models.hf_io import model_class_for_hf

    hf_model, hf_config = _hf_tiny("DeepseekV3", n_group=4, topk_group=2)
    hf_dict = hf_config.to_dict()
    hf_dict["model_type"] = "kimi_k2"
    assert model_class_for_hf(hf_dict) == "llm_training_tpu.models.Deepseek"
    cfg = config_from_hf(hf_dict, compute_dtype="float32", moe_impl="dense")
    assert cfg.version == 3
    params = params_from_hf(hf_model.state_dict(), cfg)
    ids = np.random.default_rng(31).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(Deepseek(cfg).apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-4, atol=3e-4)


def test_logits_parity_with_hf_deepseek_v2_greedy():
    """V2-Lite-style: softmax scores, plain greedy top-k."""
    hf_model, hf_config = _hf_tiny(
        "DeepseekV2", topk_method="greedy", routed_scaling_factor=1.0,
    )
    cfg, _, _ = _parity(hf_model, hf_config, seed=31)
    assert cfg.version == 2 and cfg.topk_method == "greedy"


def test_logits_parity_with_hf_deepseek_v2_group_limited():
    """V2/V2-Chat-style: group-limited greedy (per-group max selection)."""
    hf_model, hf_config = _hf_tiny(
        "DeepseekV2", topk_method="group_limited_greedy", n_group=4,
        topk_group=2, routed_scaling_factor=16.0,
    )
    cfg, _, _ = _parity(hf_model, hf_config, seed=32)
    assert cfg.topk_method == "group_limited_greedy"


def test_full_rank_q_when_lora_disabled():
    """q_lora_rank=None uses the single full-rank q projection (V2-Lite)."""
    hf_model, hf_config = _hf_tiny("DeepseekV2", q_lora_rank=None)
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.q_proj.weight" in sd
    assert "model.layers.0.self_attn.q_a_proj.weight" not in sd
    cfg, _, _ = _parity(hf_model, hf_config, seed=33)
    assert cfg.q_lora_rank is None


def test_hf_round_trip():
    """params -> HF -> params is exact, including stacked expert weights and
    the v3 router bias."""
    hf_model, hf_config = _hf_tiny("DeepseekV3", n_group=4, topk_group=2)
    cfg = config_from_hf(hf_config)
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    assert set(back) == set(sd)
    for key in sd:
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


def test_config_round_trip():
    cfg = DeepseekConfig(**TINY, n_group=4, topk_group=2)
    hf = config_to_hf(cfg)
    assert hf["model_type"] == "deepseek_v3"
    cfg2 = config_from_hf(hf, compute_dtype="float32")
    assert cfg2.model_dump() == cfg.model_dump()


@pytest.mark.slow
def test_ragged_and_dense_impls_agree():
    cfg_d = DeepseekConfig(**TINY, n_group=4, topk_group=2, moe_impl="dense")
    cfg_r = DeepseekConfig(**TINY, n_group=4, topk_group=2, moe_impl="ragged")
    model_d, model_r = Deepseek(cfg_d), Deepseek(cfg_r)
    ids = jnp.asarray(np.random.default_rng(34).integers(0, 128, (2, 16)))
    params = jax.jit(model_d.init)(jax.random.key(7), ids)
    out_d = jax.jit(model_d.apply)(params, ids).logits
    out_r = jax.jit(model_r.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_r), rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_e2e_fit_decreases_loss():
    """Tiny DeepSeek V3 trains end to end (MLA + MoE under jit/grad/remat)."""
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.optim import OptimConfig
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    objective = CLM(CLMConfig(
        model=ModelProvider(
            model_class="llm_training_tpu.models.Deepseek",
            model_kwargs=dict(
                TINY, n_group=4, topk_group=2,
                enable_gradient_checkpointing=True,
            ),
        ),
        optim=OptimConfig(learning_rate=3e-3, warmup_steps=2),
    ))
    data = DummyDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=32, num_samples=64, vocab_size=128,
    ))
    losses = []

    class Track:
        def on_step_end(self, trainer, step, metrics):
            losses.append(float(metrics["loss"]))

    Trainer(
        TrainerConfig(max_steps=20, log_every_n_steps=1, mesh=MeshConfig()),
        callbacks=[Track()],
    ).fit(objective, data)
    assert np.mean(losses[-3:]) < np.mean(losses[:3])


@pytest.mark.slow
def test_export_reloads_in_transformers(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = DeepseekConfig(**TINY, n_group=4, topk_group=2)
    model = Deepseek(cfg)
    ids = jnp.asarray(np.random.default_rng(35).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(8), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "DeepseekV3ForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-4, atol=3e-4)


def test_v2_greedy_ignores_groups():
    """HF V2 only group-masks under topk_method='group_limited_greedy'; a
    greedy config that happens to carry n_group/topk_group must route over
    ALL experts (parity would break if the mask applied)."""
    hf_model, hf_config = _hf_tiny(
        "DeepseekV2", topk_method="greedy", n_group=4, topk_group=1,
    )
    cfg, _, _ = _parity(hf_model, hf_config, seed=36)
    assert cfg.topk_method == "greedy" and cfg.n_group == 4


@pytest.mark.slow
def test_sharded_fit_matches_single_device(devices):
    """The MLA + MoE logical axes must compose with a real fsdp x tensor
    mesh: losses on the sharded mesh equal the single-device run."""
    from conftest import fit_losses
    from llm_training_tpu.parallel import MeshConfig

    kwargs = dict(TINY, n_group=4, topk_group=2, num_attention_heads=4, moe_impl="dense")
    single = fit_losses("llm_training_tpu.models.Deepseek", kwargs)
    sharded = fit_losses(
        "llm_training_tpu.models.Deepseek", kwargs,
        mesh=MeshConfig(fsdp_size=4, tensor_parallel_size=2),
    )
    np.testing.assert_allclose(single, sharded, rtol=2e-4)


@pytest.mark.slow
def test_hf_causal_lm_loads_deepseek_checkpoint(tmp_path):
    """End-to-end: HF checkpoint dir -> HFCausalLM router -> Deepseek module
    -> streamed weights -> logits parity (the reference's `HFCausalLM`
    wrapping, `hf_causal_lm.py:22`, for the newest family class)."""
    torch = pytest.importorskip("torch")
    from llm_training_tpu.models import HFCausalLM, HFCausalLMConfig
    from llm_training_tpu.models.hf_io import load_pretrained_params

    hf_model, _ = _hf_tiny("DeepseekV3", n_group=4, topk_group=2)
    hf_model.save_pretrained(tmp_path / "dsv3", safe_serialization=True)

    model = HFCausalLM(HFCausalLMConfig(
        hf_path=str(tmp_path / "dsv3"), compute_dtype="float32",
        moe_impl="dense",
    ))
    assert isinstance(model, Deepseek)
    params = load_pretrained_params(model.config, tmp_path / "dsv3")

    ids = np.random.default_rng(37).integers(0, 128, (2, 16))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(jax.tree.map(jnp.asarray, params), jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-4, atol=3e-4)
