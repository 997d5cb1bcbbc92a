"""Mixture-of-experts: dense/ragged impl agreement, HF logits parity for
Mixtral / Qwen2-MoE / Qwen3-MoE, export round trip, aux loss, and training.

The reference reaches MoE only through HFCausalLM's torch wrapping
(`hf_causal_lm.py:22`); here the graph is native (models/moe.py) with a
dropless ragged_dot grouped-matmul path, so parity against the HF torch
implementations is the correctness bar.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models import Llama, LlamaConfig
from llm_training_tpu.models.llama.hf_conversion import (
    config_from_hf,
    params_from_hf,
    params_to_hf,
)

TINY_MOE = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=112,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=64,
    num_experts=4,
    num_experts_per_tok=2,
    moe_intermediate_size=48,
    compute_dtype="float32",
)


@pytest.mark.slow
def test_dense_and_ragged_impls_agree():
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 24)))
    cfg_d = LlamaConfig(**TINY_MOE, moe_impl="dense")
    cfg_r = LlamaConfig(**TINY_MOE, moe_impl="ragged")
    model_d, model_r = Llama(cfg_d), Llama(cfg_r)
    params = jax.jit(model_d.init)(jax.random.key(0), ids)
    out_d = jax.jit(model_d.apply)(params, ids)
    out_r = jax.jit(model_r.apply)(params, ids)
    np.testing.assert_allclose(out_d.logits, out_r.logits, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out_d.aux_loss, out_r.aux_loss, rtol=1e-6)


def test_router_stats_parity_dense_vs_ragged():
    """The health-layer router stats (load fractions / dropped fraction)
    must be impl-invariant: dense and ragged on the same params/batch agree
    exactly on sel_frac/mean_prob, and both truly-dropless single-rank
    paths report zero drops (guards the EP capacity-buffer accounting —
    an impl that drifted here would corrupt the telemetry the
    ep_capacity_factor tuning reads)."""
    tiny = dict(TINY_MOE, num_hidden_layers=1)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 128, (2, 16)))
    model_d = Llama(LlamaConfig(**tiny, moe_impl="dense"))
    model_r = Llama(LlamaConfig(**tiny, moe_impl="ragged"))
    params = jax.jit(model_d.init)(jax.random.key(3), ids)
    rs_d = jax.jit(model_d.apply)(params, ids).router_stats
    rs_r = jax.jit(model_r.apply)(params, ids).router_stats
    assert rs_d.layer_ids == rs_r.layer_ids == (0,)
    np.testing.assert_allclose(rs_d.sel_frac, rs_r.sel_frac, rtol=1e-6)
    np.testing.assert_allclose(rs_d.mean_prob, rs_r.mean_prob, rtol=1e-6)
    # each row sums to top_k (each of the K selections per token counts)
    np.testing.assert_allclose(
        np.asarray(rs_d.sel_frac.sum(axis=-1)),
        TINY_MOE["num_experts_per_tok"], rtol=1e-6,
    )
    assert float(rs_d.dropped) == 0.0 and float(rs_r.dropped) == 0.0


def test_bucketed_impl_matches_dense_at_full_capacity():
    """moe_impl='bucketed' with capacity >= every group size is exact: the
    dense-bmm bucket formulation must reproduce the dense path bit-for-tol,
    and report zero drops."""
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, (2, 24)))
    cfg_d = LlamaConfig(**TINY_MOE, moe_impl="dense")
    # factor = num_experts -> capacity == all T*K rows: drops impossible
    cfg_b = LlamaConfig(**TINY_MOE, moe_impl="bucketed", moe_capacity_factor=4.0)
    model_d, model_b = Llama(cfg_d), Llama(cfg_b)
    params = jax.jit(model_d.init)(jax.random.key(1), ids)
    out_d = jax.jit(model_d.apply)(params, ids)
    out_b = jax.jit(model_b.apply)(params, ids)
    np.testing.assert_allclose(out_d.logits, out_b.logits, rtol=2e-5, atol=2e-5)
    assert float(out_b.ep_dropped_rows) == 0.0


def test_bucketed_impl_counts_drops():
    """Tiny capacity drops exactly the rows beyond each expert's bucket,
    the counter matches the capacity math, and gradients still flow."""
    from llm_training_tpu.models.moe import dropless_moe_apply

    T, H, E, K = 16, 8, 4, 2
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    topk_idx = jnp.zeros((T, K), jnp.int32)  # all 32 rows -> expert 0
    topk_w = jnp.full((T, K), 0.5, jnp.float32)
    w = jnp.asarray(rng.standard_normal((E, H, H)) * 0.1, jnp.float32)

    def bmm_fn(xb):
        return jnp.einsum("ech,ehg->ecg", xb, w)

    def f(x):
        out, dropped = dropless_moe_apply(
            x, topk_idx, topk_w, E, "bucketed", None, None,
            bmm_fn=bmm_fn, moe_capacity_factor=1.0,
        )
        return out.sum(), dropped

    (total, dropped), grads = jax.value_and_grad(f, has_aux=True)(x)
    # capacity = ceil(32/4 * 1.0) = 8 rows/expert; expert 0 gets all 32
    # assignments -> 24 dropped
    assert float(dropped) == 24.0
    assert np.isfinite(float(total)) and np.all(np.isfinite(np.asarray(grads)))


@pytest.mark.slow
def test_aux_loss_near_topk_at_init():
    """Balanced routing at random init: f_e ~ top_k/E, P_e ~ 1/E, so the
    HF-scale aux E * sum(f_pooled * P_pooled) ~ top_k regardless of depth
    (stats pool across layers BEFORE the product, and each of the K
    selections per token is counted, like HF's load_balancing_loss_func
    whose coefficient the conversion imports verbatim)."""
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, (4, 32)))
    cfg = LlamaConfig(**TINY_MOE)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(1), ids)
    aux = float(jax.jit(model.apply)(params, ids).aux_loss)
    assert np.isfinite(aux)
    top_k = TINY_MOE["num_experts_per_tok"]
    assert 0.9 * top_k < aux < 1.6 * top_k


@pytest.mark.slow
def test_aux_loss_excludes_padding():
    """Router statistics must ignore padding tokens (segment id 0): the aux
    over a padded batch equals the aux over the unpadded rows."""
    rng = np.random.default_rng(2)
    ids = jnp.asarray(rng.integers(1, 128, (2, 24)))
    seg_full = jnp.ones((2, 24), jnp.int32)
    padded_ids = jnp.concatenate([ids, jnp.zeros((2, 8), jnp.int32)], axis=1)
    seg_padded = jnp.concatenate([seg_full, jnp.zeros((2, 8), jnp.int32)], axis=1)

    cfg = LlamaConfig(**TINY_MOE, moe_impl="dense")
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(2), ids)
    aux_ref = float(jax.jit(model.apply)(params, ids, segment_ids=seg_full).aux_loss)
    aux_pad = float(jax.jit(model.apply)(params, padded_ids, segment_ids=seg_padded).aux_loss)
    np.testing.assert_allclose(aux_pad, aux_ref, rtol=1e-5)


@pytest.mark.slow
def test_dense_model_has_no_aux():
    cfg = LlamaConfig(**{k: v for k, v in TINY_MOE.items()
                         if not k.startswith(("num_experts", "moe_"))})
    model = Llama(cfg)
    ids = jnp.ones((1, 8), jnp.int32)
    params = jax.jit(model.init)(jax.random.key(0), ids)
    assert jax.jit(model.apply)(params, ids).aux_loss is None


# ------------------------------------------------------------ HF parity


def _parity(hf_model, hf_config, seed):
    torch = pytest.importorskip("torch")
    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Llama(cfg)
    ids = np.random.default_rng(seed).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-4, atol=3e-4)
    return cfg, params, model


@pytest.mark.slow
def test_logits_parity_with_hf_mixtral():
    torch = pytest.importorskip("torch")
    from transformers import MixtralConfig, MixtralForCausalLM

    hf_config = MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4, num_experts_per_tok=2,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = MixtralForCausalLM(hf_config).eval()
    assert "model.layers.0.block_sparse_moe.experts.0.w1.weight" in hf_model.state_dict()
    cfg, _, _ = _parity(hf_model, hf_config, seed=20)
    assert cfg.moe_style == "mixtral" and cfg.norm_topk_prob


def test_logits_parity_with_hf_qwen2_moe():
    torch = pytest.importorskip("torch")
    from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM

    hf_config = Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=48, shared_expert_intermediate_size=80,
        norm_topk_prob=False,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = Qwen2MoeForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.shared_expert_gate.weight" in sd
    assert "model.layers.0.self_attn.q_proj.bias" in sd  # qwen2-style biases
    cfg, _, _ = _parity(hf_model, hf_config, seed=21)
    assert cfg.shared_expert_intermediate_size == 80
    assert cfg.attention_bias and not cfg.attention_out_bias


def test_logits_parity_with_hf_qwen3_moe():
    torch = pytest.importorskip("torch")
    from transformers import Qwen3MoeConfig, Qwen3MoeForCausalLM

    hf_config = Qwen3MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=48, norm_topk_prob=True,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = Qwen3MoeForCausalLM(hf_config).eval()
    cfg, _, _ = _parity(hf_model, hf_config, seed=22)
    assert cfg.qk_norm and cfg.norm_topk_prob


@pytest.mark.slow
def test_moe_export_round_trip(tmp_path):
    """Export our MoE tree -> transformers reloads it as Qwen3-MoE with
    matching logits (expert stacks unstack correctly in both directions)."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(**TINY_MOE, qk_norm=True, head_dim=16, moe_impl="dense")
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(23).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(3), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "Qwen3MoeForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-4, atol=3e-4)


def test_config_export_reimport_qwen2_moe_style():
    """config_to_hf emits attention_bias=None for the qwen2-style asymmetric
    bias layout; config_from_hf must re-import that as the hardcoded qwen2
    default instead of crashing on the explicit None."""
    from llm_training_tpu.models.llama.hf_conversion import config_to_hf

    cfg = LlamaConfig(
        **TINY_MOE, attention_bias=True, attention_out_bias=False,
        shared_expert_intermediate_size=80,
    )
    hf = config_to_hf(cfg)
    assert hf["model_type"] == "qwen2_moe" and hf["attention_bias"] is None
    back = config_from_hf(hf)
    assert back.attention_bias and not back.attention_out_bias
    assert back.num_experts == cfg.num_experts
    assert back.shared_expert_intermediate_size == 80


def test_hf_round_trip_state_dict():
    pytest.importorskip("torch")
    from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM

    hf_config = Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=48, shared_expert_intermediate_size=80,
    )
    import torch

    torch.manual_seed(1)
    hf_model = Qwen2MoeForCausalLM(hf_config).eval()
    cfg = config_from_hf(hf_config)
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    assert set(back) == set(sd)
    for key in sd:
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


# ------------------------------------------------------------ training


@pytest.mark.slow
def test_moe_trains_and_logs_aux(devices):
    """End-to-end fit on the CPU mesh: loss decreases, aux_loss is finite
    and reported, ragged impl under jit+grad+remat+scan."""
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.optim import OptimConfig
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    seen = {}

    class Capture:
        def on_step_end(self, trainer, step, metrics):
            seen[step] = {k: float(v) for k, v in metrics.items()
                          if k in ("loss", "aux_loss")}

    objective = CLM(
        CLMConfig(
            model=ModelProvider(
                model_class="Llama",
                model_kwargs=dict(
                    **{**TINY_MOE, "compute_dtype": "float32",
                       "param_dtype": "float32"},
                    moe_impl="ragged",
                    enable_gradient_checkpointing=True,
                ),
            ),
            optim=OptimConfig(learning_rate=3e-3, warmup_steps=2),
        )
    )
    # data vocab (16) << model vocab (128): initial loss ~ln(128) has clear
    # headroom above the ~ln(16) floor, so the decrease assertion is stable
    dm = DummyDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=32, num_samples=256, vocab_size=16))
    trainer = Trainer(
        TrainerConfig(max_steps=16, log_every_n_steps=4, mesh=MeshConfig()),
        callbacks=[Capture()],
    )
    trainer.fit(objective, dm)
    steps = sorted(seen)
    assert seen[steps[-1]]["loss"] < seen[steps[0]]["loss"]
    assert all(np.isfinite(m["aux_loss"]) for m in seen.values())


def test_logits_parity_with_hf_olmoe():
    """OLMoE routes to the Llama module: full-width qk-norm (pre-norm
    blocks, unlike OLMo-2), clip_qkv clamp, and qwen-style expert naming
    where HF's intermediate_size is the per-expert width."""
    torch = pytest.importorskip("torch")
    from transformers import OlmoeConfig, OlmoeForCausalLM

    hf_config = OlmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_experts=4, num_experts_per_tok=2,
        norm_topk_prob=False, clip_qkv=3.0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = OlmoeForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.experts.0.gate_proj.weight" in sd
    assert "model.layers.0.input_layernorm.weight" in sd  # pre-norm, not OLMo-2
    # full-width: the q norm spans all heads
    assert sd["model.layers.0.self_attn.q_norm.weight"].shape == (64,)
    cfg, _, _ = _parity(hf_model, hf_config, seed=22)
    assert cfg.qk_norm_scope == "full" and cfg.norm_scheme == "pre"
    assert cfg.moe_intermediate_size == 48 and cfg.clip_qkv == 3.0


def test_logits_parity_with_hf_flex_olmo():
    """FlexOlmo routes to the Llama module: OLMo-2 post-norm blocks +
    full-width qk-norm composed with the OLMoE-style sparse MoE (softmax
    top-k over qwen-named experts, intermediate_size = per-expert width)."""
    torch = pytest.importorskip("torch")
    from transformers import FlexOlmoConfig, FlexOlmoForCausalLM

    from llm_training_tpu.models.llama.hf_conversion import (
        config_from_hf,
        config_to_hf,
        params_from_hf,
    )

    hf_config = FlexOlmoConfig(
        vocab_size=128, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_experts=4, num_experts_per_tok=2,
        norm_topk_prob=False, pad_token_id=0, attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = FlexOlmoForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.post_feedforward_layernorm.weight" in sd
    assert "model.layers.0.self_attn.q_norm.weight" in sd
    assert "model.layers.0.mlp.experts.3.gate_proj.weight" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    assert cfg.norm_scheme == "post" and cfg.qk_norm_scope == "full"
    assert cfg.num_experts == 4 and cfg.moe_intermediate_size == 48
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(61).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)

    # export picks flex_olmo (post-norm), not olmoe
    out = config_to_hf(cfg)
    assert out["model_type"] == "flex_olmo"
    cfg2 = config_from_hf(out, compute_dtype="float32")
    assert cfg2.norm_scheme == "post" and cfg2.num_experts == 4


@pytest.mark.parametrize("shared", [False, True])
def test_logits_parity_with_hf_granitemoe(shared):
    """GraniteMoe routes to the Llama module: granite scalar multipliers +
    a PRE-stacked fused-expert MoE (input_linear [E, 2I, H], gate rows
    first; router under router.layer). Its softmax-after-topk routing is
    numerically identical to our softmax->topk->renormalize path. The
    shared variant adds an always-on (gate-free) shared MLP."""
    torch = pytest.importorskip("torch")
    if shared:
        from transformers import GraniteMoeSharedConfig as HFConfig
        from transformers import GraniteMoeSharedForCausalLM as HFModel
        extra = dict(shared_intermediate_size=40)
    else:
        from transformers import GraniteMoeConfig as HFConfig
        from transformers import GraniteMoeForCausalLM as HFModel
        extra = {}

    from llm_training_tpu.models.llama.hf_conversion import config_to_hf

    hf_config = HFConfig(
        vocab_size=128, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4, num_experts_per_tok=2,
        # non-identity multipliers so the granite scalars are LIVE
        embedding_multiplier=6.0, attention_multiplier=0.2,
        residual_multiplier=0.5, logits_scaling=2.0,
        attn_implementation="eager", **extra,
    )
    torch.manual_seed(0)
    hf_model = HFModel(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.block_sparse_moe.input_linear.weight" in sd
    assert "model.layers.0.block_sparse_moe.router.layer.weight" in sd
    if shared:
        assert "model.layers.0.shared_mlp.input_linear.weight" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32", moe_impl="dense")
    assert cfg.moe_style == "granite" and cfg.norm_topk_prob
    assert not cfg.shared_expert_gated
    assert cfg.attention_multiplier == 0.2 and cfg.residual_multiplier == 0.5
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(62).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=3e-4, atol=3e-4)

    out = config_to_hf(cfg)
    expected = "granitemoeshared" if shared else "granitemoe"
    assert out["model_type"] == expected
    assert out["attention_multiplier"] == 0.2
    cfg2 = config_from_hf(out, compute_dtype="float32", moe_impl="dense")
    # export emits head_dim explicitly (HF GraniteMoe derives it), so the
    # reimport carries the resolved value rather than None
    assert cfg2.resolved_head_dim == cfg.resolved_head_dim
    assert cfg2.model_dump() == {**cfg.model_dump(), "head_dim": cfg2.head_dim}


def test_granitemoe_state_dict_round_trip():
    """params -> HF -> params is exact through the fused-stack layout."""
    torch = pytest.importorskip("torch")
    from transformers import GraniteMoeSharedConfig, GraniteMoeSharedForCausalLM

    hf_config = GraniteMoeSharedConfig(
        vocab_size=128, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4, num_experts_per_tok=2,
        shared_intermediate_size=40, attn_implementation="eager",
    )
    torch.manual_seed(1)
    hf_model = GraniteMoeSharedForCausalLM(hf_config).eval()
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    assert set(back) == set(sd)
    for key in sd:
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


# ------------------------------------------------ the ragged path's combine
#
# `moe._combine` gathers the experts' sorted rows back into the assignments'
# order and sums a token's K of them in float32. Held against the formula it
# replaced, written out: a float32 scatter-add of the weighted rows at
# `token_order`.


def _combine_case(top_k, held, seed=0):
    """Seeded inputs of one combine: rows sorted by expert, the router's
    weights, and for a held share the mask of the assignments held here, with
    NaN planted in the rows of the others (what a grouped product may leave
    in rows of no group)."""
    tokens, hidden, experts = 24, 16, 12
    rng = np.random.default_rng(seed)
    picks = np.stack([rng.permutation(experts)[:top_k] for _ in range(tokens)]).astype(np.int32)
    weights = jnp.asarray(rng.random((tokens, top_k)) + 0.1, jnp.float32)
    order = jnp.argsort(jnp.asarray(picks).reshape(-1))
    ys = rng.standard_normal((tokens * top_k, hidden)).astype(np.float32)
    mine = None
    if held:
        mine = jnp.asarray(picks < experts - 4)  # the last four experts are held elsewhere
        ys[~np.asarray(mine).reshape(-1)[np.asarray(order)]] = np.nan
    return jnp.asarray(ys), order, weights, mine


def _scatter_add_combine(ys, order, weights, mine):
    """What `dropless_moe_apply` did until PR 50, in float32."""
    tokens, top_k = weights.shape
    ys = ys * weights.reshape(-1)[order][:, None]
    if mine is not None:
        ys = jnp.where(mine.reshape(-1)[order][:, None], ys, 0)
    token_order = (jnp.arange(tokens * top_k) // top_k)[order]
    return jnp.zeros((tokens, ys.shape[-1]), jnp.float32).at[token_order].add(ys)


def _row_scatter_adds(jaxpr, rows, hidden):
    """The scatter-adds of a jaxpr (inner ones included) whose updates are
    `[rows, hidden]`."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scatter-add" and eqn.invars[2].aval.shape == (rows, hidden):
            found.append(eqn)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            found += _row_scatter_adds(inner, rows, hidden)
    return found


@pytest.mark.parametrize("held", [False, True])
@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_combine_sums_the_gathered_rows_as_the_scatter_add_did(top_k, held):
    from llm_training_tpu.models.moe import _combine

    ys, order, weights, mine = _combine_case(top_k, held)
    new = jax.jit(lambda ys, w: _combine(ys, order, w, mine, jnp.float32))
    out = new(ys, weights)
    assert out.shape == (weights.shape[0], ys.shape[1]) and out.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(out)))  # selected away, not weighted by 0
    np.testing.assert_allclose(out, _scatter_add_combine(ys, order, weights, mine), rtol=1e-5, atol=1e-6)
    # one float32 sum a token, rounded once: bfloat16 rows come out within a
    # rounding of the float32 sum of those rows
    low = jnp.nan_to_num(ys).astype(jnp.bfloat16)
    exact = _scatter_add_combine(low.astype(jnp.float32), order, weights, mine)
    rounded = jax.jit(lambda ys, w: _combine(ys, order, w, mine, jnp.bfloat16))(low, weights)
    assert rounded.dtype == jnp.bfloat16
    np.testing.assert_allclose(rounded.astype(jnp.float32), exact, rtol=2.0**-8, atol=1e-6)
    assert not _row_scatter_adds(jax.make_jaxpr(new)(ys, weights).jaxpr, *ys.shape)


@pytest.mark.parametrize("held", [False, True])
@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_combine_gradients_are_the_scatter_adds_and_gather_both_ways(top_k, held):
    from llm_training_tpu.models.moe import _combine

    ys, order, weights, mine = _combine_case(top_k, held, seed=1)
    probe = jnp.asarray(np.random.default_rng(2).standard_normal((weights.shape[0], ys.shape[1])), jnp.float32)
    ys = jnp.nan_to_num(ys)  # a gradient through a NaN row is the select's business: held to zero below
    loss = lambda combine: lambda ys, w: jnp.sum(combine(ys, order, w, mine) * probe)
    new = jax.jit(jax.grad(loss(lambda *a: _combine(*a, jnp.float32)), argnums=(0, 1)))
    d_ys, d_w = new(ys, weights)
    want_ys, want_w = jax.jit(jax.grad(loss(_scatter_add_combine), argnums=(0, 1)))(ys, weights)
    np.testing.assert_allclose(d_ys, want_ys, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(d_w, want_w, rtol=1e-5, atol=1e-6)
    if held:  # nothing flows into the rows held elsewhere
        elsewhere = ~np.asarray(mine).reshape(-1)[np.asarray(order)]
        assert elsewhere.any() and not np.asarray(d_ys)[elsewhere].any()
    # the backward pass did not become the scatter the forward lost, and its
    # gather stands under the scope the forward's does (a `custom_vjp` keeps
    # the scope it is called under: `transpose(jvp(moe_scatter))`)
    assert not _row_scatter_adds(jax.make_jaxpr(new)(ys, weights).jaxpr, *ys.shape)
    names = re.findall(r'loc\("([^"]*/gather)"', new.lower(ys, weights).as_text(debug_info=True))
    assert {name.split("/")[-2] for name in names} == {"jvp(moe_scatter)", "transpose(jvp(moe_scatter))"}
    assert _row_scatter_adds(  # the probe finds one where there is one: a plain gather's transpose
        jax.make_jaxpr(jax.grad(lambda ys: jnp.sum(ys[jnp.argsort(order)] * 2.0)))(ys).jaxpr, *ys.shape
    )
