"""Llama model: shapes, scan/remat invariance, tied embeddings, and logits
parity against HF transformers' torch implementation on a tiny config."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models import Llama, LlamaConfig
from llm_training_tpu.models.llama.hf_conversion import (
    config_from_hf,
    config_to_hf,
    params_from_hf,
    params_to_hf,
)

TINY = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=112,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=64,
    compute_dtype="float32",
)


def _init_and_run(cfg, ids, **kwargs):
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(0), ids)
    return jax.jit(model.apply)(params, ids, **kwargs), params


@pytest.mark.slow
def test_forward_shapes_and_dtypes():
    cfg = LlamaConfig(**TINY)
    ids = jnp.ones((2, 10), jnp.int32)
    out, _ = _init_and_run(cfg, ids, return_last_hidden_states=True)
    assert out.logits.shape == (2, 10, 128)
    assert out.last_hidden_states.shape == (2, 10, 64)


@pytest.mark.slow
def test_hidden_only_forward():
    cfg = LlamaConfig(**TINY)
    ids = jnp.ones((2, 10), jnp.int32)
    out, _ = _init_and_run(cfg, ids, compute_logits=False, return_last_hidden_states=True)
    assert out.logits is None
    assert out.last_hidden_states is not None


@pytest.mark.slow
def test_scan_and_loop_layers_agree():
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 12)))
    cfg_scan = LlamaConfig(**TINY, scan_layers=True)
    model_scan = Llama(cfg_scan)
    params_scan = jax.jit(model_scan.init)(jax.random.key(0), ids)

    # restack scanned params into per-layer trees for the loop model
    hf_sd = params_to_hf(jax.tree.map(lambda x: x, params_scan["params"]), cfg_scan)
    cfg_loop = LlamaConfig(**TINY, scan_layers=False)
    params_loop = params_from_hf(hf_sd, cfg_loop)

    out_scan = jax.jit(model_scan.apply)(params_scan, ids)
    out_loop = Llama(cfg_loop).apply(params_loop, ids)
    np.testing.assert_allclose(out_scan.logits, out_loop.logits, rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("granularity", ["full", "selective"])
@pytest.mark.slow
def test_remat_matches_no_remat(granularity):
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 128, (1, 8)))
    cfg = LlamaConfig(**TINY)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(0), ids)

    cfg_remat = LlamaConfig(
        **TINY, enable_gradient_checkpointing=True, recompute_granularity=granularity
    )
    model_remat = Llama(cfg_remat)

    def loss(m, p):
        return m.apply(p, ids).logits.astype(jnp.float32).sum()

    np.testing.assert_allclose(loss(model, params), loss(model_remat, params), rtol=1e-6)
    g1 = jax.grad(lambda p: loss(model, p))(params)
    g2 = jax.grad(lambda p: loss(model_remat, p))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5), g1, g2
    )


@pytest.mark.slow
def test_tied_embeddings():
    cfg = LlamaConfig(**{**TINY, "tie_word_embeddings": True})
    ids = jnp.ones((1, 4), jnp.int32)
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(0), ids)
    flat = jax.tree_util.tree_leaves_with_path(params)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    assert not any("lm_head" in n for n in names)
    out = jax.jit(model.apply)(params, ids)
    assert out.logits.shape == (1, 4, 128)


@pytest.mark.slow
def test_packed_forward_matches_separate_docs():
    """End-to-end (full model) packing parity: one packed row with segment ids
    == two separate unpadded forwards."""
    rng = np.random.default_rng(2)
    cfg = LlamaConfig(**TINY)
    model = Llama(cfg)
    doc_a = rng.integers(1, 128, 5)
    doc_b = rng.integers(1, 128, 7)
    packed = jnp.asarray(np.concatenate([doc_a, doc_b])[None])
    segment_ids = jnp.asarray([[1] * 5 + [2] * 7])
    position_ids = jnp.asarray([list(range(5)) + list(range(7))])
    params = jax.jit(model.init)(jax.random.key(0), packed)

    out = jax.jit(model.apply)(params, packed, segment_ids=segment_ids, position_ids=position_ids)
    out_a = jax.jit(model.apply)(params, jnp.asarray(doc_a[None]))
    out_b = jax.jit(model.apply)(params, jnp.asarray(doc_b[None]))
    np.testing.assert_allclose(out.logits[0, :5], out_a.logits[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(out.logits[0, 5:], out_b.logits[0], rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------- HF parity


def _hf_tiny_llama(rope_scaling=None, tie=False):
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig as HFLlamaConfig, LlamaForCausalLM

    hf_config = HFLlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=112,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64 if rope_scaling is None else 131072,
        rope_scaling=rope_scaling,
        tie_word_embeddings=tie,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    return LlamaForCausalLM(hf_config).eval(), hf_config


@pytest.mark.parametrize(
    "rope_scaling",
    [
        None,
        {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
         "high_freq_factor": 4.0, "original_max_position_embeddings": 8192},
    ],
)
def test_logits_parity_with_hf(rope_scaling):
    torch = pytest.importorskip("torch")
    hf_model, hf_config = _hf_tiny_llama(rope_scaling)
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.rope_config.type == ("default" if rope_scaling is None else "llama3")

    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(3).integers(0, 128, (2, 16))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_hf_round_trip():
    hf_model, hf_config = _hf_tiny_llama()
    cfg = config_from_hf(hf_config)
    params = params_from_hf(hf_model.state_dict(), cfg)
    back = params_to_hf(params, cfg)
    sd = {k: v.detach().numpy() for k, v in hf_model.state_dict().items()}
    assert set(back) == set(sd)
    for key in sd:
        np.testing.assert_array_equal(back[key], sd[key], err_msg=key)


# -------------------------------------------- sibling architectures (routing)


def test_logits_parity_with_hf_mistral():
    """Mistral routes to the Llama module (sliding window + GQA + SwiGLU)."""
    torch = pytest.importorskip("torch")
    from transformers import MistralConfig, MistralForCausalLM

    hf_config = MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8,
    )
    torch.manual_seed(0)
    hf_model = MistralForCausalLM(hf_config).eval()
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.sliding_window == 8
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(4).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_qwen2():
    """Qwen2 routes to the Llama module; its q/k/v projections carry biases
    while o_proj does not — the asymmetry must survive conversion."""
    torch = pytest.importorskip("torch")
    from transformers import Qwen2Config, Qwen2ForCausalLM

    hf_config = Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64,
    )
    torch.manual_seed(0)
    hf_model = Qwen2ForCausalLM(hf_config).eval()
    # qwen2 really has the asymmetric bias layout
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.q_proj.bias" in sd
    assert "model.layers.0.self_attn.o_proj.bias" not in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(5).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_qwen3():
    """Qwen3 routes to the Llama module; its per-head q/k RMSNorm (over
    head_dim, before RoPE) must be applied and its weights converted."""
    torch = pytest.importorskip("torch")
    from transformers import Qwen3Config, Qwen3ForCausalLM

    hf_config = Qwen3Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = Qwen3ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.q_norm.weight" in sd
    assert "model.layers.0.self_attn.q_proj.bias" not in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.qk_norm and not cfg.attention_bias
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(6).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_olmo2():
    """OLMo-2 routes to the Llama module with post-norm blocks (no input
    norms; block outputs normed into the residual) and a FULL-width qk-norm
    applied before the head reshape."""
    torch = pytest.importorskip("torch")
    from transformers import Olmo2Config, Olmo2ForCausalLM

    hf_config = Olmo2Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = Olmo2ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.q_norm.weight" in sd
    assert "model.layers.0.post_feedforward_layernorm.weight" in sd
    assert "model.layers.0.input_layernorm.weight" not in sd
    # full-width: the norm spans all heads, not one head_dim
    assert sd["model.layers.0.self_attn.q_norm.weight"].shape == (64,)

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_scheme == "post" and cfg.qk_norm_scope == "full"
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(12).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_qwen3_export_round_trip(tmp_path):
    """Export a qk_norm model -> HF reloads it as Qwen3 with matching
    logits (the norm weights must survive both directions)."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(**TINY, qk_norm=True, head_dim=16)
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(11).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(2), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(out_dir, attn_implementation="eager").eval()
    assert type(hf_model).__name__ == "Qwen3ForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_qwen2_export_round_trip(tmp_path):
    """Exporting a Qwen2-derived config must produce a checkpoint that
    transformers loads with NO missing keys (asymmetric bias preserved)."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM, Qwen2Config, Qwen2ForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    hf_config = Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64,
    )
    torch.manual_seed(0)
    hf_model = Qwen2ForCausalLM(hf_config).eval()
    cfg = config_from_hf(hf_config, compute_dtype="float32")
    params = params_from_hf(hf_model.state_dict(), cfg)

    out = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")
    reloaded = AutoModelForCausalLM.from_pretrained(out).eval()
    assert reloaded.config.model_type == "qwen2"

    ids = np.random.default_rng(6).integers(0, 128, (1, 16))
    with torch.no_grad():
        a = hf_model(torch.tensor(ids)).logits.numpy()
        b = reloaded(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5)


def test_logits_parity_with_hf_granite():
    """Granite routes to the Llama module with four scalar multipliers:
    embeddings scaled into the residual stream, a config attention scale
    replacing 1/sqrt(head_dim), block outputs scaled before the residual
    add, and logits divided by logits_scaling."""
    torch = pytest.importorskip("torch")
    from transformers import GraniteConfig, GraniteForCausalLM

    hf_config = GraniteConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64,
        embedding_multiplier=12.0, attention_multiplier=0.12,
        residual_multiplier=0.22, logits_scaling=6.0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = GraniteForCausalLM(hf_config).eval()

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.embedding_multiplier == 12.0
    assert cfg.attention_multiplier == 0.12
    assert cfg.residual_multiplier == 0.22
    assert cfg.logits_scaling == 6.0
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(13).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_granite_export_round_trip(tmp_path):
    """A config with non-identity multipliers must export as Granite and
    reload in transformers with matching logits (multipliers live only in
    config.json — the weights are plain Llama)."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **TINY, embedding_multiplier=12.0, attention_multiplier=0.12,
        residual_multiplier=0.22, logits_scaling=6.0,
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(14).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(3), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "GraniteForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_starcoder2():
    """Starcoder2 routes to the Llama module with biased LayerNorm blocks,
    biased q/k/v/o projections, and a non-gated c_fc -> gelu_tanh -> c_proj
    MLP; HF's use_bias covers attention and MLP together and norm_epsilon is
    the LayerNorm eps."""
    torch = pytest.importorskip("torch")
    from transformers import Starcoder2Config, Starcoder2ForCausalLM

    hf_config = Starcoder2Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, use_bias=True, norm_epsilon=1e-5,
        sliding_window=8, tie_word_embeddings=True,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = Starcoder2ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.c_fc.bias" in sd
    assert "model.layers.0.input_layernorm.bias" in sd
    assert "model.norm.bias" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_type == "layernorm" and cfg.mlp_type == "gelu"
    assert cfg.attention_bias and cfg.attention_out_bias and cfg.mlp_bias
    assert cfg.sliding_window == 8
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    # 24 > sliding_window so local attention actually truncates
    ids = np.random.default_rng(15).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_starcoder2_export_round_trip(tmp_path):
    """A layernorm+gelu config must export as Starcoder2 and reload in
    transformers with NO missing keys and matching logits."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **TINY, norm_type="layernorm", mlp_type="gelu",
        attention_bias=True, mlp_bias=True, tie_word_embeddings=True,
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(16).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(4), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "Starcoder2ForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("use_qk_norm", [False, True])
def test_logits_parity_with_hf_cohere(use_qk_norm):
    """Cohere (Command R) routes to the Llama module: a single mean-centered
    weight-only input norm feeding attention AND mlp in parallel, interleaved
    (GPT-J) rope pairing, always-tied embeddings, a multiplicative
    logit_scale, and (Command R+) a per-head-weighted qk-norm."""
    torch = pytest.importorskip("torch")
    from transformers import CohereConfig, CohereForCausalLM

    hf_config = CohereConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, logit_scale=0.125,
        layer_norm_eps=1e-5, use_qk_norm=use_qk_norm,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = CohereForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.post_attention_layernorm.weight" not in sd
    if use_qk_norm:
        assert sd["model.layers.0.self_attn.q_norm.weight"].shape == (4, 16)

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_scheme == "parallel" and cfg.norm_type == "layernorm_nobias"
    assert cfg.rope_interleaved and cfg.logit_scale == 0.125
    assert cfg.qk_norm == use_qk_norm
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(17).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_unexportable_combos_raise():
    """Feature combinations no HF architecture represents must fail at
    export instead of silently falling through to a plain-llama config that
    reloads with random-initialized modules."""
    import pytest as _pytest

    from llm_training_tpu.models.llama.hf_conversion import config_to_hf

    with _pytest.raises(ValueError, match="Starcoder2"):
        config_to_hf(LlamaConfig(**TINY, mlp_type="gelu"))  # gelu w/o layernorm
    with _pytest.raises(ValueError, match="use_bias"):
        config_to_hf(LlamaConfig(
            **TINY, norm_type="layernorm", mlp_type="gelu",
            attention_bias=True, mlp_bias=False,
        ))
    with _pytest.raises(ValueError, match="clip_qkv"):
        config_to_hf(LlamaConfig(**TINY, clip_qkv=3.0))  # dense, no OLMoE home
    # a cohere-graph config with layer_types but rope on EVERY layer must
    # refuse the cohere2 export (the HF module derives NoPE on full layers)
    with _pytest.raises(ValueError, match="layer_types"):
        config_to_hf(LlamaConfig(
            **{**TINY, "num_hidden_layers": 2, "scan_layers": False},
            norm_scheme="parallel", norm_type="layernorm_nobias",
            rope_interleaved=True, sliding_window=8,
            layer_types=["sliding_attention", "full_attention"],
        ))


def test_logits_parity_with_hf_phi():
    """Phi-1/1.5/2 routes to the Llama module: parallel blocks under one
    biased LayerNorm, partial rotary (tables span factor*head_dim), biased
    everything including the untied lm_head, and HF's dense/fc1/fc2/
    final_layernorm key naming."""
    torch = pytest.importorskip("torch")
    from transformers import PhiConfig, PhiForCausalLM

    hf_config = PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, partial_rotary_factor=0.5,
        resid_pdrop=0.0, embd_pdrop=0.0, attention_dropout=0.0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = PhiForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.dense.bias" in sd
    assert "model.layers.0.mlp.fc1.weight" in sd
    assert "model.final_layernorm.bias" in sd
    assert "lm_head.bias" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_scheme == "parallel" and cfg.norm_type == "layernorm"
    assert cfg.partial_rotary_factor == 0.5 and cfg.lm_head_bias
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(18).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_phi_export_round_trip(tmp_path):
    """Export a phi-graph config -> transformers reloads it as Phi with NO
    missing keys (renamed dense/fc1/fc2/final_layernorm + lm_head.bias all
    present) and matching logits."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **TINY, norm_scheme="parallel", norm_type="layernorm", mlp_type="gelu",
        attention_bias=True, mlp_bias=True, lm_head_bias=True,
        partial_rotary_factor=0.5,
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(19).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(5), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "PhiForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("cls_name", ["Glm", "Glm4"])
def test_logits_parity_with_hf_glm(cls_name):
    """GLM / GLM-4 route to the Llama module: interleaved partial rotary
    (factor 0.5), q/k/v biases with no o_proj bias, a fused gate_up_proj
    split at the conversion boundary, and (GLM-4) sandwich norms — input
    AND output norms around both blocks."""
    torch = pytest.importorskip("torch")
    import transformers

    config_cls = getattr(transformers, cls_name + "Config")
    model_cls = getattr(transformers, cls_name + "ForCausalLM")
    hf_config = config_cls(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, partial_rotary_factor=0.5, max_position_embeddings=64,
        attention_bias=True, pad_token_id=0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = model_cls(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.gate_up_proj.weight" in sd
    assert "model.layers.0.self_attn.q_proj.bias" in sd
    assert "model.layers.0.self_attn.o_proj.bias" not in sd
    if cls_name == "Glm4":
        assert "model.layers.0.post_self_attn_layernorm.weight" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.rope_interleaved and cfg.partial_rotary_factor == 0.5
    assert cfg.norm_scheme == ("sandwich" if cls_name == "Glm4" else "pre")
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(40).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_glm4_export_round_trip(tmp_path):
    """A sandwich + interleaved config exports as GLM-4 and reloads in
    transformers with NO missing keys (re-fused gate_up) and matching
    logits."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **TINY, norm_scheme="sandwich", rope_interleaved=True, head_dim=16,
        fused_gate_up=True, partial_rotary_factor=0.5, attention_bias=True,
        attention_out_bias=False, pad_token_id=0,
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(41).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(9), ids)
    # zero-init biases would mask a bias-dropping export: randomize them
    import flax.linen as fnn

    def salt_biases(path, leaf):
        if path[-1].key == "bias":
            value = leaf.value if isinstance(leaf, fnn.Partitioned) else leaf
            noise = jnp.asarray(
                np.random.default_rng(len(str(path))).normal(0, 0.1, value.shape),
                value.dtype,
            )
            return leaf.replace_boxed(noise) if isinstance(leaf, fnn.Partitioned) else noise
        return leaf
    params = jax.tree_util.tree_map_with_path(
        salt_biases, params, is_leaf=lambda x: isinstance(x, fnn.Partitioned)
    )
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "Glm4ForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_nemotron():
    """Nemotron routes to the Llama module: zero-centered (1+w) biased
    LayerNorm blocks, a non-gated up -> relu^2 -> down MLP, and partial
    rotary."""
    torch = pytest.importorskip("torch")
    from transformers import NemotronConfig, NemotronForCausalLM

    hf_config = NemotronConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, partial_rotary_factor=0.5, norm_eps=1e-5,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = NemotronForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.up_proj.weight" in sd
    assert "model.layers.0.mlp.gate_proj.weight" not in sd
    assert "model.layers.0.input_layernorm.bias" in sd
    # salt the zero-init norm weights so the (1 + w) convention is LIVE:
    # a plain-LayerNorm misread would pass with w == 0
    with torch.no_grad():
        for k, v in sd.items():
            if "layernorm.weight" in k or k == "model.norm.weight":
                v.copy_(torch.linspace(-0.2, 0.2, v.numel()))

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_type == "layernorm1p" and cfg.mlp_type == "relu2"
    assert cfg.partial_rotary_factor == 0.5
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(42).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_nemotron_export_round_trip(tmp_path):
    """A layernorm1p + relu2 config exports as Nemotron and reloads in
    transformers with NO missing keys and matching logits."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **TINY, norm_type="layernorm1p", mlp_type="relu2", head_dim=16,
        partial_rotary_factor=0.5,
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(43).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(12), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "NemotronForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_ernie45():
    """Ernie 4.5 routes to the Llama module: plain llama weights with
    GLM-style interleaved full-dim rope."""
    torch = pytest.importorskip("torch")
    from transformers import Ernie4_5Config, Ernie4_5ForCausalLM

    hf_config = Ernie4_5Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64, use_bias=True,
        tie_word_embeddings=True,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = Ernie4_5ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.gate_proj.weight" in sd  # NOT fused
    assert "model.layers.0.self_attn.o_proj.bias" in sd  # use_bias covers o

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.rope_interleaved and not cfg.fused_gate_up
    assert cfg.attention_bias and cfg.attention_out_bias
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(44).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_hunyuan():
    """HunYuan dense routes to the Llama module: per-head qk-norm applied
    AFTER rotary (query_layernorm/key_layernorm HF names)."""
    torch = pytest.importorskip("torch")
    from transformers import HunYuanDenseV1Config, HunYuanDenseV1ForCausalLM

    hf_config = HunYuanDenseV1Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = HunYuanDenseV1ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.query_layernorm.weight" in sd
    # salt the norm weights: pre- vs post-rope ordering only shows when the
    # norm is NOT a no-op... (ones-init RMS weights still rescale rows, but
    # make them asymmetric to be safe)
    with torch.no_grad():
        for k, v in sd.items():
            if "layernorm.weight" in k and "self_attn" in k:
                v.copy_(torch.linspace(0.5, 1.5, v.numel()))

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.qk_norm and cfg.qk_norm_position == "post_rope"
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(45).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_gpt2():
    """GPT-2 routes to the Llama module: learned wpe positions (no rope),
    biased LayerNorm + gelu MLP, fused Conv1D c_attn split into q/k/v at
    the conversion boundary (Conv1D stores [in, out] — no transposes)."""
    torch = pytest.importorskip("torch")
    from transformers import GPT2Config, GPT2LMHeadModel

    hf_config = GPT2Config(
        vocab_size=128, n_embd=64, n_inner=112, n_layer=2, n_head=4,
        n_positions=64, embd_pdrop=0.0, attn_pdrop=0.0, resid_pdrop=0.0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = GPT2LMHeadModel(hf_config).eval()
    sd = hf_model.state_dict()
    assert "transformer.wpe.weight" in sd
    assert sd["transformer.h.0.attn.c_attn.weight"].shape == (64, 192)

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.position_embedding_type == "learned" and cfg.tie_word_embeddings
    assert cfg.intermediate_size == 112 and cfg.num_key_value_heads == 4
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(46).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_gpt2_export_round_trip(tmp_path):
    """A learned-positions config exports as GPT-2 and reloads in
    transformers with NO missing keys (re-fused c_attn) and matching
    logits."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=64, compute_dtype="float32",
        position_embedding_type="learned", norm_type="layernorm",
        mlp_type="gelu", attention_bias=True, mlp_bias=True,
        tie_word_embeddings=True, scan_layers=False,
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(47).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(13), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "GPT2LMHeadModel"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_smollm3():
    """SmolLM3 routes to the Llama module: a plain llama graph with
    per-layer NoPE (every 4th layer skips rotary; NoPE layers rotate with
    identity tables so the layer body stays uniform)."""
    torch = pytest.importorskip("torch")
    from transformers import SmolLM3Config, SmolLM3ForCausalLM

    hf_config = SmolLM3Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, pad_token_id=0,
        attn_implementation="eager",
    )
    assert hf_config.no_rope_layers == [1, 1, 1, 0]
    torch.manual_seed(0)
    hf_model = SmolLM3ForCausalLM(hf_config).eval()

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.no_rope_layers == [1, 1, 1, 0] and not cfg.scan_layers
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(48).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_olmo3():
    """OLMo-3 routes to the Llama module: OLMo-2's post-norm + full qk-norm
    plus a per-layer sliding/full pattern with DUAL rope tables — sliding
    layers rotate unscaled, full layers with the configured rope_scaling."""
    torch = pytest.importorskip("torch")
    from transformers import Olmo3Config, Olmo3ForCausalLM

    hf_config = Olmo3Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8,
        rope_scaling={"rope_type": "yarn", "factor": 4.0},
        attn_implementation="eager",
    )
    assert hf_config.layer_types == [
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention",
    ]
    torch.manual_seed(0)
    hf_model = Olmo3ForCausalLM(hf_config).eval()

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_scheme == "post" and cfg.qk_norm_scope == "full"
    assert cfg.layer_sliding_window(0) == 8 and cfg.layer_sliding_window(3) is None
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Llama(cfg)

    # 24 > sliding_window so local attention truncates, and yarn is live on
    # the full layer only
    ids = np.random.default_rng(49).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_ministral():
    """Ministral routes to the Llama module: mistral weights with an
    explicit per-layer sliding/full `layer_types` pattern, rotated by ONE
    rope table (unlike OLMo-3's dual-table variant)."""
    torch = pytest.importorskip("torch")
    from transformers import MinistralConfig, MinistralForCausalLM

    hf_config = MinistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8, head_dim=16,
        layer_types=["sliding_attention", "full_attention"] * 2,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = MinistralForCausalLM(hf_config).eval()

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.layer_types == ["sliding_attention", "full_attention"] * 2
    assert not cfg.dual_local_rope
    params = params_from_hf(hf_model.state_dict(), cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(50).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_helium():
    """Helium routes to the Llama module: plain llama graph (o_proj bias
    hardcoded off even when attention_bias is on)."""
    torch = pytest.importorskip("torch")
    from transformers import HeliumConfig, HeliumForCausalLM

    hf_config = HeliumConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, attention_bias=True, head_dim=16,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = HeliumForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.q_proj.bias" in sd
    assert "model.layers.0.self_attn.o_proj.bias" not in sd
    # salt the zero-init biases: a bias-dropping conversion would pass
    # with fresh zeros
    with torch.no_grad():
        for k, v in sd.items():
            if k.endswith(".bias"):
                v.copy_(torch.linspace(-0.2, 0.2, v.numel()))

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.attention_bias and not cfg.attention_out_bias
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(51).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_arcee():
    """Arcee routes to the Llama module: the Nemotron-style non-gated
    up -> relu^2 -> down MLP under standard RMSNorm pre-norm blocks."""
    torch = pytest.importorskip("torch")
    from transformers import ArceeConfig, ArceeForCausalLM

    hf_config = ArceeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, head_dim=16,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = ArceeForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.up_proj.weight" in sd
    assert "model.layers.0.mlp.gate_proj.weight" not in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.mlp_type == "relu2" and cfg.norm_type == "rmsnorm"
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(52).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_logits_parity_with_hf_seed_oss():
    """Seed-OSS routes to the Llama module: qkv biases with a SEPARATE
    o_proj bias flag; nonzero residual_dropout is refused at import."""
    torch = pytest.importorskip("torch")
    from transformers import SeedOssConfig, SeedOssForCausalLM

    hf_config = SeedOssConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, head_dim=16,
        attention_bias=True, attention_out_bias=False, residual_dropout=0.0,
        attention_dropout=0.0, attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = SeedOssForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.self_attn.q_proj.bias" in sd
    assert "model.layers.0.self_attn.o_proj.bias" not in sd
    with torch.no_grad():
        for k, v in sd.items():
            if k.endswith(".bias"):
                v.copy_(torch.linspace(-0.2, 0.2, v.numel()))

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.attention_bias and not cfg.attention_out_bias
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(53).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)

    with pytest.raises(ValueError, match="residual_dropout"):
        config_from_hf({**hf_config.to_dict(), "residual_dropout": 0.1})


def test_logits_parity_with_hf_stablelm():
    """StableLM routes to the Llama module: biased LayerNorm pre-norm
    blocks with a SWIGLU MLP, partial rotary 0.25, optional qkv biases
    (o_proj hardcoded bias-free)."""
    torch = pytest.importorskip("torch")
    from transformers import StableLmConfig, StableLmForCausalLM

    hf_config = StableLmConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, use_qkv_bias=True,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = StableLmForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.input_layernorm.bias" in sd
    assert "model.layers.0.self_attn.q_proj.bias" in sd
    assert "model.layers.0.self_attn.o_proj.bias" not in sd
    assert "model.layers.0.mlp.gate_proj.weight" in sd
    # salt zero-init biases so a bias-dropping conversion cannot pass
    with torch.no_grad():
        for k, v in sd.items():
            if k.endswith(".bias"):
                v.copy_(torch.linspace(-0.2, 0.2, v.numel()))

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_type == "layernorm" and cfg.mlp_type == "swiglu"
    assert cfg.partial_rotary_factor == 0.25
    assert cfg.attention_bias and not cfg.attention_out_bias
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(54).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)

    # export picks stablelm; round trip preserves the graph knobs
    out = config_to_hf(cfg)
    assert out["model_type"] == "stablelm" and out["use_qkv_bias"]
    cfg2 = config_from_hf(out, compute_dtype="float32")
    assert cfg2.norm_type == "layernorm" and cfg2.partial_rotary_factor == 0.25

    with pytest.raises(ValueError, match="parallel_residual"):
        config_from_hf({**hf_config.to_dict(), "use_parallel_residual": True})


def test_logits_parity_with_hf_exaone4():
    """EXAONE-4 routes to the Llama module: OLMo-2-style post-norm blocks,
    per-head (qwen3-style) qk-norm, a 3:1 sliding/full hybrid pattern where
    FULL-attention layers are NoPE (sliding layers rotate) — composed from
    norm_scheme='post' + qk_norm head + layer_types + derived
    no_rope_layers."""
    torch = pytest.importorskip("torch")
    from transformers import Exaone4Config, Exaone4ForCausalLM

    hf_config = Exaone4Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8,
        sliding_window_pattern=4,  # every 4th layer is global attention
        attn_implementation="eager",
    )
    assert hf_config.layer_types == [
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention",
    ]
    torch.manual_seed(0)
    hf_model = Exaone4ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.post_feedforward_layernorm.weight" in sd
    assert "model.layers.0.self_attn.q_norm.weight" in sd
    assert "model.layers.0.input_layernorm.weight" not in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_scheme == "post" and cfg.qk_norm_scope == "head"
    assert cfg.no_rope_layers == [1, 1, 1, 0]  # full layer is NoPE
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(55).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)

    out = config_to_hf(cfg)
    assert out["model_type"] == "exaone4"
    cfg2 = config_from_hf(out, compute_dtype="float32")
    assert cfg2.layer_types == cfg.layer_types
    assert cfg2.no_rope_layers == cfg.no_rope_layers


def test_logits_parity_with_hf_apertus():
    """Apertus routes to the Llama module: non-gated up -> xIELU -> down MLP
    whose activation carries two LEARNABLE scalars per layer (stored as
    softplus pre-images under mlp.act_fn), plus qwen3-style per-head
    qk-norm. The scalars are salted so a conversion that dropped or
    misread them cannot pass."""
    torch = pytest.importorskip("torch")
    from transformers import ApertusConfig, ApertusForCausalLM

    hf_config = ApertusConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = ApertusForCausalLM(hf_config).eval().float()
    sd = hf_model.state_dict()
    assert "model.layers.0.mlp.act_fn.alpha_p" in sd
    assert "model.layers.0.mlp.gate_proj.weight" not in sd
    assert "model.layers.0.self_attn.q_norm.weight" in sd
    with torch.no_grad():  # make the learnable activation scalars LIVE
        sd["model.layers.0.mlp.act_fn.alpha_p"].copy_(torch.tensor([1.3]))
        sd["model.layers.1.mlp.act_fn.alpha_n"].copy_(torch.tensor([-0.4]))

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.mlp_type == "xielu" and cfg.qk_norm_scope == "head"
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(56).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)

    out = config_to_hf(cfg)
    assert out["model_type"] == "apertus" and out["hidden_act"] == "xielu"
    cfg2 = config_from_hf(out, compute_dtype="float32")
    assert cfg2.mlp_type == "xielu"


@pytest.mark.slow
def test_logits_parity_with_hf_cohere2():
    """Cohere2 (Command R7B) = the Cohere graph + a sliding/full layer
    pattern where full-attention layers skip rope entirely (derived NoPE,
    like EXAONE-4) — routed to the looped Llama path via layer_types +
    no_rope_layers."""
    torch = pytest.importorskip("torch")
    from transformers import Cohere2Config, Cohere2ForCausalLM

    hf_config = Cohere2Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, logit_scale=0.125,
        layer_norm_eps=1e-5, sliding_window=8, sliding_window_pattern=2,
        layer_types=["sliding_attention", "full_attention"] * 2,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = Cohere2ForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_scheme == "parallel" and cfg.norm_type == "layernorm_nobias"
    assert cfg.rope_interleaved and cfg.logit_scale == 0.125
    assert cfg.layer_types == [
        "sliding_attention", "full_attention",
        "sliding_attention", "full_attention",
    ]
    assert cfg.no_rope_layers == [1, 0, 1, 0]  # full layers are NoPE
    assert not cfg.scan_layers  # per-layer patterns loop
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    # 24 > sliding_window so local attention actually truncates
    ids = np.random.default_rng(18).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_cohere2_export_round_trip(tmp_path):
    """A parallel-block weight-only-LayerNorm config WITH a sliding/full
    pattern must export as Cohere2 and reload in transformers with matching
    logits."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **{**TINY, "num_hidden_layers": 2, "scan_layers": False},
        norm_scheme="parallel", norm_type="layernorm_nobias",
        rope_interleaved=True, logit_scale=0.125,
        tie_word_embeddings=True, sliding_window=8,
        layer_types=["sliding_attention", "full_attention"],
        no_rope_layers=[1, 0],
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(19).integers(0, 128, (2, 24)))
    params = jax.jit(model.init)(jax.random.key(5), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "Cohere2ForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_logits_parity_with_hf_phimoe():
    """Phi-3.5-MoE routes to the Llama module + MoEMLP: mixtral expert
    naming, biased LayerNorms, attention/lm_head biases, and SparseMixer
    routing — sequential argmax picks weighted by a band-masked softmax,
    weights NOT renormalized across the two picks (models/moe.py:
    sparsemixer_topk matches HF's eval-mode sparsemixer exactly)."""
    torch = pytest.importorskip("torch")
    from transformers import PhimoeConfig, PhimoeForCausalLM

    hf_config = PhimoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, num_local_experts=4,
        num_experts_per_tok=2, rms_norm_eps=1e-5,
        attention_bias=True, lm_head_bias=True,
        router_jitter_noise=0.01, input_jitter_noise=0.0,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = PhimoeForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "model.layers.0.block_sparse_moe.experts.0.w1.weight" in sd
    assert "model.layers.0.input_layernorm.bias" in sd
    assert "lm_head.bias" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_type == "layernorm" and cfg.moe_style == "mixtral"
    assert cfg.moe_router_impl == "sparsemixer" and not cfg.norm_topk_prob
    assert cfg.attention_bias and cfg.lm_head_bias
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(20).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_cohere2_imports_r7b_style_raw_config():
    """The published Command R7B config.json predates layer_types (it
    carries sliding_window_pattern=4 only) and arrives as a raw dict —
    the pattern must resolve to the derived sliding/full list + NoPE."""
    raw = dict(
        model_type="cohere2", vocab_size=128, hidden_size=64,
        intermediate_size=112, num_hidden_layers=8, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64,
        logit_scale=0.125, layer_norm_eps=1e-5, sliding_window=8,
        sliding_window_pattern=4, rope_theta=50000.0,
    )
    cfg = config_from_hf(raw, compute_dtype="float32")
    assert cfg.layer_types == (
        ["sliding_attention"] * 3 + ["full_attention"]
    ) * 2
    assert cfg.no_rope_layers == [1, 1, 1, 0] * 2
    assert cfg.sliding_window == 8 and not cfg.scan_layers


@pytest.mark.slow
def test_phimoe_export_round_trip(tmp_path):
    """A SparseMixer MoE config must export as Phimoe and reload in
    transformers with matching logits (routing weights un-renormalized)."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **{**TINY, "num_hidden_layers": 2},
        norm_type="layernorm", attention_bias=True, lm_head_bias=True,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=96,
        norm_topk_prob=False, moe_style="mixtral",
        moe_router_impl="sparsemixer",
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(21).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(6), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "PhimoeForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


def test_sparsemixer_and_cohere_window_exports_guarded():
    """Silent-fallthrough refusals: sparsemixer outside the Phimoe shape,
    and a cohere graph with a uniform window but no layer pattern."""
    import pytest as _pytest

    from llm_training_tpu.models.llama.hf_conversion import config_to_hf

    with _pytest.raises(ValueError, match="sparsemixer"):
        config_to_hf(LlamaConfig(
            **TINY, num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=32, moe_router_impl="sparsemixer",
        ))  # qwen-style naming + rmsnorm: would reload with softmax routing
    with _pytest.raises(ValueError, match="cohere"):
        config_to_hf(LlamaConfig(
            **TINY, norm_scheme="parallel", norm_type="layernorm_nobias",
            rope_interleaved=True, sliding_window=8,
        ))  # uniform window: HF Cohere would silently run full attention


@pytest.mark.slow
@pytest.mark.parametrize("parallel", [True, False])
def test_logits_parity_with_hf_gpt_neox(parallel):
    """GPT-NeoX (Pythia) routes to the Llama module: two biased LayerNorms
    feeding attention and mlp in parallel over the same block input
    (norm_scheme='parallel2'; use_parallel_residual=False is plain
    pre-norm), a per-head INTERLEAVED fused query_key_value split at
    conversion, biased gelu MLP with EXACT (erf) gelu, partial rotary
    0.25, untied embed_out."""
    torch = pytest.importorskip("torch")
    from transformers import GPTNeoXConfig, GPTNeoXForCausalLM

    hf_config = GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.25,
        use_parallel_residual=parallel, layer_norm_eps=1e-5,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = GPTNeoXForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert "gpt_neox.layers.0.attention.query_key_value.weight" in sd
    assert "embed_out.weight" in sd

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_scheme == ("parallel2" if parallel else "pre")
    assert cfg.norm_type == "layernorm" and cfg.mlp_type == "gelu"
    assert cfg.mlp_bias and cfg.attention_bias and not cfg.gelu_approximate
    assert cfg.partial_rotary_factor == 0.25
    params = params_from_hf(sd, cfg)
    model = Llama(cfg)

    ids = np.random.default_rng(22).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_gpt_neox_export_round_trip(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **{**TINY, "num_hidden_layers": 2, "num_key_value_heads": TINY["num_attention_heads"]},
        norm_scheme="parallel2", norm_type="layernorm", mlp_type="gelu",
        gelu_approximate=False, attention_bias=True, mlp_bias=True,
        lm_head_bias=False, partial_rotary_factor=0.25,
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(23).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(7), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "GPTNeoXForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_logits_parity_with_hf_olmo1():
    """OLMo-1 routes to the Llama module: a plain bias-free llama graph
    whose norms are FULLY non-parametric (F.layer_norm with no weight or
    bias — zero norm keys in the checkpoint) plus the clip_qkv clamp."""
    torch = pytest.importorskip("torch")
    from transformers import OlmoConfig, OlmoForCausalLM

    hf_config = OlmoConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, clip_qkv=1.5,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    hf_model = OlmoForCausalLM(hf_config).eval()
    sd = hf_model.state_dict()
    assert not any("norm" in k for k in sd)  # truly parameter-free norms

    cfg = config_from_hf(hf_config, compute_dtype="float32")
    assert cfg.norm_type == "layernorm_nonparam" and cfg.clip_qkv == 1.5
    params = params_from_hf(sd, cfg)
    assert "input_layernorm" not in str(jax.tree_util.tree_structure(params))
    model = Llama(cfg)

    ids = np.random.default_rng(24).integers(0, 128, (2, 24))
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids)).logits.numpy()
    ours = jax.jit(model.apply)(params, jnp.asarray(ids)).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_olmo1_export_round_trip(tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    from llm_training_tpu.models.hf_io import save_hf_checkpoint

    cfg = LlamaConfig(
        **{**TINY, "num_hidden_layers": 2, "rms_norm_eps": 1e-5},
        norm_type="layernorm_nonparam", clip_qkv=2.0,
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(25).integers(0, 128, (2, 16)))
    params = jax.jit(model.init)(jax.random.key(8), ids)
    out_dir = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")

    hf_model = AutoModelForCausalLM.from_pretrained(
        out_dir, attn_implementation="eager"
    ).eval()
    assert type(hf_model).__name__ == "OlmoForCausalLM"
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(np.asarray(ids))).logits.numpy()
    ours = jax.jit(model.apply)(params, ids).logits
    np.testing.assert_allclose(np.asarray(ours), hf_logits, rtol=2e-4, atol=2e-4)
