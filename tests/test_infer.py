"""Inference subsystem tests (docs/inference.md): KV-cache decode parity
against full forwards (the canonical cache-correctness oracle), sampling
transforms, the eval harness, and the generate/evaluate CLI wiring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import full_forward_greedy as _full_forward_greedy

from llm_training_tpu.infer import (
    GenerateConfig,
    InferenceEngine,
    SamplingConfig,
    cache_bytes,
    init_decode_state,
    sample_tokens,
)
from llm_training_tpu.infer.sampling import top_k_filter, top_p_filter
from llm_training_tpu.models import (
    Gemma,
    GemmaConfig,
    Llama,
    LlamaConfig,
)

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64, attention_impl="xla",
    compute_dtype="float32", param_dtype="float32",
)


def _init(model, seed=0):
    return jax.jit(model.init)(jax.random.key(seed), np.zeros((1, 4), np.int32))


# ------------------------------------------------------------ greedy parity


@pytest.mark.parametrize("scan_layers", [True, False], ids=["scan", "looped"])
def test_greedy_decode_matches_full_forward(scan_layers):
    """N-token greedy generation through the KV cache must be token-
    identical to argmax over N full forward passes — with RAGGED prompt
    lengths, so the left-pad bookkeeping (per-row positions, pad segment
    ids) is part of what parity proves."""
    model = Llama(LlamaConfig(**TINY, scan_layers=scan_layers))
    variables = _init(model)
    engine = InferenceEngine(model, variables)
    prompts = [[3, 17, 42, 7, 11], [5, 9], [1, 2, 3]]
    n = 8
    result = engine.generate(prompts, GenerateConfig(max_new_tokens=n))
    for row, prompt in enumerate(prompts):
        expected = _full_forward_greedy(model, variables, prompt, n)
        assert result["tokens"][row] == expected, f"row {row}"
        assert result["sequences"][row] == list(prompt) + expected


def test_greedy_decode_moe_and_sliding_window():
    """The smoke-config shape: a tiny MoE Llama (router + experts run in
    the decode programs too) with a sliding window small enough to actually
    truncate attention mid-generation."""
    model = Llama(LlamaConfig(
        **TINY, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
        sliding_window=4,
    ))
    variables = _init(model)
    engine = InferenceEngine(model, variables)
    prompts = [[3, 17, 42, 7, 11, 2]]
    result = engine.generate(prompts, GenerateConfig(max_new_tokens=6))
    assert result["tokens"][0] == _full_forward_greedy(model, variables, prompts[0], 6)


def test_greedy_decode_gemma():
    model = Gemma(GemmaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=64, attention_impl="xla",
        compute_dtype="float32",
    ))
    variables = _init(model)
    engine = InferenceEngine(model, variables)
    prompts = [[3, 17, 42], [5, 9, 11, 13]]
    result = engine.generate(prompts, GenerateConfig(max_new_tokens=5))
    for row, prompt in enumerate(prompts):
        assert result["tokens"][row] == _full_forward_greedy(model, variables, prompt, 5)


def test_prefill_logits_match_full_forward():
    """Prefill writes the cache AND must reproduce the training forward's
    logits on the prompt (same stack, same mask) — checked directly on the
    model so a future engine change can't mask a stack regression."""
    from llm_training_tpu.models.base import DecodeState  # noqa: F401

    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    ids = jax.random.randint(jax.random.key(3), (2, 6), 0, 64)
    state = init_decode_state(model.config, batch_size=2, max_length=10)
    out = model.apply(
        variables, input_ids=ids,
        segment_ids=jnp.ones_like(ids),
        position_ids=jnp.broadcast_to(jnp.arange(6), (2, 6)),
        decode_state=state,
    )
    full = model.apply(variables, input_ids=ids)
    np.testing.assert_allclose(
        np.asarray(out.logits), np.asarray(full.logits), rtol=2e-5, atol=2e-5
    )
    assert int(out.decode_state.index) == 6


# ------------------------------------------------------------ sampling


def test_sample_tokens_greedy_is_argmax():
    logits = jnp.asarray([[0.1, 3.0, -1.0], [2.0, 0.0, 2.5]])
    tokens = sample_tokens(logits, None, SamplingConfig(temperature=0.0))
    assert tokens.tolist() == [1, 2]


def test_top_k_filter_keeps_k_largest():
    logits = jnp.asarray([[1.0, 4.0, 2.0, 3.0]])
    filtered = np.asarray(top_k_filter(logits, 2))
    assert (filtered[0] > -1e9).tolist() == [False, True, False, True]
    # k >= vocab is the identity
    np.testing.assert_array_equal(np.asarray(top_k_filter(logits, 4)), np.asarray(logits))


def test_top_p_filter_nucleus():
    # probs ~ [0.643, 0.236, 0.087, 0.032]: p=0.7 keeps the boundary-
    # crossing 2nd token (HF semantics), p=0.5 keeps only the 1st
    logits = jnp.asarray([[4.0, 3.0, 2.0, 1.0]])
    keep_07 = np.asarray(top_p_filter(logits, 0.7))[0] > -1e9
    assert keep_07.tolist() == [True, True, False, False]
    keep_05 = np.asarray(top_p_filter(logits, 0.5))[0] > -1e9
    assert keep_05.tolist() == [True, False, False, False]
    # p=1.0 keeps everything
    assert (np.asarray(top_p_filter(logits, 1.0))[0] > -1e9).all()


def test_sampled_tokens_respect_filters_and_seed():
    logits = jax.random.normal(jax.random.key(0), (4, 32))
    config = SamplingConfig(temperature=0.7, top_k=5)
    a = sample_tokens(logits, jax.random.key(1), config)
    b = sample_tokens(logits, jax.random.key(1), config)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # every sampled token must be inside each row's top-5
    top5 = jax.lax.top_k(logits, 5)[1]
    for row in range(4):
        assert int(a[row]) in np.asarray(top5[row]).tolist()


def test_sampling_config_validators():
    with pytest.raises(ValueError):
        SamplingConfig(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingConfig(top_k=0)
    with pytest.raises(ValueError):
        SamplingConfig(top_p=0.0)
    with pytest.raises(ValueError):
        sample_tokens(jnp.zeros((1, 4)), None, SamplingConfig(temperature=1.0))


# ------------------------------------------------------------ engine


def test_engine_rejects_unthreaded_families():
    class NoCacheModel:
        config = None

        def __call__(self, input_ids=None, segment_ids=None, position_ids=None,
                     inputs_embeds=None, compute_logits=True,
                     return_last_hidden_states=False):
            raise AssertionError("never applied")

    with pytest.raises(NotImplementedError, match="decode_state"):
        InferenceEngine(NoCacheModel(), {})


# what `models/__init__.py` exports, by the shard audit's tiny configs (its
# registry holds every concrete family; llama three times)
_DECODING_FAMILIES = {"llama", "llama_moe", "llama_pp", "phi3", "gemma", "solar_open2", "longcat_flash", "afmoe", "olmo_hybrid", "deepseek", "phi4flash", "gigachat3_5"}


def _family_names():
    from llm_training_tpu.analysis.shard_audit import FAMILY_REGISTRY

    return [spec.name for spec in FAMILY_REGISTRY]


@pytest.mark.parametrize("family", _family_names())
def test_supports_decoding_is_the_configs_declaration(family):
    """A family decodes when its config declares a cache, and those are the
    families whose `__call__` takes a `decode_state`."""
    import inspect

    from llm_training_tpu.analysis.shard_audit import FAMILY_REGISTRY
    from llm_training_tpu.infer.engine import supports_decoding

    model = next(s for s in FAMILY_REGISTRY if s.name == family).build()
    assert supports_decoding(model) is (family in _DECODING_FAMILIES)
    takes_state = "decode_state" in inspect.signature(type(model).__call__).parameters
    assert takes_state is (family in _DECODING_FAMILIES)
    if family not in _DECODING_FAMILIES:
        with pytest.raises(NotImplementedError, match="declares no cache"):
            init_decode_state(model.config, 1, 8)


def test_a_signature_alone_does_not_make_a_family_decode():
    """A `__call__` that takes `decode_state` under a config that declares
    no cache: no pool or buffer could be sized for it."""
    from llm_training_tpu.infer.engine import supports_decoding
    from llm_training_tpu.models.base import BaseModelConfig

    class TakesAState:
        config = BaseModelConfig()

        def __call__(self, input_ids=None, decode_state=None):
            raise AssertionError("never applied")

    assert not supports_decoding(TakesAState())
    with pytest.raises(NotImplementedError, match="decode_state"):
        InferenceEngine(TakesAState(), {})


def test_engine_eos_truncation():
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    engine = InferenceEngine(model, variables)
    base = engine.generate([[3, 17, 42]], GenerateConfig(max_new_tokens=6))
    eos = base["tokens"][0][2]  # force a stop at the 3rd greedy token
    result = engine.generate(
        [[3, 17, 42]], GenerateConfig(max_new_tokens=6, eos_token_id=eos)
    )
    assert result["tokens"][0] == base["tokens"][0][:3]
    assert result["tokens"][0][-1] == eos


def test_generate_config_validators():
    with pytest.raises(ValueError, match="max_new_tokens"):
        GenerateConfig(max_new_tokens=0)
    with pytest.raises(ValueError, match="max_new_tokens"):
        GenerateConfig(max_new_tokens=-5)
    with pytest.raises(ValueError, match="max_length"):
        GenerateConfig(max_length=0)


def test_engine_cache_sizing_and_stats():
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    engine = InferenceEngine(model, variables)
    with pytest.raises(ValueError, match="max_length"):
        engine.generate([[1, 2, 3]], GenerateConfig(max_new_tokens=8, max_length=4))
    result = engine.generate(
        [[1, 2, 3]], GenerateConfig(max_new_tokens=4, cache_dtype="bfloat16")
    )
    stats = result["stats"]
    # [L=2, B=1, S=7, H=2, D=8] bf16 k+v
    assert stats["decode/cache_bytes"] == 2 * (2 * 1 * 7 * 2 * 8) * 2
    assert stats["decode/new_tokens"] == 4
    assert stats["decode/prefill_time_s"] > 0


def test_init_decode_state_dtypes():
    config = LlamaConfig(**TINY)
    state = init_decode_state(config, 2, 8)
    assert state.k.dtype == jnp.float32  # param dtype default
    assert int(state.index) == 0
    assert state.segment_ids.shape == (2, 8)
    bf16 = init_decode_state(config, 2, 8, cache_dtype="bfloat16")
    assert bf16.k.dtype == jnp.bfloat16
    assert cache_bytes(bf16) == cache_bytes(state) // 2


def test_engine_on_mesh(devices):
    """Sharded decode: the default 8-device mesh, batch divisible by the
    data ways — greedy tokens must match the meshless run exactly."""
    import flax.linen as nn

    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.parallel.mesh import build_mesh
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    prompts = [[i + 1, i + 2, i + 3] for i in range(8)]
    reference = InferenceEngine(model, variables).generate(
        prompts, GenerateConfig(max_new_tokens=4)
    )
    mesh = build_mesh(MeshConfig(), devices)
    with mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        sharded_vars = jax.device_put(variables)
    engine = InferenceEngine(model, sharded_vars, mesh=mesh, rules=LOGICAL_AXIS_RULES)
    result = engine.generate(prompts, GenerateConfig(max_new_tokens=4))
    assert result["tokens"] == reference["tokens"]


# ------------------------------------------------------------ evaluate


def _dummy_data(**kwargs):
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig

    return DummyDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=16, num_samples=48, vocab_size=64,
        validation_split=16, **kwargs,
    ))


def test_run_evaluation_packed_nll(devices):
    from llm_training_tpu.infer import run_evaluation
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.parallel.mesh import build_mesh
    from llm_training_tpu.trainer.state import TrainState

    objective = CLM(CLMConfig(model=ModelProvider(
        model_class="llm_training_tpu.models.Llama", model_kwargs=TINY,
    )))
    variables = _init(objective.model)
    state = TrainState.create(variables, (), jax.random.key(0))
    mesh = build_mesh(MeshConfig(), devices)
    result = run_evaluation(objective, state, _dummy_data(), mesh)
    assert np.isfinite(result["eval/nll_per_token"])
    np.testing.assert_allclose(
        result["eval/perplexity"], np.exp(result["eval/nll_per_token"]), rtol=1e-6
    )
    # 2 val batches of 8x16 tokens, every position a target except the last
    # of each (unpacked) row
    assert result["eval/batches"] == 2.0
    assert result["eval/tokens"] == 2 * 8 * (16 - 1)
    with pytest.raises(ValueError, match="limit_batches"):
        run_evaluation(objective, state, _dummy_data(), mesh, split="train")


# ------------------------------------------------------------ CLI


@pytest.mark.slow
def test_cli_generate_and_evaluate_from_checkpoint(devices, tmp_path):
    """End-to-end acceptance path: fit -> checkpoint -> `generate` /
    `evaluate` -> decode gauges visible in `report`."""
    import yaml

    from llm_training_tpu.cli.main import main

    config = {
        "seed_everything": 7,
        "trainer": {
            "max_steps": 2,
            "log_every_n_steps": 1,
            "checkpoint_every_n_steps": 2,
            "checkpoint": {"dirpath": str(tmp_path / "ckpt"), "async_save": False},
            "loggers": [{
                "class_path": "llm_training_tpu.callbacks.JsonlLogger",
                "init_args": {
                    "save_dir": str(tmp_path / "runs"),
                    "project": "t", "name": "r",
                },
            }],
        },
        "model": {
            "class_path": "llm_training_tpu.lms.CLM",
            "init_args": {
                "model": {
                    "model_class": "llm_training_tpu.models.Llama",
                    "model_kwargs": TINY,
                },
                "optim": {"learning_rate": 1e-3},
            },
        },
        "data": {
            "class_path": "llm_training_tpu.data.DummyDataModule",
            "init_args": {
                "batch_size": 8, "max_length": 16, "num_samples": 32,
                "vocab_size": 64, "validation_split": 8,
            },
        },
    }
    config_path = tmp_path / "config.yaml"
    config_path.write_text(yaml.safe_dump(config))
    assert main(["fit", "--config", str(config_path)]) == 0
    assert main([
        "generate", "--config", str(config_path),
        "--prompt-tokens", "3,17,42", "--max-new-tokens", "4",
    ]) == 0
    assert main([
        "evaluate", "--config", str(config_path), "--limit-batches", "1",
    ]) == 0
    from llm_training_tpu.telemetry.report import render_report

    report = render_report(tmp_path / "runs" / "t" / "r")
    assert "== Inference ==" in report
    assert "decode_tokens_per_sec" in report
    assert "perplexity" in report
