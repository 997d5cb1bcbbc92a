"""HF checkpoint IO: streamed safetensors loading, export round-trip through
`transformers`, convert_to_hf script, and pre-trained init in the trainer."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models import Llama, LlamaConfig
from llm_training_tpu.models.hf_io import (
    LazyStateDict,
    load_hf_config,
    load_pretrained_params,
    model_class_for_hf,
    save_hf_checkpoint,
)
from llm_training_tpu.models.llama.hf_conversion import config_from_hf, params_from_hf

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))

TINY_HF = dict(
    vocab_size=128,
    hidden_size=64,
    intermediate_size=112,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=64,
)


@pytest.fixture(scope="module")
def hf_llama_dir(tmp_path_factory):
    """A tiny HF Llama saved with save_pretrained (single safetensors)."""
    import torch
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers import LlamaForCausalLM

    torch.manual_seed(0)
    hf_model = LlamaForCausalLM(HFLlamaConfig(**TINY_HF, attention_bias=False))
    out = tmp_path_factory.mktemp("hf_llama")
    hf_model.save_pretrained(out, safe_serialization=True)
    return out


def test_lazy_state_dict_reads_all_keys(hf_llama_dir):
    lazy = LazyStateDict(hf_llama_dir)
    assert "model.embed_tokens.weight" in lazy
    tensor = lazy["model.layers.0.self_attn.q_proj.weight"]
    assert tuple(tensor.shape) == (64, 64)
    assert len(lazy) > 10


def test_load_pretrained_matches_eager(hf_llama_dir):
    from transformers import LlamaForCausalLM

    cfg = config_from_hf(load_hf_config(hf_llama_dir), compute_dtype="float32")
    streamed = load_pretrained_params(cfg, hf_llama_dir)
    eager = params_from_hf(
        LlamaForCausalLM.from_pretrained(hf_llama_dir).state_dict(), cfg
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        streamed, eager,
    )


def test_load_pretrained_with_shardings(hf_llama_dir, devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    cfg = config_from_hf(load_hf_config(hf_llama_dir), compute_dtype="float32")
    mesh = Mesh(np.array(devices).reshape(8), ("fsdp",))
    params = load_pretrained_params(cfg, hf_llama_dir)
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, PartitionSpec()), params
    )
    placed = load_pretrained_params(cfg, hf_llama_dir, shardings, jnp.float32)
    leaf = placed["params"]["embed_tokens"]["embedding"]
    assert isinstance(leaf, jax.Array) and leaf.dtype == jnp.float32


@pytest.mark.slow
def test_export_roundtrip_through_transformers(tmp_path):
    """our params -> save_hf_checkpoint -> transformers forward == ours."""
    import torch
    from transformers import LlamaForCausalLM

    cfg = LlamaConfig(
        **{k: v for k, v in TINY_HF.items()}, compute_dtype="float32",
        param_dtype="float32",
    )
    model = Llama(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 16), np.int32))
    params = jax.jit(model.init)(jax.random.key(0), ids)
    ours = jax.jit(model.apply)(params, ids).logits

    out = save_hf_checkpoint(params, cfg, tmp_path / "export", dtype="float32")
    hf_model = LlamaForCausalLM.from_pretrained(out, torch_dtype=torch.float32)
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(np.asarray(ids)).long()).logits.numpy()
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4, rtol=2e-3)


@pytest.mark.slow
def test_sharded_export(tmp_path):
    """Multiple safetensors shards + index.json when over the shard budget."""
    cfg = LlamaConfig(**TINY_HF, compute_dtype="float32", param_dtype="float32")
    model = Llama(cfg)
    params = jax.jit(model.init)(jax.random.key(0), jnp.ones((1, 4), jnp.int32))
    out = save_hf_checkpoint(
        params, cfg, tmp_path / "sharded", dtype="float32", max_shard_bytes=200_000
    )
    index = json.loads((out / "model.safetensors.index.json").read_text())
    assert len(set(index["weight_map"].values())) > 1
    # and it still loads
    streamed = load_pretrained_params(cfg, out)
    assert "embed_tokens" in streamed["params"]


def test_model_class_for_hf():
    assert model_class_for_hf({"model_type": "llama"}).endswith("Llama")
    assert model_class_for_hf({"model_type": "mistral"}).endswith("Llama")
    assert model_class_for_hf({"model_type": "phi3"}).endswith("Phi3")


def test_unknown_model_type_llama_fallback():
    """Unknown model_types fail loudly by default; the opt-in routes them
    to the Llama family (renamed llama-layout forks)."""
    with pytest.raises(ValueError, match="assume_llama_layout"):
        model_class_for_hf({"model_type": "somebodys_llama_fork"})
    assert model_class_for_hf(
        {"model_type": "somebodys_llama_fork"}, assume_llama_layout=True
    ).endswith("Llama")
    with pytest.raises(ValueError):
        model_class_for_hf({"model_type": "mamba"})


def _tiny_fit(tmp_path, pre_trained=None, max_steps=1, lr=1e-3):
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.optim import OptimConfig
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig
    from llm_training_tpu.trainer.checkpoint import CheckpointConfig, Checkpointer

    model_kwargs = dict(TINY_HF, compute_dtype="float32", param_dtype="float32")
    model_node = {
        "class_path": "llm_training_tpu.lms.CLM",
        "init_args": {
            "model": {
                "model_class": "llm_training_tpu.models.Llama",
                "model_kwargs": model_kwargs,
            },
            "optim": {"learning_rate": lr, "warmup_steps": 0},
            **({"pre_trained_weights": str(pre_trained)} if pre_trained else {}),
        },
    }
    objective = CLM(
        CLMConfig(
            model=ModelProvider(
                model_class="llm_training_tpu.models.Llama", model_kwargs=model_kwargs
            ),
            optim=OptimConfig(learning_rate=lr, warmup_steps=0),
            pre_trained_weights=str(pre_trained) if pre_trained else None,
        )
    )
    datamodule = DummyDataModule(
        DummyDataModuleConfig(
            batch_size=8, max_length=16, num_samples=32, vocab_size=128
        )
    )
    checkpointer = Checkpointer(
        CheckpointConfig(dirpath=str(tmp_path / "ckpt"), async_save=False),
        run_config={"model": model_node, "data": {}},
    )
    trainer = Trainer(
        TrainerConfig(max_steps=max_steps, log_every_n_steps=1, mesh=MeshConfig()),
        checkpointer=checkpointer,
    )
    state = trainer.fit(objective, datamodule)
    return trainer, objective, state, tmp_path / "ckpt"


@pytest.mark.slow
def test_convert_to_hf_script(tmp_path):
    """fit -> checkpoint -> convert -> transformers can load the export."""
    import torch
    from transformers import LlamaForCausalLM

    from convert_to_hf import convert_checkpoint

    _, objective, state, ckpt_dir = _tiny_fit(tmp_path)
    out = convert_checkpoint(ckpt_dir, tmp_path / "hf_out", dtype="float32")
    hf_model = LlamaForCausalLM.from_pretrained(out, torch_dtype=torch.float32)

    ids = np.random.default_rng(1).integers(0, 128, (2, 12), np.int64)
    with torch.no_grad():
        theirs = hf_model(torch.from_numpy(ids)).logits.numpy()
    ours = objective.jax.jit(model.apply)(
        jax.device_get(state.params), jnp.asarray(ids, jnp.int32)
    ).logits
    np.testing.assert_allclose(np.asarray(ours), theirs, atol=2e-4, rtol=2e-3)


def test_dpo_pretrained_loads_policy_and_ref(hf_llama_dir):
    from llm_training_tpu.lms import DPO, DPOConfig, ModelProvider

    model_kwargs = dict(TINY_HF, compute_dtype="float32", param_dtype="float32")
    objective = DPO(
        DPOConfig(
            model=ModelProvider(
                model_class="llm_training_tpu.models.Llama", model_kwargs=model_kwargs
            ),
            pre_trained_weights=str(hf_llama_dir),
        )
    )
    import flax.linen as nn
    from jax.sharding import SingleDeviceSharding

    abstract = nn.meta.unbox(
        jax.eval_shape(
            lambda: objective.init_params(
                jax.random.key(0), {"chosen_input_ids": jnp.ones((1, 4), jnp.int32)}
            )
        )
    )
    shardings = jax.tree.map(
        lambda _: SingleDeviceSharding(jax.devices()[0]), abstract
    )
    dtypes = jax.tree.map(lambda _: jnp.float32, abstract)
    params = objective.pretrained_params(shardings, dtypes)
    a = params["policy"]["params"]["embed_tokens"]["embedding"]
    b = params["ref"]["params"]["embed_tokens"]["embedding"]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_trainer_pretrained_init(tmp_path, hf_llama_dir):
    """pre_trained_weights + lr=0: params after one step == the HF weights."""
    _, objective, state, _ = _tiny_fit(tmp_path, pre_trained=hf_llama_dir, lr=0.0)
    cfg = config_from_hf(load_hf_config(hf_llama_dir), compute_dtype="float32")
    expected = load_pretrained_params(cfg, hf_llama_dir)
    got = jax.device_get(state.params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        ),
        got, expected,
    )


# ---------------------------------------------------------------- HFCausalLM


def test_hf_causal_lm_routes_to_family(hf_llama_dir):
    """HFCausalLM(config) returns the routed flax family model with merged
    hparams and the checkpoint wired as pre-trained weights (the reference's
    wrap-any-AutoModelForCausalLM escape hatch, hf_causal_lm.py:22)."""
    from llm_training_tpu.models import HFCausalLM, HFCausalLMConfig, Llama

    model = HFCausalLM(HFCausalLMConfig(hf_path=str(hf_llama_dir)))
    assert isinstance(model, Llama)
    assert model.config.hidden_size == TINY_HF["hidden_size"]
    assert model.config.num_key_value_heads == TINY_HF["num_key_value_heads"]
    assert model.config.pre_trained_weights == str(hf_llama_dir)


def test_hf_causal_lm_overrides_and_validation(hf_llama_dir):
    from llm_training_tpu.models import HFCausalLM, HFCausalLMConfig

    model = HFCausalLM(
        HFCausalLMConfig(
            hf_path=str(hf_llama_dir),
            enable_gradient_checkpointing=True,
            attention_impl="xla",
        )
    )
    assert model.config.enable_gradient_checkpointing is True
    with pytest.raises(Exception):  # family pydantic config rejects typos
        HFCausalLM(
            HFCausalLMConfig(hf_path=str(hf_llama_dir), hiden_size=12)
        )


def test_hf_causal_lm_unknown_arch_fails_loudly(tmp_path):
    from llm_training_tpu.models import HFCausalLM, HFCausalLMConfig

    (tmp_path / "config.json").write_text(json.dumps({"model_type": "mamba"}))
    with pytest.raises(ValueError, match="unsupported HF model_type"):
        HFCausalLM(HFCausalLMConfig(hf_path=str(tmp_path)))


def test_hf_causal_lm_through_model_provider(hf_llama_dir):
    """The YAML path: ModelProvider with model_class=HFCausalLM."""
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.models import Llama

    provider = ModelProvider(
        model_class="HFCausalLM",
        model_kwargs=dict(hf_path=str(hf_llama_dir), scan_layers=False),
    )
    model = provider.get_model()
    assert isinstance(model, Llama)
    assert model.config.scan_layers is False


def test_hf_causal_lm_pipeline_load_logits_parity(hf_llama_dir, devices):
    """The llama-3-8b_pp_pt.yaml path end-to-end at tiny scale: HFCausalLM
    routes the checkpoint into a PIPELINED Llama (pipeline_stages forwarded
    through the router), load_pretrained_params adapts the scan-layout
    conversion into the [S, L/S, ...] stage stacks, and the loaded model's
    logits match the scan-routed model loaded from the same directory."""
    from llm_training_tpu.models import HFCausalLM, HFCausalLMConfig
    from llm_training_tpu.models.hf_io import load_pretrained_params

    m_scan = HFCausalLM(
        HFCausalLMConfig(hf_path=str(hf_llama_dir), compute_dtype="float32")
    )
    m_pp = HFCausalLM(
        HFCausalLMConfig(
            hf_path=str(hf_llama_dir),
            compute_dtype="float32",
            pipeline_stages=2,
            pipeline_microbatches=2,
        )
    )
    assert m_pp.config.pipeline_stages == 2

    p_scan = load_pretrained_params(m_scan.config, str(hf_llama_dir))
    p_pp = load_pretrained_params(m_pp.config, str(hf_llama_dir))
    stack_leaf = jax.tree.leaves(p_pp["params"]["pipeline"])[0]
    assert stack_leaf.shape[:2] == (2, 1)  # [S, L/S, ...]

    ids = jnp.asarray(
        np.random.default_rng(0).integers(1, TINY_HF["vocab_size"], (4, 16)),
        jnp.int32,
    )
    seg = jnp.ones((4, 16), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(16), (4, 16)).astype(jnp.int32)
    out_scan = m_scan.apply(p_scan, ids, seg, pos)
    out_pp = m_pp.apply(p_pp, ids, seg, pos)
    np.testing.assert_allclose(
        np.asarray(out_pp.logits), np.asarray(out_scan.logits), atol=2e-5
    )
