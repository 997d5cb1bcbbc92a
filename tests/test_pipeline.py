"""GPipe pipeline parallelism (models/pipeline.py, 'pipe' mesh axis).

No reference analogue — cchou0519/LLM-Training has no PP (SURVEY.md §2.8);
these tests hold the feature to the same standard as the other axes: exact
math parity against the scanned stack (microbatching must not change any
token's computation), gradient parity through the full tick loop, and a
real sharded train step composing pipe x fsdp x tensor on the virtual mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
from llm_training_tpu.optim import OptimConfig
from llm_training_tpu.parallel import MeshConfig
from llm_training_tpu.trainer import Trainer, TrainerConfig

KW = dict(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=4,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=64,
    compute_dtype="float32",
    param_dtype="float32",
)


def _models():
    from llm_training_tpu.models.llama.config import LlamaConfig
    from llm_training_tpu.models.llama.model import Llama

    return (
        Llama(LlamaConfig(**KW)),
        Llama(LlamaConfig(**KW, pipeline_stages=2, pipeline_microbatches=4)),
    )


def _inputs(batch=8, seq=16):
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(1, KW["vocab_size"], (batch, seq)), jnp.int32)
    # two packed documents per row: the segment ids must travel with their
    # microbatch through the shift buffers
    seg = jnp.asarray(np.repeat([[1, 2]], batch, 0).repeat(seq // 2, 1), jnp.int32)
    pos = jnp.asarray(np.tile(np.r_[np.arange(seq // 2), np.arange(seq // 2)], (batch, 1)), jnp.int32)
    return ids, seg, pos


def _scan_params_from_pipeline(p_p, num_layers):
    """[S, L/S, ...] pipeline stacks -> the scan path's [L, ...] layout."""
    stack = jax.tree.map(
        lambda v: v.reshape((num_layers,) + v.shape[2:]),
        p_p["pipeline"]["ticks"]["layers"],
    )
    p_s = {k: v for k, v in p_p.items() if k != "pipeline"}
    p_s["layers"] = stack
    return p_s


def test_pipeline_matches_scan_forward_and_grad(devices):
    import flax.linen as nn

    m_s, m_p = _models()
    ids, seg, pos = _inputs()
    p_p = nn.meta.unbox(jax.jit(m_p.init)(jax.random.key(0), ids, seg, pos))["params"]
    p_s = _scan_params_from_pipeline(p_p, KW["num_hidden_layers"])

    out_s = jax.jit(m_s.apply)({"params": p_s}, ids, seg, pos)
    out_p = jax.jit(m_p.apply)({"params": p_p}, ids, seg, pos)
    np.testing.assert_allclose(
        np.asarray(out_p.logits), np.asarray(out_s.logits), atol=1e-5
    )

    def loss_fn(params, model):
        out = model.apply({"params": params}, ids, seg, pos)
        logp = jax.nn.log_softmax(out.logits.astype(jnp.float32))
        return jnp.mean(logp[..., 0] ** 2)

    g_s = jax.jit(jax.grad(lambda p: loss_fn(p, m_s)))(p_s)
    g_p = jax.jit(jax.grad(lambda p: loss_fn(p, m_p)))(p_p)
    g_p_as_scan = _scan_params_from_pipeline(g_p, KW["num_hidden_layers"])
    for a, b in zip(jax.tree.leaves(g_s), jax.tree.leaves(g_p_as_scan)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_pipeline_microbatch_counts_agree(devices):
    """M = S, M = 2S and a non-divisor M (gcd fallback) must all produce
    identical logits — the schedule never changes the math."""
    import flax.linen as nn

    from llm_training_tpu.models.llama.config import LlamaConfig
    from llm_training_tpu.models.llama.model import Llama

    ids, seg, pos = _inputs()
    ref = None
    for micro in (2, 4, 3):  # 3 does not divide batch 8 -> gcd degrades to 1
        m = Llama(LlamaConfig(**KW, pipeline_stages=2, pipeline_microbatches=micro))
        p = nn.meta.unbox(jax.jit(m.init)(jax.random.key(0), ids, seg, pos))["params"]
        out = np.asarray(jax.jit(m.apply)({"params": p}, ids, seg, pos).logits)
        if ref is None:
            ref = out
        else:
            np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize(
    "mesh_kw",
    [
        dict(pipeline_parallel_size=2, fsdp_size=2, tensor_parallel_size=2),
        # sequence-parallel activations inside each stage (plain SP, not
        # ring): the shift buffers carry an act_seq axis sharded over
        # 'sequence' and GSPMD composes it with the stage shift
        dict(pipeline_parallel_size=2, fsdp_size=2, sequence_parallel_size=2),
    ],
    ids=["pipe-fsdp-tp", "pipe-fsdp-sp"],
)
def test_pipeline_sharded_train_step(devices, mesh_kw):
    """One real train step on a pipe-composed mesh: executes, loss finite,
    parameters actually move."""
    objective = CLM(
        CLMConfig(
            model=ModelProvider(
                model_class="llm_training_tpu.models.Llama",
                model_kwargs=dict(
                    KW, pipeline_stages=2, pipeline_microbatches=4,
                    enable_gradient_checkpointing=True,
                ),
            ),
            optim=OptimConfig(learning_rate=3e-3, warmup_steps=1),
        )
    )
    dm = DummyDataModule(
        DummyDataModuleConfig(batch_size=8, max_length=32, num_samples=16, vocab_size=128)
    )
    metrics = {}

    class Rec:
        def on_step_end(self, trainer, step, m):
            metrics.update(m)

    trainer = Trainer(
        TrainerConfig(
            max_steps=2, log_every_n_steps=1,
            mesh=MeshConfig(**mesh_kw),
        ),
        callbacks=[Rec()],
    )
    state = trainer.fit(objective, dm)
    assert int(jax.device_get(state.step)) == 2
    assert np.isfinite(metrics["loss"]) and metrics["loss"] > 3.0
    assert np.isfinite(metrics["grad_norm"]) and metrics["grad_norm"] > 0.0
    # the layer stacks really shard their stage axis over 'pipe'
    stack_leaf = jax.tree.leaves(state.params["params"]["pipeline"])[0]
    spec = stack_leaf.sharding.spec
    assert spec[0] == "pipe", spec


@pytest.mark.slow
def test_pipeline_loss_decreases(devices):
    objective = CLM(
        CLMConfig(
            model=ModelProvider(
                model_class="llm_training_tpu.models.Llama",
                model_kwargs=dict(
                    KW, pipeline_stages=2, pipeline_microbatches=4
                ),
            ),
            optim=OptimConfig(learning_rate=1e-2, warmup_steps=5),
        )
    )
    dm = DummyDataModule(
        DummyDataModuleConfig(batch_size=8, max_length=64, num_samples=64, vocab_size=128)
    )
    losses = []

    class Rec:
        def on_step_end(self, trainer, step, m):
            losses.append(float(m["loss"]))

    trainer = Trainer(
        TrainerConfig(
            max_steps=40, log_every_n_steps=1,
            mesh=MeshConfig(
                pipeline_parallel_size=2, fsdp_size=2, tensor_parallel_size=2
            ),
        ),
        callbacks=[Rec()],
    )
    trainer.fit(objective, dm)
    assert losses[0] > 4.0  # ~ln(128)
    assert min(losses[-5:]) < losses[0] - 0.3


MOE_KW = dict(
    KW, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
)


def test_pipeline_moe_matches_scan(devices):
    """MoE under PP: logits AND the pooled router-stat aux loss must match
    the scanned stack exactly — the pipeline pools sel_frac/mean_prob over
    the real (tick, stage) cells only (each equal-sized microbatch's mean
    averages to the full-batch mean) and masks bubble-tick junk stats."""
    import flax.linen as nn

    from llm_training_tpu.models.llama.config import LlamaConfig
    from llm_training_tpu.models.llama.model import Llama

    m_s = Llama(LlamaConfig(**MOE_KW))
    m_p = Llama(LlamaConfig(**MOE_KW, pipeline_stages=2, pipeline_microbatches=4))
    ids, seg, pos = _inputs()
    # concentrate padding in the FIRST microbatch (rows 0-1): the router
    # stats normalize per dispatch by valid-token count, so equal-weight
    # pooling would diverge here — the token-share weighting must not
    seg = seg.at[:2, 10:].set(0)
    p_p = nn.meta.unbox(jax.jit(m_p.init)(jax.random.key(0), ids, seg, pos))["params"]
    p_s = _scan_params_from_pipeline(p_p, KW["num_hidden_layers"])

    out_s = jax.jit(m_s.apply)({"params": p_s}, ids, seg, pos)
    out_p = jax.jit(m_p.apply)({"params": p_p}, ids, seg, pos)
    np.testing.assert_allclose(
        np.asarray(out_p.logits), np.asarray(out_s.logits), atol=1e-5
    )
    np.testing.assert_allclose(
        float(out_p.aux_loss), float(out_s.aux_loss), rtol=1e-6
    )

    def loss_fn(params, model):
        out = model.apply({"params": params}, ids, seg, pos)
        logp = jax.nn.log_softmax(out.logits.astype(jnp.float32))
        return jnp.mean(logp[..., 0] ** 2) + 0.01 * out.aux_loss

    g_s = jax.jit(jax.grad(lambda p: loss_fn(p, m_s)))(p_s)
    g_p = _scan_params_from_pipeline(
        jax.jit(jax.grad(lambda p: loss_fn(p, m_p)))(p_p), KW["num_hidden_layers"]
    )
    for a, b in zip(jax.tree.leaves(g_s), jax.tree.leaves(g_p)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)


def test_pipeline_moe_rejects_expert_parallel(devices):
    objective = CLM(
        CLMConfig(
            model=ModelProvider(
                model_class="llm_training_tpu.models.Llama",
                model_kwargs=dict(
                    MOE_KW, pipeline_stages=2, pipeline_microbatches=2
                ),
            ),
            optim=OptimConfig(learning_rate=1e-3),
        )
    )
    dm = DummyDataModule(
        DummyDataModuleConfig(batch_size=8, max_length=32, num_samples=16, vocab_size=128)
    )
    trainer = Trainer(
        TrainerConfig(
            max_steps=1,
            mesh=MeshConfig(
                pipeline_parallel_size=2, expert_parallel_size=2,
                tensor_parallel_size=2,
            ),
        )
    )
    with pytest.raises(ValueError, match="expert_parallel"):
        trainer.fit(objective, dm)


def test_pipeline_hf_round_trip(devices):
    """HF checkpoint -> pipeline layout -> HF: loading a converted HF state
    dict into the [S, L/S] layout must give logits parity with the scan
    model loaded from the same dict, and exporting back must reproduce the
    HF tensors bitwise (the PP layout is a pure reshape)."""
    import flax.linen as nn

    from llm_training_tpu.models.hf_io import _pp_as_scan, load_pretrained_params
    from llm_training_tpu.models.llama.hf_conversion import params_to_hf

    m_s, m_p = _models()
    ids, seg, pos = _inputs()
    p_p = nn.meta.unbox(jax.jit(m_p.init)(jax.random.key(0), ids, seg, pos))["params"]

    # export the pipelined params to an HF state dict (exercises _pp_as_scan)
    sd = params_to_hf(_pp_as_scan({"params": p_p}, m_p.config), m_p.config)
    # load it back into BOTH layouts
    p_s2 = load_pretrained_params(m_s.config, sd)["params"]
    p_p2 = load_pretrained_params(m_p.config, sd)["params"]

    out_s = jax.jit(m_s.apply)({"params": p_s2}, ids, seg, pos)
    out_p = jax.jit(m_p.apply)({"params": p_p2}, ids, seg, pos)
    np.testing.assert_allclose(
        np.asarray(out_p.logits), np.asarray(out_s.logits), atol=1e-5
    )
    # pipeline leaves really are the stage layout
    leaf = jax.tree.leaves(p_p2["pipeline"]["ticks"]["layers"])[0]
    assert leaf.shape[:2] == (2, 2)
    # and exporting the re-loaded pipeline params reproduces the dict bitwise
    sd2 = params_to_hf(_pp_as_scan({"params": p_p2}, m_p.config), m_p.config)
    assert set(sd2) == set(sd)
    for k in sd:
        np.testing.assert_array_equal(np.asarray(sd2[k]), np.asarray(sd[k]))


def test_mesh_model_stage_mismatch_raises(devices):
    """pipe mesh axis without matching model stages would silently
    replicate all work across the axis — must fail loudly at fit."""
    objective = CLM(
        CLMConfig(
            model=ModelProvider(
                model_class="llm_training_tpu.models.Llama",
                model_kwargs=dict(KW),  # pipeline_stages defaults to 1
            ),
            optim=OptimConfig(learning_rate=1e-3),
        )
    )
    dm = DummyDataModule(
        DummyDataModuleConfig(batch_size=8, max_length=32, num_samples=16, vocab_size=128)
    )
    trainer = Trainer(
        TrainerConfig(
            max_steps=1,
            mesh=MeshConfig(pipeline_parallel_size=2, fsdp_size=2, tensor_parallel_size=2),
        )
    )
    with pytest.raises(ValueError, match="pipeline_stages"):
        trainer.fit(objective, dm)


@pytest.mark.slow
def test_pipeline_save_resume_matches_uninterrupted(devices, tmp_path):
    """Checkpoint/resume determinism holds for the [S, L/S] layout on the
    pipe mesh: a run interrupted at step 3 and resumed matches the
    uninterrupted run's losses exactly (orbax restores the stage-sharded
    stacks + the data stream position)."""
    from llm_training_tpu.trainer.checkpoint import CheckpointConfig, Checkpointer

    def objective():
        return CLM(
            CLMConfig(
                model=ModelProvider(
                    model_class="llm_training_tpu.models.Llama",
                    model_kwargs=dict(
                        KW, pipeline_stages=2, pipeline_microbatches=4
                    ),
                ),
                optim=OptimConfig(
                    learning_rate=1e-3, warmup_steps=2, lr_scheduler="constant"
                ),
            )
        )

    def data():
        return DummyDataModule(
            DummyDataModuleConfig(
                batch_size=8, max_length=32, num_samples=48, vocab_size=128
            )
        )

    mesh = MeshConfig(pipeline_parallel_size=2, fsdp_size=2, tensor_parallel_size=2)

    class Rec:
        def __init__(self):
            self.losses = {}

        def on_step_end(self, trainer, step, metrics):
            self.losses[step] = float(metrics["loss"])

    rec_full = Rec()
    Trainer(
        TrainerConfig(max_steps=6, log_every_n_steps=1, mesh=mesh),
        callbacks=[rec_full],
        checkpointer=Checkpointer(
            CheckpointConfig(dirpath=str(tmp_path / "full"), async_save=False)
        ),
    ).fit(objective(), data())

    ckpt_dir = str(tmp_path / "resume")
    rec_a, rec_b = Rec(), Rec()
    Trainer(
        TrainerConfig(
            max_steps=3, log_every_n_steps=1, checkpoint_every_n_steps=3, mesh=mesh
        ),
        callbacks=[rec_a],
        checkpointer=Checkpointer(CheckpointConfig(dirpath=ckpt_dir, async_save=False)),
    ).fit(objective(), data())
    Trainer(
        TrainerConfig(max_steps=6, log_every_n_steps=1, mesh=mesh),
        callbacks=[rec_b],
        checkpointer=Checkpointer(CheckpointConfig(dirpath=ckpt_dir, async_save=False)),
    ).fit(objective(), data())

    # the resumed run must actually RESUME at step 4 (a silent restore
    # miss would rerun 1-6 deterministically and pass the loss checks)
    assert set(rec_b.losses) == {4, 5, 6}
    for step in range(1, 4):  # checkpointing must not perturb the live run
        np.testing.assert_allclose(
            rec_a.losses[step], rec_full.losses[step], rtol=1e-6,
            err_msg=f"interrupted step {step}",
        )
    for step in range(4, 7):
        np.testing.assert_allclose(
            rec_b.losses[step], rec_full.losses[step], rtol=1e-6,
            err_msg=f"step {step}",
        )


def test_pipeline_config_validation():
    from llm_training_tpu.models.llama.config import LlamaConfig

    with pytest.raises(ValueError, match="split evenly"):
        LlamaConfig(**{**KW, "num_hidden_layers": 5}, pipeline_stages=2)
    with pytest.raises(ValueError, match="scan_layers"):
        LlamaConfig(**KW, pipeline_stages=2, scan_layers=False)
    with pytest.raises(ValueError, match="rotary"):
        LlamaConfig(**KW, pipeline_stages=2, position_embedding_type="learned")
    with pytest.raises(ValueError, match="ring_attention"):
        LlamaConfig(**KW, pipeline_stages=2, ring_attention=True)
