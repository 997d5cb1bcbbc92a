"""Each plain reference against the repo's module at a tiny size, in float32
on the CPU: full-sequence logits on packed rows, and for training the loss,
the first clipped gradient and three AdamW steps against optax."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks import common
from benchmarks.references import olmoe, phi3
from conftest import TINY


def f32_config(name):
    cfg = {**TINY["configs"][name]}
    cfg["program"] = {**cfg["program"], "model_kwargs": {
        **cfg["program"]["model_kwargs"], "param_dtype": "float32", "compute_dtype": "float32",
        "attention_impl": "xla", "enable_gradient_checkpointing": False}}
    return cfg


def packed_batch(vocab, rows=2):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(rows, 48)).astype(np.int32)
    seg = np.concatenate([np.full(20, 1), np.full(24, 2), np.zeros(4)]).astype(np.int32)
    pos = np.concatenate([np.arange(20), np.arange(24), np.zeros(4)]).astype(np.int32)
    return {"input_ids": ids, "segment_ids": np.tile(seg, (rows, 1)), "position_ids": np.tile(pos, (rows, 1))}


@pytest.mark.parametrize("name,reference", [("tiny-phi3", phi3), ("tiny-olmoe", olmoe)])
def test_logits_agree_with_the_module(name, reference):
    cfg = f32_config(name)
    model = common.build_model(cfg)
    batch = packed_batch(cfg["vocab_size"])
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    variables = nn.meta.unbox(jax.jit(lambda k: common.seeded_tree(k, abstract, 0.02))(common.base_key(7)))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v: model.apply(v, **{k: jnp.asarray(a) for k, a in batch.items()}).logits)(variables)
    got = reference.logits(variables["params"], cfg, *(jnp.asarray(batch[k]) for k in ("input_ids", "segment_ids", "position_ids")))
    real = batch["segment_ids"] > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 2e-5


def test_train_steps_agree_with_the_objective_and_optax():
    from benchmarks.runners import train_fit
    from llm_training_tpu.lms.clm import CLM, CLMConfig
    from llm_training_tpu.optim.builder import OptimConfig, build_optimizer

    cfg = f32_config("tiny-phi3-train")
    model = common.build_model(cfg)
    objective = CLM(CLMConfig(optim=OptimConfig(**cfg["train"]["optim"]), ce_chunk_size=64), model=model)
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = nn.meta.unbox(jax.jit(lambda k: common.seeded_tree(k, abstract, 0.02))(common.base_key(3)))
    batches = [packed_batch(cfg["vocab_size"], rows=4) for _ in range(3)]
    for i, b in enumerate(batches):
        b["input_ids"] = (b["input_ids"] + i) % cfg["vocab_size"]
    tx, _ = build_optimizer(objective.config.optim, num_total_steps=100)

    @jax.jit
    def step(p, opt, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda q: objective.loss_and_metrics(q, {**batch, "labels": batch["input_ids"]}), has_aux=True)(p)
        updates, opt = tx.update(grads, opt, p)
        return optax.apply_updates(p, updates), opt, loss

    p, opt, losses = params, tx.init(params), []
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            p, opt, loss = step(p, opt, {k: jnp.asarray(a) for k, a in b.items()})
            losses.append(float(loss))
            if i == 0:
                mu = train_fit.find_first_moment(opt)
                first = {k: float(jnp.linalg.norm(v)) / 0.1 for k, v in train_fit.flat(mu).items()}
    change = {k: float(jnp.linalg.norm(a - b)) for (k, a), b in zip(train_fit.flat(p).items(), jax.tree.leaves(params))}
    optim = {**cfg["train"]["optim"]["optimizer_kwargs"], "learning_rate": 1e-05, "grad_clip_norm": 1.0}
    out = phi3.train_steps(lambda: jax.tree.map(jnp.copy, params["params"]), cfg, optim, batches, rows_per_block=2)
    assert np.allclose(out["losses"], losses, atol=2e-5)
    for key, want in (("grad_norms", first), ("change_norms", change)):
        got = {"params/" + k: float(v) for k, v in train_fit.flat(out[key]).items()}
        gap, where = train_fit.worst_leaf_gap(got, want)
        assert gap < 2e-3, (key, gap, where)
