"""Loss-curve parity vs the torch/CUDA reference semantics.

The project's north star (`BASELINE.json`) is throughput at "loss-curve parity vs the CUDA
FSDP baseline". This harness proves the training *math* matches end to end:
the same tiny Llama (identical weights via the HF converter), the same token
stream, and the same optimizer hyperparameters are trained for 20 steps in
torch (the reference's stack) and in this framework, and the two loss
trajectories must track within fp32 drift. Covers: forward parity, CE
shift/masking, AdamW semantics (decoupled weight decay), global-norm grad
clipping, and cosine-warmup LR scheduling.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

torch = pytest.importorskip("torch")

from transformers import LlamaConfig as HFLlamaConfig  # noqa: E402
from transformers import LlamaForCausalLM  # noqa: E402

from llm_training_tpu.lms.clm import CLM, CLMConfig  # noqa: E402
from llm_training_tpu.models.llama import Llama  # noqa: E402
from llm_training_tpu.models.llama.hf_conversion import (  # noqa: E402
    config_from_hf,
    params_from_hf,
)

STEPS = 20
LR = 1e-3
WARMUP = 5
WD = 0.1
BETAS = (0.9, 0.95)
EPS = 1e-8
CLIP = 1.0
BATCH, SEQ, VOCAB = 4, 32, 128


def _lr_at(step: int) -> float:
    """linear warmup -> cosine decay to 0 (shared schedule definition)."""
    if step < WARMUP:
        return LR * (step + 1) / WARMUP
    progress = (step - WARMUP) / max(STEPS - WARMUP, 1)
    return LR * 0.5 * (1 + math.cos(math.pi * progress))


def _data():
    rng = np.random.default_rng(0)
    return rng.integers(0, VOCAB, (STEPS, BATCH, SEQ)).astype(np.int64)


def _hf_model():
    torch.manual_seed(0)
    return LlamaForCausalLM(
        HFLlamaConfig(
            vocab_size=VOCAB, hidden_size=64, intermediate_size=112,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=SEQ,
        )
    )


def _train_torch(model, data) -> list[float]:
    model.train()
    opt = torch.optim.AdamW(
        model.parameters(), lr=LR, betas=BETAS, eps=EPS, weight_decay=WD
    )
    losses = []
    for step in range(STEPS):
        for group in opt.param_groups:
            group["lr"] = _lr_at(step)
        ids = torch.tensor(data[step])
        out = model(ids, labels=ids)  # HF shifts internally
        opt.zero_grad()
        out.loss.backward()
        torch.nn.utils.clip_grad_norm_(model.parameters(), CLIP)
        opt.step()
        losses.append(float(out.loss.detach()))
    return losses


def _train_ours(hf_model, data) -> list[float]:
    cfg = config_from_hf(
        hf_model.config, compute_dtype="float32", param_dtype="float32"
    )
    params = jax.tree.map(jnp.asarray, params_from_hf(hf_model.state_dict(), cfg))
    objective = CLM(CLMConfig(), model=Llama(cfg))

    def schedule(count):
        # the exact `_lr_at` math, traceable
        warm = LR * (count + 1) / WARMUP
        progress = (count - WARMUP) / max(STEPS - WARMUP, 1)
        cos = LR * 0.5 * (1 + jnp.cos(jnp.pi * progress))
        return jnp.where(count < WARMUP, warm, cos)
    tx = optax.chain(
        optax.clip_by_global_norm(CLIP),
        optax.adamw(schedule, b1=BETAS[0], b2=BETAS[1], eps=EPS, weight_decay=WD),
    )
    opt_state = tx.init(params)

    @jax.jit
    def step_fn(params, opt_state, ids):
        def loss_fn(p):
            loss, _ = objective.loss_and_metrics(p, {"input_ids": ids}, train=False)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    losses = []
    for step in range(STEPS):
        params, opt_state, loss = step_fn(params, opt_state, jnp.asarray(data[step]))
        losses.append(float(loss))
    return losses


@pytest.mark.slow
def test_loss_curves_match_torch_reference():
    data = _data()
    hf_model = _hf_model()
    torch_losses = _train_torch(_hf_model(), data)
    our_losses = _train_ours(hf_model, data)

    # step 0: pure forward parity; later steps accumulate optimizer drift
    assert abs(our_losses[0] - torch_losses[0]) < 1e-4, (our_losses[0], torch_losses[0])
    np.testing.assert_allclose(our_losses, torch_losses, rtol=2e-3, atol=2e-3)
    # and training actually learns (loss drops on a fixed random stream it
    # can memorize a little)
    assert our_losses[-1] < our_losses[0]
