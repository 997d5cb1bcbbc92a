"""`models/llama/model.py:_plain_rows` (PR 48): a decoding layer hands its head
projections on as `[rows, out]` behind a barrier, so that the chip's compiler
reads their weights where they lie in the layer stack. It computes nothing:
on seeded weights a chunk and a decoding step give the logits of the path
without it, on the three stacks whose cells it was written for (Phi-3's GQA,
Trinity's gated attention with its two page groups, openPangu's latent
attention, cut small), and a forward without a cache lowers to the same text.
What the barrier does to the chip's program is held in
`tests/test_chip_compile.py:_projection_slices`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.infer.cache import init_decode_state
from llm_training_tpu.models import Phi3, Phi3Config
from llm_training_tpu.models.afmoe import Afmoe, AfmoeConfig
from llm_training_tpu.models.afmoe import model as afmoe_model
from llm_training_tpu.models.deepseek import Deepseek, DeepseekConfig
from llm_training_tpu.models.deepseek import model as deepseek_model
from llm_training_tpu.models.llama import model as llama_model
from tests import test_afmoe, test_pangu_ultra_moe, test_phi3

CHUNK, LENGTH = 8, 16


def _stack(family):
    """(model, seeded variables, the module whose attention calls the helper)."""
    if family == "phi3":
        float32 = dict(param_dtype="float32", compute_dtype="float32", attention_impl="xla")
        model = Phi3(Phi3Config(**test_phi3.TINY, **float32))
        return model, test_afmoe.seeded_variables(model), llama_model
    if family == "trinity":
        model = Afmoe(AfmoeConfig(**{**test_afmoe.TINY, "num_hidden_layers": 6}))
        return model, test_afmoe.seeded_variables(model), afmoe_model
    model = Deepseek(DeepseekConfig(**test_pangu_ultra_moe.TINY))
    return model, test_pangu_ultra_moe.seeded_variables(model), deepseek_model


def _programs(model):
    """(a chunk of 8 tokens into an empty dense cache and then one decoding
    step a row, jitted: `-> (chunk logits, step logits)`; a forward without a
    cache, jitted), traced anew at each call of this function."""
    ids = jnp.asarray(np.random.default_rng(3).integers(0, 160, size=(2, CHUNK + 1)), jnp.int32)
    ones = jnp.ones((2, CHUNK), jnp.int32)

    def decode(variables):
        state = init_decode_state(model.config, 2, LENGTH)
        chunk = model.apply(
            variables, input_ids=ids[:, :CHUNK], segment_ids=ones,
            position_ids=jnp.tile(jnp.arange(CHUNK), (2, 1)), decode_state=state,
        )
        step = model.apply(
            variables, input_ids=ids[:, CHUNK:], segment_ids=ones[:, :1],
            position_ids=jnp.full((2, 1), CHUNK), decode_state=chunk.decode_state,
        )
        return chunk.logits, step.logits

    def forward(variables):
        return model.apply(variables, input_ids=ids[:, :CHUNK], segment_ids=ones).logits

    return jax.jit(decode), jax.jit(forward)


@pytest.mark.parametrize("family", ["phi3", "trinity", "pangu"])
def test_decoding_behind_the_barrier_is_the_path_without_it(family, monkeypatch):
    model, variables, module = _stack(family)
    decode, forward = _programs(model)
    # the helper engages where a cache is open
    assert "optimization_barrier" in decode.lower(variables).as_text()
    with_helper = decode(variables)
    training = forward.lower(variables).as_text()
    assert "optimization_barrier" not in training  # and nowhere else

    monkeypatch.setattr(module, "_plain_rows", lambda cache, projected: projected)
    decode, forward = _programs(model)
    assert "optimization_barrier" not in decode.lower(variables).as_text()
    for mine, parents in zip(with_helper, decode(variables)):
        assert np.isfinite(np.asarray(mine)).all()
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(parents))
    assert forward.lower(variables).as_text() == training
