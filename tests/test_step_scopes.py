"""The name stack the benchmark's train and norm readers rest on
(docs/observability.md, "what the benchmark's train readers rest on"): the
two scopes the program sets for them (`rms_norm`, ops/rms_norm.py;
`optimizer`, trainer/trainer.py), the block scopes that were there, and
jax's own markers of the pass (`transpose(jvp(`, `rematted_computation`), on
a tiny Llama under selective recomputation; and that the two scopes are
names and nothing else: each program lowers to the same text without them.
A jax that renames a marker fails here, not silently in a reader."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import source_info_util

from llm_training_tpu.models import Llama, LlamaConfig
from llm_training_tpu.serve import ServeConfig, ServingEngine

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64, attention_impl="xla",
    compute_dtype="float32", param_dtype="float32",
)
SERVE = dict(max_batch=2, max_model_len=48, block_size=8, prefill_chunk=4, eos_token_id=None)
NEW_SCOPES = ("rms_norm", "optimizer")
PROGRAMS = ("train_step", "decode_step", "prefill_chunk")
BACKWARD, REMAT = "transpose(jvp(", "rematted_computation"


def _train_step():
    from llm_training_tpu.lms import CLM, CLMConfig
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.parallel.mesh import build_mesh
    from llm_training_tpu.trainer import Trainer, TrainerConfig
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES, _batch_shardings

    objective = CLM(CLMConfig(model=ModelProvider(model_class="Llama", model_kwargs=dict(
        TINY, enable_gradient_checkpointing=True, recompute_granularity="selective",
    ))))
    trainer = Trainer(TrainerConfig(mesh=MeshConfig()))
    mesh = trainer.mesh = build_mesh(trainer.config.mesh, jax.devices())
    keys = ("input_ids", "labels", "segment_ids", "position_ids")
    sample = {k: np.zeros((8, 16), np.int32) for k in keys}
    with mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        tx, _ = trainer._build_tx(objective)
        boxed = trainer._abstract_state(objective, sample, tx)
        trainer.state_shardings = trainer._state_shardings(boxed)
        step = jax.jit(
            trainer._build_step(objective, tx),
            in_shardings=(trainer.state_shardings, _batch_shardings(sample, mesh)),
            out_shardings=(trainer.state_shardings, None),
            donate_argnums=0,
        )
        return step.lower(
            nn.meta.unbox(boxed), {k: jax.ShapeDtypeStruct((8, 16), jnp.int32) for k in keys}
        )


def _serve_program(program):
    model = Llama(LlamaConfig(**TINY))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    engine = ServingEngine(model, variables, ServeConfig(**SERVE))
    jitted, packed = (
        (engine._decode_jit, engine._decode_packed) if program == "decode_step"
        else (engine._prefill_jit, engine._prefill_packed)
    )
    return jitted.lower(
        variables, packed, engine._pool_k, engine._pool_v, engine._rng, engine._last_tokens
    )


def _lowered(program):
    """The program built anew, so no trace of an earlier build is reused."""
    return _train_step() if program == "train_step" else _serve_program(program)


def _without_names(text: str) -> str:
    """A lowered program's text with every location (the name stack lives
    there) taken out: the `#loc` table and each op's reference into it."""
    body = (line for line in text.splitlines() if not line.startswith("#loc"))
    return "\n".join(re.sub(r" loc\((?:#loc\d*|\"[^\"]*\")\)", "", line) for line in body)


@pytest.fixture()
def no_new_scopes(monkeypatch):
    """`jax.named_scope` of one of the two names adds nothing to the stack."""
    enter = source_info_util.ExtendNameStackContextManager.__enter__

    def skipping(self):
        if self.name not in NEW_SCOPES:
            return enter(self)
        self.prev = source_info_util._source_info_context.context  # what __exit__ puts back
        return self.prev.name_stack

    monkeypatch.setattr(source_info_util.ExtendNameStackContextManager, "__enter__", skipping)


@pytest.fixture(scope="module")
def train_names():
    """Every whole op_name of the compiled train step: the names composed as
    a device profile shows them, transforms included (a reduction's inner
    computation keeps a relative name: no op of a device's line)."""
    names = re.findall(r'op_name="([^"]*)"', _train_step().compile().as_text())
    return [name for name in names if name.startswith("jit(train_step)/")]


@pytest.mark.parametrize("scope", [
    "rms_norm", "optimizer", "loss_ce", "/mlp/", "/self_attn/", "embed_tokens",
])
def test_train_step_names_hold_the_scope_a_reader_searches_for(train_names, scope):
    assert any(scope in name for name in train_names), scope


def test_train_step_names_say_the_pass_of_an_mlp_op(train_names):
    mlp = [name for name in train_names if "/mlp/" in name]
    forward = [n for n in mlp if BACKWARD not in n and REMAT not in n]
    backward = [n for n in mlp if BACKWARD in n and REMAT not in n]
    recomputed = [n for n in mlp if REMAT in n]
    assert forward and backward and recomputed
    assert all("/jvp(" in n for n in forward)
    # the second pass runs inside the backward: it holds both markers
    assert all(BACKWARD in n and "/checkpoint/" in n for n in recomputed)
    # the norms' own ops are named in all three passes too, inside no block
    norms = [n for n in train_names if "input_layernorm/rms_norm/" in n]
    assert {(BACKWARD in n, REMAT in n) for n in norms} == {(False, False), (True, False), (True, True)}
    # the needle is not a module's name: only the scope itself holds it
    assert all("/rms_norm/" in n for n in train_names if "rms_norm" in n)
    assert all(n.startswith("jit(train_step)/optimizer/") for n in train_names if "optimizer" in n)


@pytest.mark.parametrize("program", PROGRAMS[1:])
def test_serve_programs_name_their_norms(program):
    text = _lowered(program).as_text(debug_info=True)
    assert f"module @jit_{program} " in text
    assert "input_layernorm/rms_norm/" in text and "norm/rms_norm/" in text


@pytest.mark.parametrize("program", PROGRAMS)
def test_the_two_scopes_are_names_and_nothing_else(program, request):
    named = _lowered(program).as_text(debug_info=True)
    assert "rms_norm/" in named and ("optimizer/" in named) == (program == "train_step")
    request.getfixturevalue("no_new_scopes")
    bare = _lowered(program).as_text(debug_info=True)
    assert "rms_norm/" not in bare and "optimizer/" not in bare
    assert bare != named
    assert _without_names(bare) == _without_names(named)
