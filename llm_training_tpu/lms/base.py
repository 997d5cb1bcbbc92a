"""Objective base: config surface, model protocol, model provider.

Capability parity: reference `lms/base_lm.py:32` + `lms/base_lm_config.py`
(init/load weights, optim config, frozen-module regexes, grad-norm logging)
and `lms/model_provider.py:9-22` (YAML `{model_class, model_config}` node →
lazy model factory). The meta-device/materialization machinery of the
reference (`base_lm.py:135-231`) has no analogue: JAX init is already
abstract (`jax.eval_shape`) and weights stream straight into sharded arrays.
"""

from __future__ import annotations

import importlib
from typing import Any, Protocol, runtime_checkable

import jax.numpy as jnp
from pydantic import BaseModel, ConfigDict

from llm_training_tpu.models.base import CausalLMOutput
from llm_training_tpu.optim.builder import OptimConfig


@runtime_checkable
class CausalLM(Protocol):
    """Structural protocol for anything an objective can drive
    (reference `lms/protos/clm_proto.py:9-26`).

    `decode_state` (a `models.base.DecodeState` KV cache) is OPTIONAL for
    implementations: a family whose config declares what its stack caches
    (`BaseModelConfig.cache_specs`) accepts it and runs under the inference
    engine's prefill/decode programs; `infer.engine.supports_decoding` asks
    for the declaration and the engines raise NotImplementedError without."""

    def __call__(
        self,
        input_ids: jnp.ndarray | None = None,
        segment_ids: jnp.ndarray | None = None,
        position_ids: jnp.ndarray | None = None,
        inputs_embeds: jnp.ndarray | None = None,
        compute_logits: bool = True,
        return_last_hidden_states: bool = False,
    ) -> CausalLMOutput: ...

    def get_input_embeddings_path(self) -> str: ...

    def get_output_embeddings_path(self) -> str | None: ...


class BaseLMConfig(BaseModel):
    model_config = ConfigDict(extra="forbid")

    init_weights: bool = True
    load_weights: bool = True
    # HF checkpoint dir to initialize from (reference `pre_trained_weights`,
    # `base_lm_config.py:13-43`); streamed into sharded arrays via hf_io
    pre_trained_weights: str | None = None
    optim: OptimConfig = OptimConfig()
    frozen_modules: list[str] = []
    log_grad_norm: bool = True


def resolve_pretrained_source(objective: Any) -> str | None:
    """Objective-level `pre_trained_weights` wins; else the model config's
    own weight-source field (reference `base_model.py:32-33`)."""
    return (
        objective.config.pre_trained_weights
        or objective.model.config.pre_trained_weights
    )


def load_single_model_pretrained(objective: Any, shardings: Any, dtypes: Any) -> Any:
    """Shared CLM/ORPO loader: stream the HF weight source into sharded
    arrays (reference `base_lm.py:175-193`)."""
    from llm_training_tpu.models.hf_io import load_pretrained_params

    return load_pretrained_params(
        objective.model.config, resolve_pretrained_source(objective), shardings, dtypes
    )


class ModelProvider(BaseModel):
    """`{model_class, model_config}` config node -> validated config +
    lazy model factory (reference `lms/model_provider.py:9-22`)."""

    model_config = ConfigDict(extra="forbid")

    model_class: str
    model_kwargs: dict[str, Any] = {}

    def _resolve(self) -> tuple[type, type]:
        module_name, _, class_name = self.model_class.rpartition(".")
        if not module_name:
            module_name = "llm_training_tpu.models"
        module = importlib.import_module(module_name)
        model_cls = getattr(module, class_name)
        config_cls = getattr(module, class_name + "Config")
        return model_cls, config_cls

    def get_config(self) -> Any:
        _, config_cls = self._resolve()
        return config_cls(**self.model_kwargs)

    def get_model(self) -> Any:
        model_cls, _ = self._resolve()
        return model_cls(self.get_config())
