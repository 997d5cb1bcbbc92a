"""Test harness: single-process 8-device CPU mesh.

The reference has no test suite (SURVEY.md §4); this framework's tests run
every parallelism mode (DP/FSDP/TP/SP/CP) on a virtual 8-device CPU mesh via
XLA's host-platform device-count override, so distributed behavior is
CI-testable without hardware.

The harness forces the CPU platform (env AND jax.config, before any backend
is instantiated): the suite is defined over the virtual CPU mesh, and a
pytest process must never reach for — and so hold — an attached TPU, which
belongs to one process at a time.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_PLATFORM_NAME"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# tests compile what they test: the CLI's `_apply_extra_config` points a
# process at the checkout's persistent compile cache, and the in-process
# `main([...])` drives here must not turn every later test into a cache read
# (the switch is read once, at the process's first compile)
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


def fit_losses(model_class: str, model_kwargs: dict, mesh=None,
               max_steps: int = 6, lr: float = 1e-3) -> list[float]:
    """Run a tiny CLM fit and return the per-step losses (shared harness for
    the per-family sharded-mesh tests)."""
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.optim import OptimConfig
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    objective = CLM(CLMConfig(
        model=ModelProvider(model_class=model_class, model_kwargs=model_kwargs),
        optim=OptimConfig(learning_rate=lr, warmup_steps=2),
    ))
    data = DummyDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=32, num_samples=64,
        vocab_size=model_kwargs.get("vocab_size", 128),
    ))
    losses: list[float] = []

    class Track:
        def on_step_end(self, trainer, step, metrics):
            losses.append(float(metrics["loss"]))

    Trainer(
        TrainerConfig(max_steps=max_steps, log_every_n_steps=1,
                      mesh=mesh or MeshConfig()),
        callbacks=[Track()],
    ).fit(objective, data)
    return losses


_oracle_cache: dict[int, tuple] = {}  # id(model) -> (model, jitted forward)
ORACLE_WIDTH = 32  # static pad width: covers every prompt + n the callers use


def full_forward_greedy(model, variables, prompt, n):
    """The cache-correctness oracle of test_infer / test_serve: n argmax
    tokens from n FULL forward passes (no cache) — jitted ONCE per model at
    a padded static width (length traced, pads masked via segment ids) so
    each step is a cheap cached call, not an eager CPU forward (~1.6 s each
    for the tiny scan model)."""
    import jax.numpy as jnp
    import numpy as np

    entry = _oracle_cache.get(id(model))
    if entry is None or entry[0] is not model:

        @jax.jit
        def fwd(variables, ids, length):
            seg = (jnp.arange(ids.shape[1]) < length).astype(jnp.int32)[None]
            out = model.apply(variables, input_ids=ids, segment_ids=seg)
            logits = jax.lax.dynamic_index_in_dim(
                out.logits[0], length - 1, axis=0, keepdims=False
            )
            return jnp.argmax(logits)

        entry = (model, fwd)  # strong model ref: id() can't be recycled
        _oracle_cache[id(model)] = entry
    fwd = entry[1]
    seq = list(prompt)
    for _ in range(n):
        ids = np.zeros((1, ORACLE_WIDTH), np.int32)
        ids[0, : len(seq)] = seq
        seq.append(int(fwd(variables, jnp.asarray(ids), jnp.int32(len(seq)))))
    return seq[len(prompt):]

