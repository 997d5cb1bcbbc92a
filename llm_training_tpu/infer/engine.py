"""Batched KV-cache generation engine.

Two jitted programs over the SAME sharded decoder stack the trainer runs
(pjit-style train/infer unification, arxiv 2204.06514):

- `prefill`: the whole (left-padded) prompt batch at full width — one
  forward that writes every prompt position's k/v into the cache and
  samples the first new token from the last column's logits;
- `decode_step`: one token per row, appended to the cache at the shared
  dynamic index, next token sampled in-program (greedy / temperature /
  top-k / top-p under an explicit PRNG key).

Prompts are LEFT-padded to a common width so the whole batch shares one
cache append index (`models/base.py:DecodeState`); per-row RoPE positions
subtract the pad length, and pad slots carry segment id 0 so the attention
mask never reaches them. The cache buffers are donated through both
programs — decoding mutates them in place in HBM.

Decode telemetry (prefill_time_s, tokens/sec, cache bytes) is published
through the process registry, so the `generate` CLI lands it in
`telemetry.jsonl` and `report` renders it with zero extra wiring.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import BaseModel, ConfigDict, model_validator

from llm_training_tpu.infer.cache import (
    cache_bytes,
    decode_state_shardings,
    init_decode_state,
)
from llm_training_tpu.infer.sampling import (
    SamplingConfig,
    sample_tokens_with_logprob,
)
from llm_training_tpu.models.base import DecodeState

logger = logging.getLogger(__name__)


class GenerateConfig(BaseModel):
    """Knobs of one `generate` call (docs/inference.md)."""

    model_config = ConfigDict(extra="forbid")

    max_new_tokens: int = 32
    # cache capacity; default = padded prompt width + max_new_tokens
    max_length: int | None = None
    # None/'param' = the model's param dtype; 'float32' | 'bfloat16'
    cache_dtype: str | None = None
    seed: int = 0
    # stop a row at this token; generation ends early once every row stopped
    eos_token_id: int | None = None
    sampling: SamplingConfig = SamplingConfig()

    @model_validator(mode="after")
    def _validate(self) -> "GenerateConfig":
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}"
            )
        if self.max_length is not None and self.max_length < 1:
            raise ValueError(f"max_length must be >= 1, got {self.max_length}")
        return self


def mesh_context(mesh: Any, rules: Any = ()) -> contextlib.ExitStack:
    """The ambience every sharded inference/serving program runs under:
    the mesh + the logical axis rules, or nothing off-mesh. Shared by
    `InferenceEngine` and `serve.ServingEngine`."""
    context = contextlib.ExitStack()
    if mesh is not None:
        import flax.linen as nn

        context.enter_context(mesh)
        context.enter_context(nn.logical_axis_rules(rules or ()))
    return context


def supports_decoding(model: Any) -> bool:
    """A family decodes when its config declares what its stack caches
    (`BaseModelConfig.cache_specs`): the shared llama/gemma/phi3 stacks,
    solar_open2, whose linear-attention (KDA) layers keep a fixed state slab
    a decode slot beside the key/value cache, and longcat_flash and deepseek
    (V2, V3, pangu_ultra_moe), whose MLA blocks cache one latent row a token.
    Not declared yet: bamba's mamba layers, qwen3-next's and minimax's linear
    attention."""
    declared = getattr(model.config, "cache_specs", None)
    return declared is not None and declared() is not None


def _left_pad(prompts: Sequence[Sequence[int]], pad_id: int) -> tuple[np.ndarray, np.ndarray]:
    """-> (input_ids [B, P] left-padded, pad_lens [B])."""
    if len(prompts) == 0:
        raise ValueError("generate() needs at least one prompt")
    lengths = [len(p) for p in prompts]
    if min(lengths) == 0:
        raise ValueError("empty prompt: each prompt needs at least one token")
    width = max(lengths)
    ids = np.full((len(prompts), width), pad_id, np.int32)
    for row, prompt in enumerate(prompts):
        ids[row, width - len(prompt):] = np.asarray(prompt, np.int32)
    return ids, np.asarray([width - n for n in lengths], np.int32)


class InferenceEngine:
    """Drives a restored model over the decode programs.

    `variables` is the model's full variable dict (what `model.init` /
    checkpoint restore return: `{"params": ...}`); `mesh` + `rules` give
    the cache its sharding (heads over 'tensor', batch over 'data'/'fsdp')
    — omit both for single-process use (tests)."""

    def __init__(
        self,
        model: Any,
        variables: Any,
        mesh: Any | None = None,
        rules: Any = (),
    ):
        if not supports_decoding(model):
            raise NotImplementedError(
                f"{type(model).__name__} does not support KV-cache decoding "
                "yet: its config declares no cache and its __call__ takes no "
                "decode_state (non-standard sequence mixers need their own "
                "cache layout — see docs/inference.md)"
            )
        self.model = model
        self.variables = variables
        self.mesh = mesh
        self.rules = rules
        self._prefill_jit = None
        self._decode_jit = None
        self._sampling: SamplingConfig | None = None

    # ------------------------------------------------------------ programs

    def _build_programs(self, sampling: SamplingConfig):
        """(Re)build the jitted prefill/decode programs; cached until the
        sampling config changes (it is baked into the traces)."""
        if self._sampling == sampling and self._prefill_jit is not None:
            return
        model = self.model

        def prefill(variables, input_ids, segment_ids, position_ids, state, rng):
            out = model.apply(
                variables,
                input_ids=input_ids,
                segment_ids=segment_ids,
                position_ids=position_ids,
                decode_state=state,
            )
            logits = out.logits[:, -1, :].astype(jnp.float32)
            token, logprob = sample_tokens_with_logprob(logits, rng, sampling)
            return out.decode_state, token, logprob

        def decode_step(variables, tokens, pad_lens, state, rng):
            # per-row RoPE position: absolute cache slot minus left-pad
            position_ids = (state.index - pad_lens)[:, None]
            out = model.apply(
                variables,
                input_ids=tokens[:, None],
                segment_ids=jnp.ones((tokens.shape[0], 1), jnp.int32),
                position_ids=position_ids,
                decode_state=state,
            )
            logits = out.logits[:, -1, :].astype(jnp.float32)
            token, logprob = sample_tokens_with_logprob(logits, rng, sampling)
            return out.decode_state, token, logprob

        # the cache is donated: k/v update in place across the token loop
        self._prefill_jit = jax.jit(prefill, donate_argnums=(4,))
        self._decode_jit = jax.jit(decode_step, donate_argnums=(3,))
        self._sampling = sampling

    # ------------------------------------------------------------ generate

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        config: GenerateConfig | None = None,
    ) -> dict[str, Any]:
        """-> {"tokens": new tokens per row (truncated after eos),
        "logprobs": chosen-token logprobs per row (aligned with "tokens"),
        "sequences": prompt + new tokens, "lengths": generated count per
        row, "stop_reasons": "eos" | "max_tokens" per row, "stats": decode
        telemetry}."""
        from llm_training_tpu.telemetry import get_registry

        config = config or GenerateConfig()
        model_config = self.model.config
        pad_id = model_config.pad_token_id or 0
        ids, pad_lens = _left_pad(prompts, pad_id)
        batch, width = ids.shape
        max_length = config.max_length or width + config.max_new_tokens
        if max_length < width + config.max_new_tokens:
            raise ValueError(
                f"max_length {max_length} cannot hold the padded prompt "
                f"({width}) plus max_new_tokens ({config.max_new_tokens})"
            )
        self._build_programs(config.sampling)

        with mesh_context(self.mesh, self.rules):
            state = init_decode_state(
                model_config, batch, max_length,
                mesh=self.mesh, rules=self.rules,
                cache_dtype=config.cache_dtype,
                # length-dependent RoPE variants select tables from the
                # length the generation will REACH, not the cache capacity
                rope_length=width + config.max_new_tokens,
            )
            # decode/cache_bytes is published by init_decode_state itself
            registry = get_registry()
            registry.gauge("decode/max_length").set(max_length)

            # a prompt may legitimately CONTAIN pad_id tokens, so padding is
            # identified positionally (the left-pad region), not by value
            segment_ids = (
                np.arange(width)[None, :] >= pad_lens[:, None]
            ).astype(np.int32)
            position_ids = np.maximum(
                np.arange(width)[None, :] - pad_lens[:, None], 0
            ).astype(np.int32)
            ids_j, seg_j, pos_j, pad_j = self._place(
                ids, segment_ids, position_ids, pad_lens
            )

            rng = jax.random.key(config.seed)
            t0 = time.perf_counter()
            state, token, logprob = self._prefill_jit(
                self.variables, ids_j, seg_j, pos_j, state,
                jax.random.fold_in(rng, 0),
            )
            token.block_until_ready()
            prefill_s = time.perf_counter() - t0
            registry.gauge("decode/prefill_time_s").set(prefill_s)

            eos = config.eos_token_id
            if eos is not None:
                # early-stop needs each token on host: the per-step fetch
                # IS the stop check (and the natural decode sync point)
                new_tokens = [np.asarray(jax.device_get(token))]
                new_logprobs = [np.asarray(jax.device_get(logprob))]
                step_times: list[float] = []
                for step in range(1, config.max_new_tokens):
                    t_step = time.perf_counter()
                    state, token, logprob = self._decode_jit(
                        self.variables, token, pad_j, state,
                        jax.random.fold_in(rng, step),
                    )
                    host_token = np.asarray(jax.device_get(token))
                    step_times.append(time.perf_counter() - t_step)
                    new_tokens.append(host_token)
                    new_logprobs.append(np.asarray(jax.device_get(logprob)))
                    if all(eos in row for row in np.stack(new_tokens, 1)):
                        break
                grid = np.stack(new_tokens, axis=1)  # [B, T]
                lp_grid = np.stack(new_logprobs, axis=1)
                steady = step_times[1:] if len(step_times) > 1 else step_times
                steady_steps, steady_s = len(steady), sum(steady)
            else:
                # no stop token: free-running dispatch, ONE fence at the
                # end — per-step host round trips would serialize the loop
                # for nothing. The first decode step is fenced separately
                # so its trace+compile stays out of the steady-state rate.
                device_tokens = [token]
                device_logprobs = [logprob]
                steady_steps = steady_s = 0
                for step in range(1, config.max_new_tokens):
                    state, token, logprob = self._decode_jit(
                        self.variables, token, pad_j, state,
                        jax.random.fold_in(rng, step),
                    )
                    device_tokens.append(token)
                    device_logprobs.append(logprob)
                    if step == 1:
                        jax.device_get(token)  # compile fence
                        t_steady = time.perf_counter()
                host = jax.device_get(device_tokens)  # the real fence
                host_lp = jax.device_get(device_logprobs)
                if config.max_new_tokens > 2:
                    steady_s = time.perf_counter() - t_steady
                    steady_steps = config.max_new_tokens - 2
                grid = np.stack([np.asarray(t) for t in host], axis=1)
                lp_grid = np.stack([np.asarray(t) for t in host_lp], axis=1)
        tokens, logprobs, sequences, lengths, stop_reasons = [], [], [], [], []
        for row in range(batch):
            emitted = grid[row].tolist()
            if eos is not None and eos in emitted:
                emitted = emitted[: emitted.index(eos) + 1]
                stop_reasons.append("eos")
            else:
                stop_reasons.append("max_tokens")
            tokens.append(emitted)
            logprobs.append([float(v) for v in lp_grid[row, : len(emitted)]])
            lengths.append(len(emitted))
            sequences.append(list(prompts[row]) + emitted)

        # steady-state decode rate: the first decode step carries the
        # trace+compile and is excluded in both loop variants above
        decode_tps = batch * steady_steps / steady_s if steady_s > 0 else 0.0
        stats = {
            "decode/prefill_time_s": prefill_s,
            "decode/tokens_per_sec": decode_tps,
            "decode/new_tokens": int(sum(len(t) for t in tokens)),
            "decode/cache_bytes": cache_bytes(state),
            "decode/max_length": max_length,
        }
        registry.gauge("decode/tokens_per_sec").set(decode_tps)
        registry.gauge("decode/new_tokens").set(stats["decode/new_tokens"])
        logger.info(
            "generate: %d prompts, %d new tokens | prefill %.3fs | "
            "%.1f tokens/s decode",
            batch, stats["decode/new_tokens"], prefill_s, decode_tps,
        )
        return {
            "tokens": tokens,
            # chosen-token logprob per emitted token, under the sampled
            # distribution (raw for greedy, filtered for temperature > 0 —
            # see infer/sampling.py:sample_tokens_with_logprob)
            "logprobs": logprobs,
            "sequences": sequences,
            # per-row generated length + why each row stopped ("eos" |
            # "max_tokens") — callers (serve scheduler, evaluate, generate)
            # no longer re-scan the outputs for the eos token
            "lengths": lengths,
            "stop_reasons": stop_reasons,
            "stats": stats,
        }

    def _place(self, ids, segment_ids, position_ids, pad_lens):
        """Host arrays -> device, batch-sharded over the mesh when the
        batch divides its data ways (replicated otherwise)."""
        arrays = (
            jnp.asarray(ids), jnp.asarray(segment_ids),
            jnp.asarray(position_ids), jnp.asarray(pad_lens),
        )
        if self.mesh is None:
            return arrays
        from jax.sharding import NamedSharding

        from llm_training_tpu.infer.cache import _divisible_spec

        batch2d = NamedSharding(
            self.mesh,
            _divisible_spec(arrays[0].shape, ("batch", None), self.mesh, self.rules),
        )
        batch1d = NamedSharding(
            self.mesh,
            _divisible_spec(arrays[3].shape, ("batch",), self.mesh, self.rules),
        )
        return tuple(
            jax.device_put(a, batch1d if a.ndim == 1 else batch2d)
            for a in arrays
        )
