"""RL post-training tests (docs/post-training.md): sampled-logprob
correctness against a numpy reference under top-k/top-p, decode-logprob
fidelity (incremental paged decode == teacher-forced full forward),
group-relative advantages, verifiable rewards, generation-staleness
rejection, the fused-vs-host weight-sync stream-equivalence contract,
SLO-breach rollout yielding, and the frozen-modules restore-tree fix the
GRPO policy/reference layout depends on. The end-to-end learning +
crash-resume legs live in scripts/rl_smoke.py (precommit)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.infer import SamplingConfig
from llm_training_tpu.infer.sampling import (
    filtered_logits,
    sample_tokens_with_logprob,
)
from llm_training_tpu.lms.grpo import group_relative_advantages
from llm_training_tpu.models import Gemma, GemmaConfig, Llama, LlamaConfig
from llm_training_tpu.rl import RolloutCollector, resolve_reward, sync_weights
from llm_training_tpu.rl import reward as reward_mod
from llm_training_tpu.rl.rollout import parse_rollout_id, rollout_id
from llm_training_tpu.serve import ServeConfig, ServingEngine
from llm_training_tpu.telemetry.registry import TelemetryRegistry
from llm_training_tpu.telemetry.slo import SLOMonitor, specs_from_config

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64, attention_impl="xla",
    compute_dtype="float32", param_dtype="float32",
)


def _init(model, seed=0):
    return jax.jit(model.init)(jax.random.key(seed), np.zeros((1, 4), np.int32))


def _engine(model, variables, **overrides):
    config = ServeConfig(**{
        "max_batch": 2, "max_model_len": 48, "block_size": 8,
        "prefill_chunk": 4, "eos_token_id": None, **overrides,
    })
    return ServingEngine(model, variables, config)


# ------------------------------------------------- sampled-logprob unit


def _numpy_filtered_logprobs(logits, temperature, top_k, top_p):
    """Independent reference for the behavior distribution: temperature
    scale, then top-k, then top-p over the survivors (HF order), then
    log-softmax. Mirrors docs/inference.md semantics, not the jax code."""
    x = np.asarray(logits, np.float64) / temperature
    if top_k is not None and top_k < x.shape[-1]:
        threshold = np.sort(x, axis=-1)[..., -top_k][..., None]
        x = np.where(x >= threshold, x, -1e10)
    if top_p is not None:
        order = np.argsort(-x, axis=-1)
        sorted_x = np.take_along_axis(x, order, axis=-1)
        probs = np.exp(sorted_x - sorted_x.max(-1, keepdims=True))
        probs /= probs.sum(-1, keepdims=True)
        mass_before = np.cumsum(probs, axis=-1) - probs
        keep = mass_before < top_p
        threshold = np.min(
            np.where(keep, sorted_x, np.inf), axis=-1, keepdims=True
        )
        x = np.where(x >= threshold, x, -1e10)
    x -= x.max(-1, keepdims=True)
    return x - np.log(np.exp(x).sum(-1, keepdims=True))


@pytest.mark.parametrize(
    "temperature,top_k,top_p",
    [(1.0, None, None), (0.7, 8, None), (1.3, None, 0.9), (0.9, 12, 0.8)],
    ids=["plain", "top_k", "top_p", "both"],
)
def test_sampled_logprob_matches_numpy_reference(temperature, top_k, top_p):
    """The logprob the sampler returns must be the chosen token's mass
    under the FILTERED distribution it actually drew from — pinned
    against an independent numpy implementation of the filter chain."""
    logits = jax.random.normal(jax.random.key(3), (5, 32)) * 3.0
    config = SamplingConfig(temperature=temperature, top_k=top_k, top_p=top_p)
    tokens, logprobs = sample_tokens_with_logprob(
        logits, jax.random.key(7), config
    )
    reference = _numpy_filtered_logprobs(logits, temperature, top_k, top_p)
    for row in range(5):
        np.testing.assert_allclose(
            float(logprobs[row]), reference[row, int(tokens[row])],
            rtol=1e-4, atol=1e-5,
        )
    # a filtered-out token carries ~no mass in the behavior distribution
    if top_k is not None:
        worst = int(jnp.argmin(logits[0]))
        filtered = jax.nn.log_softmax(filtered_logits(logits, config))
        assert float(filtered[0, worst]) < -1e8


def test_greedy_logprob_is_raw_log_softmax():
    """temperature=0 scores under the RAW distribution, so incremental
    greedy-decode logprobs are comparable to a teacher-forced forward."""
    logits = jax.random.normal(jax.random.key(0), (3, 16))
    tokens, logprobs = sample_tokens_with_logprob(
        logits, None, SamplingConfig(temperature=0.0)
    )
    raw = jax.nn.log_softmax(logits, axis=-1)
    assert list(tokens) == list(jnp.argmax(logits, axis=-1))
    np.testing.assert_allclose(
        np.asarray(logprobs),
        np.asarray(raw)[np.arange(3), np.asarray(tokens)],
        rtol=1e-6,
    )


# ---------------------------------------------------------- GRPO math


def test_group_relative_advantages_standardize_within_group():
    rewards = jnp.asarray([1.0, 0.0, 1.0, 1.0, 5.0, 0.0])
    groups = jnp.asarray([0, 0, 0, 0, 1, 1])
    adv = np.asarray(group_relative_advantages(rewards, groups))
    g0 = np.asarray([1.0, 0.0, 1.0, 1.0])
    expected0 = (g0 - g0.mean()) / (g0.std() + 1e-6)
    np.testing.assert_allclose(adv[:4], expected0, rtol=1e-5)
    # group mean is removed exactly — a constant reward shift is invisible
    shifted = np.asarray(
        group_relative_advantages(rewards + 10.0, groups)
    )
    np.testing.assert_allclose(adv, shifted, rtol=1e-5)


def test_group_relative_advantages_degenerate_groups():
    # singleton group and zero-variance group: advantage ~0, never inf/nan
    adv = np.asarray(group_relative_advantages(
        jnp.asarray([3.0, 1.0, 1.0]), jnp.asarray([0, 1, 1])
    ))
    assert np.all(np.isfinite(adv))
    np.testing.assert_allclose(adv, 0.0, atol=1e-5)


# ------------------------------------------------------------- rewards


def test_reward_builtins(monkeypatch):
    copy_digit = resolve_reward("copy_digit")
    assert copy_digit([1, 2, 7], [7, 7, 3, 7]) == pytest.approx(0.75)
    assert copy_digit([1, 2, 7], []) == 0.0

    monkeypatch.setenv(reward_mod.TARGET_LEN_ENV, "4")
    length = resolve_reward("length")
    assert length([1], [5, 5, 5, 5]) == pytest.approx(1.0)
    assert length([1], [5, 5]) < 1.0

    monkeypatch.setenv(reward_mod.ANSWER_ENV, "42")
    numeric = resolve_reward("numeric_answer")
    # tokens render as space-separated decimal ids: "42" is token 42,
    # not the pair (4, 2)
    assert numeric([1], [3, 42, 5]) == pytest.approx(1.0)
    assert numeric([1], [4, 2]) == 0.0


def test_reward_env_selection(monkeypatch):
    monkeypatch.setenv(reward_mod.REWARD_ENV, "regex")
    monkeypatch.setenv(reward_mod.PATTERN_ENV, r"7 7")
    reward = resolve_reward(None)
    assert reward([0], [3, 7, 7, 1]) == pytest.approx(1.0)
    assert reward([0], [3, 1]) == 0.0
    monkeypatch.delenv(reward_mod.REWARD_ENV)
    # unset env -> copy_digit default (behavioral check: fraction of
    # completion tokens equal to the prompt's last token)
    assert resolve_reward(None)([1, 7], [7, 7, 3]) == pytest.approx(2 / 3)
    with pytest.raises(ValueError):
        resolve_reward("no_such_reward")


def test_rollout_id_roundtrip():
    assert parse_rollout_id(rollout_id(3, 1, 2)) == (3, 1, 2)
    assert parse_rollout_id("user:42") is None
    assert parse_rollout_id("rl:banana") is None


# ----------------------------------------- decode-logprob fidelity


def _teacher_forced_logprobs(model, variables, prompt, tokens):
    """One full forward over prompt+tokens; logprob of tokens[j] read at
    predictor position len(prompt)+j-1 of the raw log-softmax."""
    seq = list(prompt) + list(tokens)
    ids = jnp.asarray([seq], jnp.int32)
    out = jax.jit(model.apply)(variables, input_ids=ids)
    logps = jax.nn.log_softmax(out.logits[0].astype(jnp.float32), axis=-1)
    return [
        float(logps[len(prompt) + j - 1, token])
        for j, token in enumerate(tokens)
    ]


def _fidelity_model(name):
    if name == "gemma":
        return Gemma(GemmaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, max_position_embeddings=64,
            attention_impl="xla", compute_dtype="float32",
        ))
    extra = {
        "scan": dict(scan_layers=True),
        "looped": dict(scan_layers=False),
        "moe": dict(
            num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32
        ),
    }[name]
    return Llama(LlamaConfig(**TINY, **extra))


@pytest.mark.parametrize("name", ["scan", "looped", "moe", "gemma"])
def test_decode_logprobs_match_teacher_forced_forward(name):
    """The behavior logprobs the engine collects token-by-token through
    the paged cache must equal a teacher-forced full forward over the
    finished sequence at the same weights — the property that makes them
    usable as GRPO's importance-ratio denominator."""
    model = _fidelity_model(name)
    variables = _init(model)
    engine = _engine(
        model, variables,
        sampling=SamplingConfig(temperature=1.0), seed=11,
    )
    collector = RolloutCollector(engine, group_size=2, max_new_tokens=8)
    rollouts = collector.collect(0, [[3, 17, 42, 7], [5, 9]])
    assert len(rollouts) == 4
    assert collector.stats()["rl/rollouts_stale_dropped"] == 0
    for rollout in rollouts:
        assert len(rollout.logprobs) == len(rollout.tokens) == 8
        reference = _teacher_forced_logprobs(
            model, variables, rollout.prompt, rollout.tokens
        )
        np.testing.assert_allclose(
            rollout.logprobs, reference, rtol=1e-4, atol=1e-5,
            err_msg=f"{name}: incremental decode logprobs diverge from "
            "teacher-forced forward",
        )


# ------------------------------------------------ generation staleness


def test_stale_generation_rollouts_dropped():
    """A weight reload mid-collection makes every in-flight rollout span
    two generations — ALL of them must be dropped at harvest, none may
    reach a training batch."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    engine = _engine(model, variables)
    collector = RolloutCollector(engine, group_size=2, max_new_tokens=8)

    steps = [0]

    def reload_mid_collection():
        steps[0] += 1
        if steps[0] == 3:
            engine.reload_weights(variables)  # same values, new generation
        return False

    rollouts = collector.collect(
        0, [[3, 17, 42], [5, 9]], should_stop=reload_mid_collection
    )
    stats = collector.stats()
    assert stats["rl/rollouts_stale_dropped"] >= 1
    # whatever survived was decoded entirely under the new generation
    assert all(r.generation == engine.weights_generation for r in rollouts)
    assert (
        stats["rl/rollouts_collected"] + stats["rl/rollouts_stale_dropped"]
        == 4.0
    )


# ---------------------------------------- weight-sync stream equivalence


def test_weight_sync_stream_equivalence_fused_vs_host_vs_fresh():
    """The acceptance contract (docs/post-training.md#weight-sync):
    continuing a mid-flight greedy request after a fused sync produces
    tokens identical to (a) the same scenario under the host-oracle sync
    and (b) a FRESH engine built from the synced weights and fed
    prompt + tokens-so-far."""
    model = Llama(LlamaConfig(**TINY))
    w0, w1 = _init(model, seed=0), _init(model, seed=1)
    prompt = [3, 17, 42, 7]
    total = 10

    def run_with_sync(mode):
        engine = _engine(model, w0, max_batch=1)
        events = list(engine.submit(id="r", prompt=prompt, max_new_tokens=total))
        before = [e["token"] for e in events if e.get("type") == "token"]
        while len(before) < 4:  # some tokens decoded under w0
            before += [
                e["token"] for e in engine.step() if e.get("type") == "token"
            ]
        # the engine is a call ahead of what it has returned: one more token
        # was made under w0 and is what the sync would read first
        before += [e["token"] for e in engine.flush() if e.get("type") == "token"]
        summary = sync_weights(engine, w1, mode=mode)
        assert summary["generation"] == engine.weights_generation
        done = None
        while done is None:
            for event in engine.step():
                if event.get("type") == "done":
                    done = event
        return len(before), done["tokens"]

    k_fused, fused_tokens = run_with_sync("fused")
    k_host, host_tokens = run_with_sync("host")
    assert (k_fused, fused_tokens) == (k_host, host_tokens), (
        "fused on-device sync diverged from the host round-trip oracle"
    )
    # fresh engine restored from the synced weights, fed prompt + prefix
    fresh = _engine(model, w1, max_batch=1)
    events = list(fresh.submit(
        id="f", prompt=prompt + fused_tokens[:k_fused],
        max_new_tokens=total - k_fused,
    ))
    done = next((e for e in events if e.get("type") == "done"), None)
    while done is None:
        done = next(
            (e for e in fresh.step() if e.get("type") == "done"), None
        )
    assert fused_tokens[k_fused:] == done["tokens"], (
        "post-sync continuation diverged from a fresh engine on the "
        "synced weights"
    )


# --------------------------------------------------- SLO arbitration


def test_slo_breach_yields_rollout_submission():
    """The headline scenario: user traffic and rollouts share the engine;
    a burn-rate breach on serve TTFT (fed by user terminals) must open
    the collector's yield window — and every class still completes."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    engine = _engine(model, variables)
    monitor = SLOMonitor(
        specs_from_config({"serve": {"ttft_p99_ms": 10.0}}),
        registry=TelemetryRegistry(),
        min_events=1, cooldown_s=0.0, fast_burn=1.0, slow_burn=1.0,
    )
    user_done = []

    def on_foreign(event):
        if event.get("type") == "done":
            user_done.append(event["id"])
            # a user terminal far over the 10ms TTFT target
            monitor.observe_request(ttft_ms=100.0, ok=True)

    collector = RolloutCollector(
        engine, group_size=2, max_new_tokens=6,
        slo=monitor, yield_steps=2, on_foreign_event=on_foreign,
    )
    for i in range(2):
        collector.ingest(engine.submit(
            id=f"user:{i}", prompt=[9, 4, 6], max_new_tokens=2, priority=0
        ))
    # serve traffic alongside (the rl-fit loop's serve-first posture):
    # user terminals feed the monitor and breach the 10ms TTFT target
    for _ in range(50):
        if len(user_done) == 2:
            break
        collector.ingest(engine.step())
    assert len(user_done) == 2, "user traffic never completed"
    assert monitor.breach_count() >= 1, "TTFT breach never fired"
    # the NEXT rollout wave must open a yield window before submitting
    rollouts = collector.collect(
        0, [[3, 17], [5, 9], [1, 2], [7, 4], [8, 3], [2, 6]]
    )
    stats = collector.stats()
    assert stats["rl/rollout_yields"] >= 1, (
        "collector never yielded to the serve SLO breach"
    )
    assert len(rollouts) == 12, "yield window must defer, not drop, groups"


# ------------------------------------- frozen-modules restore structure


def test_frozen_modules_shardings_match_state_tree(tmp_path):
    """optax.masked puts empty MaskedNode slots in a frozen module's
    opt_state; the shardings tree must preserve them as empties (not
    invent leaves) or every GRPO/DPO restore dies on a pytree mismatch."""
    import flax.linen as nn

    from llm_training_tpu.cli.config import load_config
    from llm_training_tpu.cli.main import _build
    from llm_training_tpu.parallel.mesh import build_mesh
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    config = load_config(
        "config/examples/smoke/rl-smoke.yaml", [f"run_root={tmp_path}"]
    )
    trainer, objective, _ = _build(config)
    assert objective.config.frozen_modules, "GRPO must freeze its reference"
    trainer.mesh = build_mesh(trainer.config.mesh, trainer.devices)
    with trainer.mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        sample_batch = {"input_ids": np.zeros((1, 8), np.int32)}
        tx, _ = trainer._build_tx(objective)
        abstract_boxed = trainer._abstract_state(objective, sample_batch, tx)
        shardings = trainer._state_shardings(abstract_boxed)
        abstract = nn.meta.unbox(abstract_boxed)
    assert jax.tree.structure(abstract) == jax.tree.structure(shardings)
