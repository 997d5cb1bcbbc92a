"""The `Deepseek` stack SERVED (`deepseek_v2`, `deepseek_v3`, `pangu_ultra_moe`;
PR 41): chunked prefill and paged decode through the latent pool, and the
dense latent buffer, against the plain reference's full forward (and, for the
members the reference does not cover, against the module's own); a planted
fault; bfloat16 against the fp8 control; `fit`, `generate` and `serve` through
the CLI. The tiny model, its weights and the tolerances are
`tests/test_pangu_ultra_moe.py`'s (one file's tests run on one worker of the
tier: the two halves run side by side)."""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.infer import GenerateConfig, InferenceEngine
from llm_training_tpu.models.deepseek import Deepseek, DeepseekConfig, reference
from llm_training_tpu.serve import ServeConfig, ServingEngine
from llm_training_tpu.telemetry import get_registry
from tests.test_pangu_ultra_moe import (
    F32_TOL,
    FAR_LEVEL,
    FAR_SHARE,
    REFERENCE_CFG,
    TINY,
    seeded_variables,
    tiny,  # noqa: F401  (the module-scoped fixture)
)

# ------------------------------------------------------------------- serving

REQUESTS = [(19, 20), (5, 30), (11, 9), (30, 6), (3, 14)]  # (prompt, new tokens)
MOE_KINDS = ("held", "zero", "elsewhere")  # `serve/moe_<kind>_assignments`
SERVE = dict(max_batch=2, max_model_len=64, block_size=8, prefill_chunk=8, num_blocks=7, eos_token_id=None)


def serve_requests():
    rng = np.random.default_rng(5)
    return [
        {"id": f"r{i}", "prompt": rng.integers(0, 256, size=n).tolist(), "max_new_tokens": m}
        for i, (n, m) in enumerate(REQUESTS)
    ]


def served_against(full_logits, requests, done, control_logits=None):
    """For each request, over every served position: (the widest gap by which
    the served token's logit, in `full_logits(ids, seg) -> [S, V]`, lies below
    that forward's best; the widest difference between the served
    log-probability and that forward's: the logits up to the constant a
    softmax removes; with `control_logits`, every served position's gap for
    ITS first choice beside the served tokens' own: two arrays)."""
    gaps, logprob_gaps, control = [], [], []
    for r in requests:
        served = done[r["id"]]["tokens"]
        tokens = r["prompt"] + served
        ids, seg = np.zeros((1, 64), np.int32), np.zeros((1, 64), np.int32)
        ids[0, : len(tokens)] = tokens
        seg[0, : len(tokens)] = 1
        logits = np.asarray(full_logits(jnp.asarray(ids), jnp.asarray(seg)))
        at = np.arange(len(r["prompt"]) - 1, len(tokens) - 1)  # position p chooses token p + 1
        rows = logits[at]
        gaps += list(rows.max(-1) - rows[np.arange(len(at)), served])
        logprobs = np.asarray(jax.nn.log_softmax(rows))[np.arange(len(at)), served]
        logprob_gaps.append(float(np.abs(logprobs - np.asarray(done[r["id"]]["logprobs"])).max()))
        if control_logits is not None:
            low = np.asarray(control_logits(jnp.asarray(ids), jnp.asarray(seg)))[at].argmax(-1)
            control += list(rows.max(-1) - rows[np.arange(len(at)), low])
    both = (np.asarray(gaps), np.asarray(control)) if control_logits is not None else None
    return float(max(gaps)), max(logprob_gaps), both


def reference_forward(variables, cfg=REFERENCE_CFG):
    return lambda ids, seg: reference.logits(variables["params"], cfg, ids, seg)[0]


def run_engine(model, variables, **serve):
    engine = ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))
    requests = serve_requests()
    events = []
    # two at once, the others join mid-flight into recycled blocks
    for r in requests[:2]:
        events += engine.submit(**r)
    for _ in range(6):
        events += engine.step()
    for r in requests[2:]:
        events += engine.submit(**r)
    while not engine.idle:
        events += engine.step()
    done = {e["id"]: e for e in events if e["type"] == "done"}
    return engine, requests, done


@pytest.mark.parametrize("variant", ["dense_experts", "grouped_experts_in_place", "latent_kernels"])
def test_chunked_prefill_then_paged_decode_is_the_reference_forward(tiny, variant, monkeypatch):
    """Prompts of 19, 5, 11, 30 and 3 tokens in chunks of 8 (chunks of unequal
    length, the last one padded), five requests through two slots (the later
    ones join mid-flight into recycled blocks, whose stale latents lie past
    their lengths), a pool of 7 blocks (so one request is evicted mid-decode
    and re-prefilled with its progress folded in): every served position
    against the reference's full forward, so a stale latent page, a block of
    the looped prefix and the scanned suffix mixed up, or a wrong rotary
    position fails. Also with the held experts multiplied in place by the
    grouped product (`moe_impl='ragged'`, the chip's path), and with the
    chip's latent kernels interpreted: a chunk in `mla_prefill`, a token a
    row in `mla_decode`, the append in `latent_page_write`."""
    _, variables = tiny
    over = {
        "dense_experts": {}, "grouped_experts_in_place": {"moe_impl": "ragged"}, "latent_kernels": {},
    }[variant]
    if variant == "latent_kernels":
        from llm_training_tpu.ops import latent_attention

        attend = latent_attention.paged_latent_attention
        monkeypatch.setattr(
            latent_attention, "paged_latent_attention",
            lambda *args, **kwargs: attend(*args, **{**kwargs, "impl": "pallas"}),
        )
    model = Deepseek(DeepseekConfig(**{**TINY, **over}))
    # the counters are the process's: whatever served on this worker before is in them
    before = [get_registry().counter(f"serve/moe_{k}_assignments").value for k in MOE_KINDS]
    with jax.default_matmul_precision("highest"):
        engine, requests, done = run_engine(model, variables)
    assert all(done[r["id"]]["stop_reason"] == "max_tokens" for r in requests)
    assert engine.scheduler.evictions >= 1 and engine.allocator.blocks_in_use == 0
    gap, logprob_gap, _ = served_against(reference_forward(variables), requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    stats = engine.stats()
    assert stats["decode/latent_pool_bytes"] == stats["decode/cache_bytes"] == 3 * 8 * 8 * 128 * 4
    # a share counts where its rows' choices went: 4 a token in each of the two MoE layers
    held, zero, elsewhere = (stats[f"serve/moe_{k}_assignments"] - b for k, b in zip(MOE_KINDS, before))
    assert zero == 0 and held > 0 and elsewhere > 0 and (held + elsewhere) % 8 == 0
    if variant == "grouped_experts_in_place":
        assert stats["decode/experts_in_place_layers"] == 2  # the scanned suffix's two
    # every MLA block's chunk attention in the kernel, or none
    assert stats["decode/chunk_attention_kernel_layers"] == (3 if variant == "latent_kernels" else 0)


@pytest.mark.parametrize("family", ["deepseek_v3_groups", "deepseek_v2_full_rank_q"])
def test_deepseek_v2_and_v3_decode_as_their_own_full_forward(family):
    """The stack's other members gain the cache with this one: V3 with its
    expert groups and a dense prefix, V2-Lite's shape (softmax router,
    full-rank `q_proj`, rotary in halves, the MoE suffix looped). The plain
    reference does not cover groups or version 2 (`tests/test_deepseek.py`
    holds those to HuggingFace), so the served positions are held to the
    module's OWN full forward without a cache."""
    base = {k: v for k, v in TINY.items() if k not in ("experts_held", "experts_first", "sandwich_norm")}
    over = {
        "deepseek_v3_groups": dict(n_group=4, topk_group=2, n_shared_experts=2),
        "deepseek_v2_full_rank_q": dict(
            version=2, q_lora_rank=None, topk_method="group_limited_greedy", n_group=4, topk_group=2,
            rope_interleave=False, scan_layers=False, routed_scaling_factor=1.0),
    }[family]
    model = Deepseek(DeepseekConfig(**{**base, **over}))
    variables = seeded_variables(model)
    with jax.default_matmul_precision("highest"):
        engine, requests, done = run_engine(model, variables)
        forward = jax.jit(lambda ids, seg: model.apply(variables, input_ids=ids, segment_ids=seg).logits[0])
        gap, logprob_gap, _ = served_against(forward, requests, done)
    assert all(done[r["id"]]["stop_reason"] == "max_tokens" for r in requests)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    assert "serve/moe_held_assignments" not in engine.stats()  # all the experts are here: nothing to count


def test_a_wrong_rotary_position_is_caught(tiny):
    """The planted fault: decode steps told a position one too early."""
    model, variables = tiny
    engine = ServingEngine(model, variables, ServeConfig(**SERVE))
    apply = model.apply

    def off_by_one(variables, input_ids, position_ids, **kw):
        if input_ids.shape[1] == 1:
            position_ids = position_ids - 1
        return apply(variables, input_ids=input_ids, position_ids=position_ids, **kw)

    object.__setattr__(model, "apply", off_by_one)
    try:
        engine._build_programs()
        requests = serve_requests()[:2]
        events = []
        for r in requests:
            events += engine.submit(**r)
        with jax.default_matmul_precision("highest"):
            while not engine.idle:
                events += engine.step()
    finally:
        object.__delattr__(model, "apply")
    done = {e["id"]: e for e in events if e["type"] == "done"}
    gap, logprob_gap, _ = served_against(reference_forward(variables), requests, done)
    assert max(gap, logprob_gap) > 100 * F32_TOL


def test_generate_through_the_dense_latent_buffer_serves_the_same_tokens(tiny):
    model, variables = tiny
    requests = serve_requests()[:3]
    with jax.default_matmul_precision("highest"):
        _, _, done = run_engine(model, variables, num_blocks=None)
        out = InferenceEngine(model, variables).generate(
            [r["prompt"] for r in requests], GenerateConfig(max_new_tokens=9)
        )
    gap, logprob_gap, _ = served_against(reference_forward(variables), requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    for row, r in enumerate(requests):  # left-padded rows of 19, 5 and 11 tokens
        assert out["tokens"][row] == done[r["id"]]["tokens"][:9]
        assert np.allclose(out["logprobs"][row], done[r["id"]]["logprobs"][:9], atol=F32_TOL)


def test_bfloat16_serving_passes_and_the_fp8_control_does_not():
    from benchmarks.references import _common, pangu_ultra_moe as copy

    model = Deepseek(DeepseekConfig(**{**TINY, "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}))
    variables = seeded_variables(model, scale=0.1)
    engine, requests, done = run_engine(model, variables)
    control = lambda ids, seg: copy.logits(
        variables["params"], REFERENCE_CFG, ids, seg, None, _common.QUANTS["fp8"])[0]
    _, _, (sound, low) = served_against(reference_forward(variables), requests, done, control)
    share = lambda gaps: float((gaps > FAR_LEVEL).mean())
    assert len(sound) == 79 and share(sound) <= FAR_SHARE < share(low), (share(sound), share(low))


# ------------------------------------------------------ the normal entry points


@pytest.mark.parametrize("family", ["pangu_ultra_moe", "deepseek_v3"])
def test_fit_then_generate_and_serve_through_the_cli(family, tmp_path, capsys, monkeypatch):
    """`fit` (the CLM objective, the MTP loss where the model has the module),
    then `generate` and `serve` from the checkpoint, through `cli.main`."""
    import yaml

    from llm_training_tpu.cli.main import main

    kwargs = {k: v for k, v in TINY.items() if k not in ("experts_held", "experts_first")}
    kwargs.update(vocab_size=128, num_hidden_layers=2)
    if family == "pangu_ultra_moe":
        kwargs.update(num_nextn_predict_layers=1)
    else:
        kwargs.update(sandwich_norm=False, n_group=4, topk_group=2)
    config = {
        "seed_everything": 7,
        "run_root": str(tmp_path),
        "trainer": {
            "max_steps": 3, "log_every_n_steps": 1,
            "checkpoint": {"dirpath": str(tmp_path / "ckpt"), "async_save": False},
            "loggers": [{"class_path": "llm_training_tpu.callbacks.JsonlLogger",
                         "init_args": {"save_dir": str(tmp_path), "project": "p", "name": family}}],
        },
        "model": {"class_path": "llm_training_tpu.lms.CLM", "init_args": {
            "model": {"model_class": "Deepseek", "model_kwargs": kwargs},
            "optim": {"learning_rate": 1e-3}}},
        "data": {"class_path": "llm_training_tpu.data.DummyDataModule", "init_args": {
            "batch_size": 8, "max_length": 32, "num_samples": 32, "vocab_size": 128}},
    }
    path = tmp_path / "config.yaml"
    path.write_text(yaml.safe_dump(config))
    assert main(["fit", "--config", str(path)]) == 0
    logged = [json.loads(line) for line in next(tmp_path.glob("p/*/metrics.jsonl")).read_text().splitlines()]
    losses = [row for row in logged if "loss" in row]
    assert losses and np.isfinite(losses[-1]["loss"])
    assert ("mtp_loss" in losses[-1]) is (family == "pangu_ultra_moe")
    capsys.readouterr()
    assert main(["generate", "--config", str(path), "--prompt-tokens", "3,17,42", "--max-new-tokens", "6"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert any(len(line.get("tokens", ())) == 6 for line in lines)
    import io

    asked = [{"id": f"q{i}", "prompt": [5 + i, 9, 77, 3][: 2 + i], "max_new_tokens": 5} for i in range(3)]
    monkeypatch.setattr(sys, "stdin", io.StringIO("".join(json.dumps(r) + "\n" for r in asked)))
    assert main(["serve", "--config", str(path), "--max-batch", "2", "--max-model-len", "32",
                 "--prefill-chunk", "4", "--eos-token-id", "-1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    done = {line["id"]: line for line in lines if line.get("type") == "done"}
    assert set(done) == {"q0", "q1", "q2"} and all(len(d["tokens"]) == 5 for d in done.values())
