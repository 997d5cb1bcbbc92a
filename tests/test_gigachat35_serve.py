"""GigaChat 3.5 SERVED (PR 49): the first stack whose engine holds a latent
page pool AND a recurrent slab. Chunked prefill and paged decode through both
against the plain reference's full forward (logits, not tokens), a request
evicted and re-admitted, a stack with a looped prefix, two scanned periods and
a looped tail, the delta-rule kernel interpreted, the dense `infer/` cache,
planted faults, bfloat16 against the fp8 control, `fit`, `generate` and
`serve` through the CLI. The tiny model, its weights and the tolerances are
`tests/test_gigachat35.py`'s (one file's tests run on one worker of the tier:
the two halves run side by side)."""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.infer import GenerateConfig, InferenceEngine
from llm_training_tpu.models.gigachat35 import GigaChat35, GigaChat35Config, reference
from llm_training_tpu.serve import ServeConfig, ServingEngine
from llm_training_tpu.telemetry import get_registry
from tests.test_gigachat35 import (
    F32_TOL,
    REFERENCE_CFG,
    TINY,
    seeded_variables,
    tiny,  # noqa: F401  (the module-scoped fixture)
)

REQUESTS = [(19, 20), (5, 30), (11, 9), (30, 6), (3, 14)]  # (prompt, new tokens)
MOE_KINDS = ("held", "zero", "elsewhere")  # `serve/moe_<kind>_assignments`
SERVE = dict(max_batch=2, max_model_len=64, block_size=8, prefill_chunk=8, num_blocks=7, eos_token_id=None)
# Read here over 4 draws of the weights, of 79 served tokens: over 0.1 below the reference's best, bfloat16 4, 15, 9
# and 5 of them (0.05 to 0.19), the fp8 control 49, 49, 44 and 50 (0.56 to 0.63); the limit stands between. The
# WIDEST gap separates less well (bfloat16 0.22 to 0.74, fp8 1.49 to 2.45): a router's near-tie that falls the other
# way moves a normalised quarter x 2.5 of the routed sum in or out of the share, one token's accident
FAR_LEVEL, FAR_SHARE = 0.1, 0.35


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
    log-probability and that forward's; with `control_logits`, every served
    position's gap for ITS first choice beside the served tokens' own)."""
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


def run_engine(model, variables, requests=None, **serve):
    engine = ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))
    requests = requests or serve_requests()
    events = []
    # two at once, the others join mid-flight into recycled blocks and slots
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


STACKS = {
    # the five-layer cut's shape: layer 0 (delta rule + dense) looped, ONE period [MLA, delta rule x 3] scanned
    "one_period": ({}, 1, 4),
    "one_period_grouped_experts_in_place": ({"moe_impl": "ragged"}, 1, 4),
    # two dense layers looped (a delta-rule one and an MLA one), two periods [delta rule, MLA, delta rule] scanned,
    # a delta-rule layer looped at the end: every layer addresses its own kind by its own index
    "prefix_two_periods_and_a_tail": (
        {"num_hidden_layers": 9, "first_k_dense_replace": 2, "full_attention_layers": [1, 3, 6]}, 3, 6),
}


@pytest.mark.parametrize("stack", sorted(STACKS))
def test_chunked_prefill_then_paged_decode_is_the_reference_forward(stack):
    """Prompts of 19, 5, 11, 30 and 3 tokens in chunks of 8 (chunks of unequal
    length, the last one padded), five requests through two slots (the later
    ones join mid-flight into recycled blocks, whose stale latents lie past
    their lengths, and recycled slots, which must read zeros), a pool of 7
    blocks (so one request is evicted mid-decode and re-prefilled with its
    progress folded in, its slab rows made anew): every served position
    against the reference's full forward, so a stale latent page, a state not
    zeroed or not written back, an MLA layer or a slab layer mixed up with
    another, or a wrong rotary position fails."""
    over, latent_layers, slab_layers = STACKS[stack]
    model = GigaChat35(GigaChat35Config(**{**TINY, **over}))
    variables = seeded_variables(model)
    cfg = {**REFERENCE_CFG, "num_hidden_layers": model.config.num_hidden_layers}
    # the counters are the process's: whatever served on this worker before is in them
    before = [get_registry().counter(f"serve/moe_{k}_assignments").value for k in MOE_KINDS]
    with jax.default_matmul_precision("highest"):
        engine, requests, done = run_engine(model, variables)
    assert all(done[r["id"]]["stop_reason"] == "max_tokens" for r in requests)
    assert engine.scheduler.evictions >= 1 and engine.allocator.blocks_in_use == 0
    gap, logprob_gap, _ = served_against(reference_forward(variables, cfg), requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    stats = engine.stats()
    # ONE pool of latent rows (640 B a token a layer at float32 x 128) beside the slab
    assert stats["decode/latent_pool_bytes"] == stats["decode/cache_bytes"] == latent_layers * 8 * 8 * 128 * 4
    state = slab_layers * 2 * (4 * 8 * 16 * 4 + 3 * 96 * 4)
    assert stats["decode/state_bytes"] == stats["decode/state_logical_bytes"] == state
    assert stats["decode/delta_step_calls/xla"] == slab_layers and stats["decode/delta_step_calls/kernel"] == 0
    # a share counts where its rows' choices went: 4 a token in each layer with experts
    held, zero, elsewhere = (stats[f"serve/moe_{k}_assignments"] - b for k, b in zip(MOE_KINDS, before))
    assert zero == 0 and held > 0 and elsewhere > 0
    if stack == "one_period_grouped_experts_in_place":
        assert stats["decode/experts_in_place_layers"] == 4  # the scanned period's four


def test_the_delta_step_kernel_advances_the_slab_beside_the_latent_pool(monkeypatch):
    """The chip's path for one token a row, interpreted: heads of 128 x 128
    (whole 8 x 128 tiles), so `delta_step` takes the slab where it lies, in a
    stack whose other cache is the latent pool."""
    from llm_training_tpu.ops import delta_rule

    monkeypatch.setattr(delta_rule, "_on_kernels", lambda impl: True)
    over = {"linear_key_head_dim": 128, "linear_value_head_dim": 128, "linear_num_value_heads": 2,
            "linear_num_key_heads": 1, "num_hidden_layers": 3}
    model = GigaChat35(GigaChat35Config(**{**TINY, **over}))
    variables = seeded_variables(model)
    requests = serve_requests()[:3]
    with jax.default_matmul_precision("highest"):
        engine, requests, done = run_engine(model, variables, requests, num_blocks=None)
    stats = engine.stats()
    assert stats["decode/delta_step_calls/kernel"] == 2 and stats["decode/delta_step_calls/xla"] == 0
    cfg = {**REFERENCE_CFG, "num_hidden_layers": 3, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
           "linear_num_value_heads": 2, "linear_num_key_heads": 1}
    gap, logprob_gap, _ = served_against(reference_forward(variables, cfg), requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL


def _state_never_written(monkeypatch):
    from llm_training_tpu.models import cache as cache_module

    monkeypatch.setattr(
        cache_module.LayerCache, "put_recurrent_rows", lambda self, layer, rows, in_place=False: self
    )


def _latent_rows_not_appended(monkeypatch):
    from llm_training_tpu.models import cache as cache_module

    proper = cache_module.LayerCache.attend_latent
    monkeypatch.setattr(
        cache_module.LayerCache, "attend_latent",
        lambda self, *args, **kwargs: (proper(self, *args, **kwargs)[0], self),
    )


def _fresh_slot_not_zeroed(monkeypatch):
    """A recycled slot hands the next request the last one's state."""
    from llm_training_tpu.models.gigachat35 import model as module

    monkeypatch.setattr(module, "_slot_rows", lambda slab, slots, fresh: slab if slots is None else slab[slots])


@pytest.mark.parametrize("plant", [_state_never_written, _latent_rows_not_appended, _fresh_slot_not_zeroed])
def test_a_planted_fault_is_caught(tiny, monkeypatch, plant):
    model, variables = tiny
    plant(monkeypatch)
    with jax.default_matmul_precision("highest"):
        _, requests, done = run_engine(model, variables)
    gap, logprob_gap, _ = served_against(reference_forward(variables), requests, done)
    assert max(gap, logprob_gap) > 100 * F32_TOL


def test_both_programs_name_the_scopes_the_benchmarks_readers_match(tiny):
    """What `decode_mla_device_ms`, `decode_gdn_conv_device_ms`, `gdn_decode_roofline_pct`,
    `prefill_gdn_chunk_device_ms`, `moe_dispatch_device_ms`, `decode_moe_shared_device_ms` and
    `decode_norm_device_ms` search for: an MLA layer's parts and its `attn_gate` inside `/self_attn/`,
    a delta-rule layer's inside `/linear_attn/` (the state's write under the recurrence's or the
    chunked rule's scope), the experts' under `/mlp/`, every norm under `rms_norm`."""
    import re

    model, variables = tiny
    engine = ServingEngine(model, variables, ServeConfig(**SERVE))
    args = lambda packed: (variables, jnp.asarray(packed), engine._pool_k, engine._pool_v, engine._rng,
                           engine._last_tokens)
    programs = {
        "decode": engine._decode_jit.lower(*args(engine._decode_packed), slab=engine._slab),
        "prefill": engine._prefill_jit.lower(*args(engine._prefill_packed), slab=engine._slab),
    }
    shared = (
        "slot0/self_attn/mla_q", "slot0/self_attn/mla_kv", "slot0/self_attn/mla_attend", "slot0/self_attn/attn_gate",
        "slot0/self_attn/mla_out", "slot1/linear_attn/gdn_conv", "slot2/linear_attn/gdn_gates",
        "slot3/linear_attn/gdn_out", "layers_0/linear_attn/gdn_conv", "layers_0/mlp/", "slot1/mlp/moe_route",
        "slot1/mlp/moe_shared", "slot0/mlp/moe_route", "input_layernorm/rms_norm", "post_mlp_layernorm/rms_norm",
        "o_norm/rms_norm", "/sample",
    )
    own = {"decode": ("linear_attn/gdn_recurrence", "self_attn/mla_absorb"), "prefill": ("linear_attn/gdn_chunk",)}
    other = {"decode": ("gdn_chunk", "mla_expand"), "prefill": ("gdn_recurrence", "mla_absorb")}
    for name, lowered in programs.items():
        assert f"jit_{'decode_step' if name == 'decode' else 'prefill_chunk'}" in lowered.as_text()[:200]
        text = lowered.as_text(debug_info=True)
        for scope in shared + own[name]:
            assert scope in text, (name, scope)
        for scope in other[name]:
            assert scope not in text, (name, scope)
        # OP names only (a frame's file or function is no op: `tests/test_serve_spans.py:_op_names`)
        named = [n for n in re.findall(r'loc\("([^"]*gdn_[^"]*)"\(', text) if "/" in n]
        assert named and all("linear_attn/" in n for n in named if not n.startswith("while/body/"))
    engine.close()


def test_generate_through_the_dense_caches_serves_the_same_tokens(tiny):
    """The dense `infer/` cache: ONE latent buffer and the slab, a batch of
    left-padded rows; and the paged engine without an eviction."""
    model, variables = tiny
    requests = serve_requests()[:3]
    with jax.default_matmul_precision("highest"):
        _, _, done = run_engine(model, variables, requests, num_blocks=None)
        out = InferenceEngine(model, variables).generate(
            [r["prompt"] for r in requests], GenerateConfig(max_new_tokens=9)
        )
    gap, logprob_gap, _ = served_against(reference_forward(variables), requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    for row, r in enumerate(requests):  # left-padded rows of 19, 5 and 11 tokens
        assert out["tokens"][row] == done[r["id"]]["tokens"][:9]
        assert np.allclose(out["logprobs"][row], done[r["id"]]["logprobs"][:9], atol=F32_TOL)


def test_bfloat16_serving_passes_and_the_fp8_control_does_not():
    from benchmarks.references import _common, gigachat3_5 as copy

    model = GigaChat35(GigaChat35Config(**{**TINY, "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}))
    variables = seeded_variables(model, scale=0.1)
    engine, requests, done = run_engine(model, variables)
    control = lambda ids, seg: copy.logits(
        variables["params"], REFERENCE_CFG, ids, seg, None, _common.QUANTS["fp8"])[0]
    _, _, (sound, low) = served_against(reference_forward(variables), requests, done, control)
    share = lambda gaps: float((gaps > FAR_LEVEL).mean())
    assert len(sound) == 79 and share(sound) <= FAR_SHARE < share(low), (share(sound), share(low))


def test_fit_then_generate_and_serve_through_the_cli(tmp_path, capsys, monkeypatch):
    """`fit` (the CLM objective with the two multi-token-prediction modules'
    loss), then `generate` and `serve` from the checkpoint, through `cli.main`."""
    import io

    import yaml

    from llm_training_tpu.cli.main import main

    kwargs = {k: v for k, v in TINY.items() if k not in ("experts_held", "experts_first")}
    kwargs.update(vocab_size=128, num_nextn_predict_layers=2)
    config = {
        "seed_everything": 7,
        "run_root": str(tmp_path),
        "trainer": {
            "max_steps": 3, "log_every_n_steps": 1,
            "checkpoint": {"dirpath": str(tmp_path / "ckpt"), "async_save": False},
            "loggers": [{"class_path": "llm_training_tpu.callbacks.JsonlLogger",
                         "init_args": {"save_dir": str(tmp_path), "project": "p", "name": "gigachat35"}}],
        },
        "model": {"class_path": "llm_training_tpu.lms.CLM", "init_args": {
            "model": {"model_class": "GigaChat35", "model_kwargs": kwargs},
            "optim": {"learning_rate": 1e-3}}},
        "data": {"class_path": "llm_training_tpu.data.DummyDataModule", "init_args": {
            "batch_size": 8, "max_length": 32, "num_samples": 32, "vocab_size": 128}},
    }
    path = tmp_path / "config.yaml"
    path.write_text(yaml.safe_dump(config))
    assert main(["fit", "--config", str(path)]) == 0
    logged = [json.loads(line) for line in next(tmp_path.glob("p/*/metrics.jsonl")).read_text().splitlines()]
    losses = [row for row in logged if "loss" in row]
    assert losses and np.isfinite(losses[-1]["loss"]) and np.isfinite(losses[-1]["mtp_loss"])
    capsys.readouterr()
    assert main(["generate", "--config", str(path), "--prompt-tokens", "3,17,42", "--max-new-tokens", "6"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    assert any(len(line.get("tokens", ())) == 6 for line in lines)
    asked = [{"id": f"q{i}", "prompt": [5 + i, 9, 77, 3][: 2 + i], "max_new_tokens": 5} for i in range(3)]
    monkeypatch.setattr(sys, "stdin", io.StringIO("".join(json.dumps(r) + "\n" for r in asked)))
    assert main(["serve", "--config", str(path), "--max-batch", "2", "--max-model-len", "32",
                 "--prefill-chunk", "4", "--eos-token-id", "-1"]) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines() if line.startswith("{")]
    done = {line["id"]: line for line in lines if line.get("type") == "done"}
    assert set(done) == {"q0", "q1", "q2"} and all(len(d["tokens"]) == 5 for d in done.values())
