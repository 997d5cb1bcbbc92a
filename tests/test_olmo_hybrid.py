"""Olmo-Hybrid (`models/olmo_hybrid`) and the shared gated delta rule
(`ops/delta_rule.py`): the rule's two forms against the equation, the module
against its plain reference, serving through the paged pool AND the state
slab (stored two heads abreast) against the reference's full forward, and the
two copies of the reference against each other. Float32 on the CPU unless a
test says otherwise. Small widths on purpose: `key_dim != value_dim`, and a
head count (6) that 8 does not divide.

Tolerances, with their reasons:
- float32 against float32 (`highest` products on both sides): 2e-3 on logits
  of magnitude 1 to 7. The two sides sum in different orders (chunks
  against one token at a time, paged gathers against [S, S] scores), and
  this stack AMPLIFIES rounding: every sub-block's output goes through an
  RMSNorm, which makes a small output (the delta rule's over a request's
  first tokens) unit-sized, its rounding with it. Read here: the reference
  in float32 lies 1.9e-3 from ITSELF in float64 and the module 3.5e-3, while
  module and reference in float32 lie 6e-4 apart; a planted fault reads
  0.3 and more.
- the rule against a float64 numpy oracle: 2e-5 of the output's scale.
- bfloat16 compute against the float32 reference: the served token's
  reference logit may lie at most `BF16_GAP` below the reference's best, and
  the fp8 control must lie further off.
"""

import json
import sys
import zlib
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.infer import GenerateConfig, InferenceEngine
from llm_training_tpu.infer.engine import supports_decoding
from llm_training_tpu.models.olmo_hybrid import OlmoHybrid, OlmoHybridConfig, reference
from llm_training_tpu.ops.delta_rule import (
    gated_delta_chunked,
    gated_delta_step,
    pack_heads,
    unpack_heads,
)
from llm_training_tpu.serve import ServeConfig, ServingEngine

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

F32_TOL = 2e-3
BF16_GAP = 1.0  # read here over 6 draws of the weights: bfloat16 0.26 to 0.55, the fp8 control 1.87 to 2.72

TINY = dict(
    vocab_size=256, hidden_size=60, intermediate_size=96, num_hidden_layers=8,
    num_attention_heads=6, num_key_value_heads=6,
    linear_num_key_heads=6, linear_num_value_heads=6,
    linear_key_head_dim=12, linear_value_head_dim=64, delta_chunk_size=16,
    param_dtype="float32", compute_dtype="float32", attention_impl="xla",
)
# the same model as the reference's mapping (the published keys)
REFERENCE_CFG = {
    **{k: v for k, v in TINY.items() if k.startswith(("linear_", "num_", "hidden_"))},
    "rms_norm_eps": 1e-6, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"] + ["linear_attention"] * 3 + ["full_attention"],
}


def seeded_variables(model, dtype_scale=0.2, seed=1):
    """Random weights that exercise every term: decays spread from slow to fast."""
    variables = nn.meta.unbox(
        jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)))(jax.random.key(0))
    )

    def draw(path, leaf):
        name = path[-1].key
        # (crc32, not hash(): a str's hash differs from one process to the next)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(jax.tree_util.keystr(path).encode()))
        if name in ("A_log", "dt_bias"):
            return (jax.random.normal(key, leaf.shape) * 0.7).astype(leaf.dtype)
        if name == "weight":
            return leaf
        return (jax.random.normal(key, leaf.shape) * dtype_scale).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(draw, variables)


@pytest.fixture(scope="module")
def tiny():
    model = OlmoHybrid(OlmoHybridConfig(**TINY))
    return model, seeded_variables(model)


# ------------------------------------------------------------------ the rule


def oracle(q, k, v, g, beta, state, starts=None):
    """S_t = alpha S_{t-1} + beta k (v - (alpha S_{t-1})^T k)^T, o_t = S_t^T q_t,
    with explicit matrices in float64."""
    q, k, v, g, beta, state = (np.asarray(a, np.float64) for a in (q, k, v, g, beta, state))
    batch, seq, heads, _ = q.shape
    out = np.zeros(v.shape)
    state = state.copy()
    for b in range(batch):
        for h in range(heads):
            s = state[b, h]
            for t in range(seq):
                if starts is not None and starts[b, t]:
                    s = np.zeros_like(s)
                s = np.exp(g[b, t, h]) * s
                kt = k[b, t, h][:, None]
                s = s + beta[b, t, h] * kt @ (v[b, t, h][None, :] - kt.T @ s)
                out[b, t, h] = s.T @ q[b, t, h]
            state[b, h] = s
    return out, state


def rule_inputs(decay, seq=150, carried=False, seed=0):
    rng = np.random.default_rng(seed)
    batch, heads, dk, dv = 2, 6, 12, 64  # a rectangular state, two heads abreast
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(batch, seq, heads, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(batch, seq, heads, dk)))
    v = rng.normal(size=(batch, seq, heads, dv))
    # ONE decay a head: "slow" remembers thousands of tokens, "strong" loses
    # e^-5 a step (e^(-G) passes float32's range within 18 tokens)
    centre = {"slow": -1e-3, "strong": -5.0, "mixed": -1.0}[decay]
    g = centre * rng.uniform(0.5, 1.5, size=(batch, seq, heads))
    beta = rng.uniform(0.0, 2.0, size=(batch, seq, heads))
    beta[:, ::7] = 2.0  # the negative-eigenvalue end, exactly
    state = rng.normal(size=(batch, heads, dk, dv)) if carried else np.zeros((batch, heads, dk, dv))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, state))


def stepped(q, k, v, g, beta, state, abreast):
    """`gated_delta_step` a token at a time on the STORED state."""
    def one(s, xs):
        return gated_delta_step(s, *xs)

    s, out = jax.lax.scan(
        one, pack_heads(state, abreast), tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    )
    return jnp.moveaxis(out, 0, 1), unpack_heads(s, abreast)


@pytest.mark.parametrize("abreast", [1, 2, 3], ids=["one_head_a_row", "two_abreast", "three_abreast"])
@pytest.mark.parametrize("carried", [False, True], ids=["zero_state", "carried_state"])
@pytest.mark.parametrize("decay", ["slow", "strong", "mixed"])
def test_step_and_chunked_rule_are_the_equation_token_by_token(decay, carried, abreast):
    q, k, v, g, beta, state = rule_inputs(decay, carried=carried)
    assert float(beta.max()) == 2.0
    want_out, want_state = oracle(q, k, v, g, beta, state)
    got_out, got_state = jax.jit(gated_delta_chunked)(q, k, v, g, beta, state)
    step_out, step_state = jax.jit(stepped, static_argnums=6)(q, k, v, g, beta, state, abreast)
    # and the reference's own token scan
    ref_out, ref_state = jax.jit(reference.delta_rule)(
        q, k, v, jnp.exp(g), beta, jnp.zeros(q.shape[:2], bool), state
    )
    scale = max(1.0, float(np.abs(want_out).max()))
    for got in (got_out, step_out, ref_out):
        assert np.isfinite(np.asarray(got)).all()
        assert np.abs(np.asarray(got) - want_out).max() < 2e-5 * scale
    for got in (got_state, step_state, ref_state):
        assert np.abs(np.asarray(got) - want_state).max() < 2e-5 * max(1.0, float(np.abs(want_state).max()))


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_rule_restarts_at_a_document_start_and_skips_padding(chunk):
    q, k, v, g, beta, state = rule_inputs("mixed", seq=140, carried=True, seed=3)
    starts = np.zeros((2, 140), bool)
    starts[0, [9, 64, 133]] = True  # mid-chunk, on a chunk's first position, late
    starts[1, 21] = True
    # padding changes nothing: beta 0, g 0
    pad = np.zeros((2, 140), bool)
    pad[:, 136:] = True
    g = jnp.where(pad[..., None], 0.0, g)
    beta = jnp.where(pad[..., None], 0.0, beta)
    want_out, want_state = oracle(q, k, v, g, beta, state, starts)
    got_out, got_state = jax.jit(gated_delta_chunked, static_argnums=7)(
        q, k, v, g, beta, state, jnp.asarray(starts), chunk
    )
    assert np.abs(np.asarray(got_out) - want_out)[~pad].max() < 2e-5 * np.abs(want_out).max()
    assert np.abs(np.asarray(got_state) - want_state).max() < 2e-5 * np.abs(want_state).max()
    # the state after the last real token IS the state after the padding
    _, before_pad = oracle(q[:, :136], k[:, :136], v[:, :136], g[:, :136], beta[:, :136], state, starts[:, :136])
    assert np.abs(np.asarray(got_state) - before_pad).max() < 2e-5 * np.abs(before_pad).max()
    # and a step with beta 0, g 0 (an idle slot) leaves the stored state as it was, exactly
    stored = pack_heads(state, 2)
    same, _ = gated_delta_step(stored, q[:, 0], k[:, 0], v[:, 0], jnp.zeros_like(g[:, 0]), jnp.zeros_like(beta[:, 0]))
    assert np.array_equal(np.asarray(same), np.asarray(stored))


@pytest.mark.parametrize("heads,value_dim,want", [
    (30, 192, 2), (64, 128, 1), (6, 64, 2), (7, 64, 1), (9, 160, 1), (8, 32, 4),
])
def test_the_stored_state_fills_whole_tiles(heads, value_dim, want):
    from llm_training_tpu.models.base import RecurrentCacheSpec

    assert RecurrentCacheSpec(1, heads, 3, value_dim, 3, 1).abreast == want
    state = jnp.arange(2 * heads * 3 * value_dim, dtype=jnp.float32).reshape(2, heads, 3, value_dim)
    stored = pack_heads(state, want)
    assert stored.shape == (2, heads // want, 3, want * value_dim)
    assert want == 1 or stored.shape[-1] % 128 == 0
    # head n p + j sits on lanes j dv .. (j + 1) dv of row p
    assert np.array_equal(np.asarray(stored[:, 0, :, :value_dim]), np.asarray(state[:, 0]))
    assert np.array_equal(np.asarray(stored[:, -1, :, -value_dim:]), np.asarray(state[:, -1]))
    assert np.array_equal(np.asarray(unpack_heads(stored, want)), np.asarray(state))


# --------------------------------------------------------- module, reference


def packed_batch(rows=2, vocab=256):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(rows, 48)).astype(np.int32)
    seg = np.concatenate([np.full(20, 1), np.full(24, 2), np.zeros(4)]).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(np.tile(seg, (rows, 1)))


def test_module_logits_are_the_reference_logits(tiny):
    model, variables = tiny
    ids, seg = packed_batch()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda v: model.apply(v, input_ids=ids, segment_ids=seg).logits)(variables)
    want = reference.logits(variables["params"], REFERENCE_CFG, ids, seg)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < F32_TOL
    assert supports_decoding(model)


def test_the_benchmarks_copy_of_the_reference_is_the_same(tiny):
    from benchmarks.references import olmo_hybrid as copy

    _, variables = tiny
    ids, seg = packed_batch()
    want = reference.logits(variables["params"], REFERENCE_CFG, ids, seg)
    got = copy.logits(variables["params"], REFERENCE_CFG, ids, seg, None)
    # the same equations in another order of summation (a key/value head's
    # scores at a time, a jitted layer at a time): float32's noise, as above
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < F32_TOL


def test_looped_stack_is_the_scanned_stack(tiny):
    model, variables = tiny
    looped = OlmoHybrid(OlmoHybridConfig(**{**TINY, "scan_layers": False}))
    stacked = variables["params"]["layers"]
    # layer i of the loop is slot i % 4 of period i // 4
    flat = {f"slot{i}": jax.tree.map(lambda a: a[i // 4], stacked[f"slot{i % 4}"]) for i in range(8)}
    loop_vars = {"params": {**variables["params"], "layers": flat}}
    ids, seg = packed_batch()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v: model.apply(v, input_ids=ids, segment_ids=seg).logits)(variables)
        got = jax.jit(lambda v: looped.apply(v, input_ids=ids, segment_ids=seg).logits)(loop_vars)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL


def test_loss_and_gradients_are_finite_under_strong_decay(tiny):
    model, variables = tiny
    # A_log 4: g near -40 a step
    strong = jax.tree_util.tree_map_with_path(
        lambda p, a: jnp.full_like(a, 4.0) if p[-1].key == "A_log" else a, variables
    )
    ids, seg = packed_batch()

    def loss(v):
        logits = model.apply(v, input_ids=ids, segment_ids=seg).logits
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1))

    value, grads = jax.jit(jax.value_and_grad(loss))(strong)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))


# ---------------------------------------------------------------- the caches


def test_one_declaration_gives_the_pool_and_the_stored_slab():
    from llm_training_tpu.infer.cache import cache_specs, init_decode_state, slab_logical_bytes
    from llm_training_tpu.serve.paged_cache import init_paged_pool, init_state_slab

    cfg = OlmoHybridConfig(**TINY)
    kv, recurrent = cache_specs(cfg)
    assert (kv.layers, kv.kv_heads, kv.head_dim) == (2, 6, 10)  # layers 3 and 7
    assert (recurrent.layers, recurrent.heads, recurrent.key_dim, recurrent.value_dim) == (6, 6, 12, 64)
    assert (recurrent.conv_taps, recurrent.conv_channels) == (3, 6 * (12 + 12 + 64))
    assert recurrent.abreast == 2 and recurrent.stored == (3, 12, 128)
    k, v = init_paged_pool(cfg, num_blocks=5, block_size=8)
    state, tail = init_state_slab(cfg, slots=3)
    assert k.shape == v.shape == (2, 5, 6, 8, 10)
    assert state.shape == (6, 3, 3, 12, 128) and state.dtype == jnp.float32
    assert tail.shape == (6, 3, 3, 528)
    dense = init_decode_state(cfg, batch_size=3, max_length=32)
    assert dense.k.shape == (2, 3, 32, 6, 10) and dense.state.shape == state.shape
    # stored two abreast, the slab is no larger than what it holds
    assert slab_logical_bytes(recurrent, 3, tail.dtype) == state.size * 4 + tail.size * 4
    # the published widths: [15, 96, 384], 144 x 128 floats a head exactly
    published = OlmoHybridConfig(num_hidden_layers=12).cache_specs()
    assert (published[0].layers, published[0].kv_heads, published[0].head_dim) == (3, 30, 128)
    assert published[1].stored == (15, 96, 384) and published[1].conv_channels == 11520
    assert published[1].layers == 9 and OlmoHybridConfig(num_hidden_layers=12).scan_period == 4


# ------------------------------------------------------------------- serving

REQUESTS = [(19, 20), (5, 30), (11, 9), (30, 6), (3, 14)]  # (prompt, new tokens)
SERVE = dict(max_batch=2, max_model_len=64, block_size=8, prefill_chunk=8, num_blocks=7, eos_token_id=None)


def serve_requests():
    rng = np.random.default_rng(5)
    return [
        {"id": f"r{i}", "prompt": rng.integers(0, 256, size=n).tolist(), "max_new_tokens": m}
        for i, (n, m) in enumerate(REQUESTS)
    ]


def served_against_reference(variables, requests, done, quant=None):
    """For each request, over every served position: (the widest gap by which
    the served token's reference logit lies below the reference's best, the
    widest difference between the served logprob and the reference's)."""
    from benchmarks.references import _common, olmo_hybrid as copy

    gaps, logprob_gaps, control = [], [], []
    for r in requests:
        served = done[r["id"]]["tokens"]
        tokens = r["prompt"] + served
        ids, seg = np.zeros((1, 64), np.int32), np.zeros((1, 64), np.int32)
        ids[0, : len(tokens)] = tokens
        seg[0, : len(tokens)] = 1
        logits = np.asarray(reference.logits(variables["params"], REFERENCE_CFG, jnp.asarray(ids), jnp.asarray(seg)))[0]
        at = np.arange(len(r["prompt"]) - 1, len(tokens) - 1)  # position p chooses token p + 1
        rows = logits[at]
        gaps.append(float((rows.max(-1) - rows[np.arange(len(at)), served]).max()))
        logprobs = np.asarray(jax.nn.log_softmax(rows))[np.arange(len(at)), served]
        logprob_gaps.append(float(np.abs(logprobs - np.asarray(done[r["id"]]["logprobs"])).max()))
        if quant is not None:
            low = np.asarray(copy.logits(
                variables["params"], REFERENCE_CFG, jnp.asarray(ids), jnp.asarray(seg), None, _common.QUANTS[quant]
            ))[0][at].argmax(-1)
            control.append(float((rows.max(-1) - rows[np.arange(len(at)), low]).max()))
    return max(gaps), max(logprob_gaps), max(control, default=None)


def run_engine(model, variables, **serve):
    engine = ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))
    requests = serve_requests()
    events = []
    # two at once, the others join mid-flight into recycled slots
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


def test_chunked_prefill_then_paged_decode_is_the_reference_forward(tiny):
    """Prompts of 19, 5, 11, 30 and 3 tokens in chunks of 8 (so chunks of
    unequal length, the last one padded), five requests through two slots (a
    recycled slot holds its last tenant's state until the first chunk reads
    it as zeros), a pool of 7 blocks (so one request is evicted mid-decode
    and re-prefilled from a zero state with its progress folded in): every
    served position's logprob against the reference's full forward."""
    model, variables = tiny
    with jax.default_matmul_precision("highest"):
        engine, requests, done = run_engine(model, variables)
    assert all(done[r["id"]]["stop_reason"] == "max_tokens" for r in requests)
    assert engine.scheduler.evictions >= 1 and engine.allocator.blocks_in_use == 0
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    stats = engine.stats()
    # six linear layers, two slots: the state as stored (3 x 12 x 128 = 6 x 12 x 64) and the tails
    assert stats["decode/state_bytes"] == 6 * 2 * (6 * 12 * 64 * 4 + 3 * 528 * 4)
    assert stats["decode/state_logical_bytes"] == stats["decode/state_bytes"]
    from llm_training_tpu.telemetry import get_registry

    # a first chunk for every admission: five requests and each requeue
    resets = get_registry().counter("serve/state_resets").value
    assert resets >= len(requests) + engine.scheduler.evictions


@pytest.mark.parametrize("fault", ["state_not_reset", "tail_one_tap_off"])
def test_a_planted_fault_in_the_slab_is_caught(tiny, monkeypatch, fault):
    """A recycled slot's state read as it was left; the conv tail read one
    tap late."""
    from llm_training_tpu.models.olmo_hybrid import model as program

    if fault == "state_not_reset":
        monkeypatch.setattr(
            program, "_slot_rows", lambda slab, slots, fresh: slab if slots is None else slab[slots]
        )
    else:
        proper = program._slot_rows

        def shifted(slab, slots, fresh):
            rows = proper(slab, slots, fresh)
            return jnp.roll(rows, 1, axis=1) if rows.ndim == 3 else rows  # the tail: [B, taps, channels]

        monkeypatch.setattr(program, "_slot_rows", shifted)
    model, variables = tiny
    with jax.default_matmul_precision("highest"):
        _, requests, done = run_engine(model, variables)
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert max(gap, logprob_gap) > 100 * F32_TOL


def test_generate_through_the_dense_cache_serves_the_same_tokens(tiny):
    model, variables = tiny
    requests = serve_requests()[:3]
    with jax.default_matmul_precision("highest"):
        _, _, done = run_engine(model, variables, num_blocks=None)
        out = InferenceEngine(model, variables).generate(
            [r["prompt"] for r in requests], GenerateConfig(max_new_tokens=9)
        )
    for row, r in enumerate(requests):  # left-padded rows of 19, 5 and 11 tokens
        assert out["tokens"][row] == done[r["id"]]["tokens"][:9]
        assert np.allclose(out["logprobs"][row], done[r["id"]]["logprobs"][:9], atol=F32_TOL)
    # and the dense path's logprobs are the reference's (its second witness)
    gap, logprob_gap, _ = served_against_reference(
        variables, requests,
        {r["id"]: {"tokens": out["tokens"][row], "logprobs": out["logprobs"][row]}
         for row, r in enumerate(requests)},
    )
    assert gap < F32_TOL and logprob_gap < F32_TOL


def test_bfloat16_serving_passes_and_the_fp8_control_does_not():
    model = OlmoHybrid(OlmoHybridConfig(**{**TINY, "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}))
    variables = seeded_variables(model, dtype_scale=0.1)
    engine, requests, done = run_engine(model, variables)
    gap, _, control = served_against_reference(variables, requests, done, quant="fp8")
    assert gap <= BF16_GAP < control, (gap, control)


def test_cli_model_provider_takes_the_family():
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.models.hf_io import conversion_module, model_class_for_hf
    from llm_training_tpu.models.olmo_hybrid.hf_conversion import config_from_hf, config_to_hf

    provider = ModelProvider(model_class="llm_training_tpu.models.OlmoHybrid", model_kwargs=TINY)
    assert isinstance(provider.get_model(), OlmoHybrid)
    assert model_class_for_hf({"model_type": "olmo_hybrid"}).endswith("OlmoHybrid")
    published = json.loads((ROOT / "benchmarks/configs/olmo-hybrid-7b.json").read_text())
    cfg = config_from_hf({**published, **published["reduced_from"], "layer_types": None})
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.hidden_size) == (32, 100352, 3840)
    assert cfg.scan_period == 4 and sum(cfg.layer_kinds) == 8 and not cfg.layer_kinds[0]
    assert (cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.linear_conv_kernel_dim) == (96, 192, 4)
    back = config_to_hf(cfg)
    assert back["layer_types"][:8] == published["layer_types"] and published["num_hidden_layers"] == 8
    assert back["rope_parameters"] == published["rope_parameters"] == {"rope_theta": None}
    with pytest.raises(NotImplementedError, match="no HuggingFace weight map"):
        conversion_module(cfg).params_from_hf({}, cfg)
    with pytest.raises(ValueError, match="rope_theta"):
        config_from_hf({**published, "rope_parameters": {"rope_theta": 10000.0}})
