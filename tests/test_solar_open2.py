"""Solar-Open2 (`models/solar_open2`): the chunked delta rule against the
equation, the module against its plain reference, serving through the paged
pool AND the state slab against the reference's full forward, the expert
share against the uncut layer, and the two copies of the reference against
each other. Float32 on the CPU unless a test says otherwise.

Tolerances, with their reasons:
- float32 against float32 (`highest` products on both sides): 1e-4 on logits
  of magnitude 1 to 10. The two sides sum in different orders (chunks of 16
  against one token at a time, paged gathers against [S, S] scores).
- the rule against a float64 numpy oracle: 2e-5 of the output's scale.
- bfloat16 compute against the float32 reference: the served token's
  reference logit may lie at most `BF16_GAP` below the reference's best. The
  fp8 control (the reference's own products rounded through e4m3) must lie
  further off than that, so the limit separates the stated precision from
  the next one down.
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
from llm_training_tpu.models.solar_open2 import SolarOpen2, SolarOpen2Config, reference
from llm_training_tpu.models.solar_open2.kda import kda_chunked, kda_step
from llm_training_tpu.serve import ServeConfig, ServingEngine

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

F32_TOL = 1e-4
BF16_GAP = 1.2  # read here over 6 draws of the weights: bfloat16 0.29 to 0.68, the fp8 control 1.81 to 2.91

TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_heads=4, linear_head_dim=16,
    n_routed_experts=16, experts_held=8, experts_first=4, num_experts_per_tok=4,
    moe_intermediate_size=32, param_dtype="float32", compute_dtype="float32",
    attention_impl="xla", moe_impl="dense",
)
# the same model as the reference's mapping (the published keys)
REFERENCE_CFG = {
    "linear_attn_config": {"num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4,
                           "num_kv_heads": None},
    "kda_allow_neg_eigval": True, "rms_norm_eps": 1e-5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "use_gqa_gate": True,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "routed_scaling_factor": 1.0,
    "experts_first": 4, "num_hidden_layers": 8, "gqa_interval": 3, "gqa_layers": [0, 4, 8, 12],
}


def seeded_variables(model, dtype_scale=0.2, seed=1):
    """Random weights that exercise every term: decays spread from slow to
    fast, a correction bias that moves the choice of experts."""
    variables = nn.meta.unbox(
        jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)))(jax.random.key(0))
    )

    def draw(path, leaf):
        name = path[-1].key
        # (crc32, not hash(): a str's hash differs from one process to the next)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(jax.tree_util.keystr(path).encode()))
        if name in ("A_log", "dt_bias"):
            return (jax.random.normal(key, leaf.shape) * 0.7).astype(leaf.dtype)
        if name == "e_score_correction_bias":
            return (jax.random.normal(key, leaf.shape) * 0.1).astype(leaf.dtype)
        if name == "weight":
            return leaf
        return (jax.random.normal(key, leaf.shape) * dtype_scale).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(draw, variables)


@pytest.fixture(scope="module")
def tiny():
    model = SolarOpen2(SolarOpen2Config(**TINY))
    return model, seeded_variables(model)


# ------------------------------------------------------------------ the rule


def oracle(q, k, v, log_alpha, beta, state, starts=None):
    """S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T, o_t = S_t^T q_t,
    with explicit matrices in float64."""
    q, k, v, log_alpha, beta, state = (np.asarray(a, np.float64) for a in (q, k, v, log_alpha, beta, state))
    batch, seq, heads, dk = q.shape
    out = np.zeros(v.shape)
    state = state.copy()
    for b in range(batch):
        for h in range(heads):
            s = state[b, h]
            for t in range(seq):
                if starts is not None and starts[b, t]:
                    s = np.zeros_like(s)
                kt = k[b, t, h][:, None]
                s = (np.eye(dk) - beta[b, t, h] * kt @ kt.T) @ (np.exp(log_alpha[b, t, h])[:, None] * s)
                s = s + beta[b, t, h] * kt @ v[b, t, h][None, :]
                out[b, t, h] = s.T @ q[b, t, h]
            state[b, h] = s
    return out, state


def rule_inputs(decay, seq=45, carried=False, seed=0):
    rng = np.random.default_rng(seed)
    batch, heads, dk, dv = 2, 3, 8, 8
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(batch, seq, heads, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(batch, seq, heads, dk)))
    v = rng.normal(size=(batch, seq, heads, dv))
    # a decay PER KEY CHANNEL: "slow" remembers thousands of tokens, "strong"
    # loses e^-5 a step (e^(-G) passes float32's range within 18 tokens)
    centre = {"slow": -1e-3, "strong": -5.0, "mixed": -1.0}[decay]
    log_alpha = centre * rng.uniform(0.5, 1.5, size=(batch, seq, heads, dk))
    beta = rng.uniform(0.0, 2.0, size=(batch, seq, heads))
    beta[:, ::7] = 2.0  # the negative-eigenvalue end, exactly
    state = rng.normal(size=(batch, heads, dk, dv)) if carried else np.zeros((batch, heads, dk, dv))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, log_alpha, beta, state))


@pytest.mark.parametrize("carried", [False, True], ids=["zero_state", "carried_state"])
@pytest.mark.parametrize("decay", ["slow", "strong", "mixed"])
def test_chunked_rule_is_the_equation_token_by_token(decay, carried):
    q, k, v, log_alpha, beta, state = rule_inputs(decay, carried=carried)
    want_out, want_state = oracle(q, k, v, log_alpha, beta, state)
    got_out, got_state = jax.jit(kda_chunked)(q, k, v, log_alpha, beta, state)

    def stepped(state):
        def one(s, xs):
            return kda_step(s, *xs)
        s, out = jax.lax.scan(one, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_alpha, beta)))
        return jnp.moveaxis(out, 0, 1), s

    step_out, step_state = jax.jit(stepped)(state)
    scale = max(1.0, float(np.abs(want_out).max()))
    for got in (got_out, step_out):
        assert np.isfinite(np.asarray(got)).all()
        assert np.abs(np.asarray(got) - want_out).max() < 2e-5 * scale
    for got in (got_state, step_state):
        assert np.abs(np.asarray(got) - want_state).max() < 2e-5 * max(1.0, float(np.abs(want_state).max()))
    if decay == "strong":
        # the hazard this guards: factored as (k_i e^G_i) . (k_j e^-G_j) the
        # same sums leave float32 inside one chunk of 64
        running = np.cumsum(np.asarray(log_alpha)[:, :64], axis=1)
        with np.errstate(over="ignore"):
            assert np.isinf(np.exp(-running.astype(np.float32))).any()


def test_chunked_rule_restarts_at_a_document_start_and_skips_padding():
    q, k, v, log_alpha, beta, state = rule_inputs("mixed", seq=40, carried=True, seed=3)
    starts = np.zeros((2, 40), bool)
    starts[0, [9, 16, 33]] = True  # mid-chunk, on a chunk's first position, late
    starts[1, 21] = True
    # padding changes nothing: beta 0, log alpha 0
    pad = np.zeros((2, 40), bool)
    pad[:, 36:] = True
    log_alpha = jnp.where(pad[..., None, None], 0.0, log_alpha)
    beta = jnp.where(pad[..., None], 0.0, beta)
    want_out, want_state = oracle(q, k, v, log_alpha, beta, state, starts)
    got_out, got_state = jax.jit(kda_chunked)(q, k, v, log_alpha, beta, state, jnp.asarray(starts))
    assert np.abs(np.asarray(got_out) - want_out)[~pad].max() < 2e-5 * np.abs(want_out).max()
    assert np.abs(np.asarray(got_state) - want_state).max() < 2e-5 * np.abs(want_state).max()
    # the state after the last real token IS the state after the padding
    _, before_pad = oracle(q[:, :36], k[:, :36], v[:, :36], log_alpha[:, :36], beta[:, :36], state, starts[:, :36])
    assert np.abs(np.asarray(got_state) - before_pad).max() < 2e-5 * np.abs(before_pad).max()


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
    from benchmarks.references import solar_open2 as copy

    _, variables = tiny
    ids, seg = packed_batch()
    want = reference.logits(variables["params"], REFERENCE_CFG, ids, seg)
    got = copy.logits(variables["params"], REFERENCE_CFG, ids, seg, None)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-6


def looped_variables(variables):
    """The scanned stack's parameters in the looped stack's layout."""
    stacked = variables["params"]["layers"]
    # layer i of the loop is slot i % 4 of period i // 4
    flat = {f"slot{i}": jax.tree.map(lambda a: a[i // 4], stacked[f"slot{i % 4}"]) for i in range(8)}
    return {"params": {**variables["params"], "layers": flat}}


def test_looped_stack_is_the_scanned_stack(tiny):
    model, variables = tiny
    looped = SolarOpen2(SolarOpen2Config(**{**TINY, "scan_layers": False}))
    loop_vars = looped_variables(variables)
    ids, seg = packed_batch()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v: model.apply(v, input_ids=ids, segment_ids=seg).logits)(variables)
        got = jax.jit(lambda v: looped.apply(v, input_ids=ids, segment_ids=seg).logits)(loop_vars)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL


def test_loss_and_gradients_are_finite_under_strong_decay(tiny):
    model, variables = tiny
    # A_log 4: log alpha near -40 a step
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


# ------------------------------------------------------------------ the share


@pytest.mark.parametrize("impl", ["dense", "ragged"])
def test_eight_shares_of_eight_experts_add_up_to_the_uncut_layer(impl):
    from llm_training_tpu.models.deepseek.model import DeepseekMoE

    base = dict(
        TINY, n_routed_experts=64, num_experts_per_tok=8, experts_held=None, experts_first=0,
        moe_impl=impl,
    )
    whole = DeepseekMoE(SolarOpen2Config(**base))
    x = jax.random.normal(jax.random.key(2), (2, 24, 64), jnp.float32)
    params = jax.jit(whole.init)(jax.random.key(3), x)
    params = nn.meta.unbox(jax.tree_util.tree_map_with_path(
        lambda p, a: jax.random.normal(jax.random.key(zlib.crc32(jax.tree_util.keystr(p).encode())), a.shape) * 0.2, params
    ))["params"]
    with jax.default_matmul_precision("highest"):
        want = reference.moe_block(x, params, {**REFERENCE_CFG, "num_experts_per_tok": 8, "experts_first": 0})
        shared = reference.swiglu(
            x, *(params["shared_experts"][n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj"))
        )
        total = jnp.zeros_like(x)
        for share in range(8):
            part = DeepseekMoE(SolarOpen2Config(**{**base, "experts_held": 8, "experts_first": 8 * share}))
            mine = {
                **params,
                **{n: params[n][8 * share : 8 * share + 8]
                   for n in ("experts_gate_proj", "experts_up_proj", "experts_down_proj")},
            }
            out, (sel_frac, _, dropped) = jax.jit(part.apply)({"params": mine}, x)
            # the router still scores and picks among all 64
            assert sel_frac.shape == (64,) and float(dropped) == 0.0
            # this share's routed part: what every share computes alike, the
            # shared expert, is counted once below
            total = total + (out - shared)
            # and the reference, given the same share, agrees with it
            alone = reference.moe_block(
                x, mine, {**REFERENCE_CFG, "num_experts_per_tok": 8, "experts_first": 8 * share}
            )
            assert np.abs(np.asarray(out) - np.asarray(alone)).max() < F32_TOL
    assert np.abs(np.asarray(total + shared) - np.asarray(want)).max() < F32_TOL


# ---------------------------------------------------------------- the caches


def test_one_declaration_gives_the_pool_and_the_slab():
    from llm_training_tpu.infer.cache import cache_specs, init_decode_state
    from llm_training_tpu.models import LlamaConfig
    from llm_training_tpu.serve.paged_cache import init_paged_pool, init_state_slab

    cfg = SolarOpen2Config(**TINY)
    kv, recurrent = cache_specs(cfg)
    assert (kv.layers, kv.kv_heads, kv.head_dim) == (2, 2, 16)  # layers 0 and 4
    assert (recurrent.layers, recurrent.heads, recurrent.conv_taps, recurrent.conv_channels) == (6, 4, 3, 192)
    k, v = init_paged_pool(cfg, num_blocks=5, block_size=8)
    state, tail = init_state_slab(cfg, slots=3)
    assert k.shape == v.shape == (2, 5, 2, 8, 16)
    assert state.shape == (6, 3, 4, 16, 16) and state.dtype == jnp.float32
    assert tail.shape == (6, 3, 3, 192)
    dense = init_decode_state(cfg, batch_size=3, max_length=32)
    assert dense.k.shape == (2, 3, 32, 2, 16) and dense.state.shape == state.shape
    # a stack of one kind keeps the pool it had, and has no slab
    llama = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=3,
                        num_attention_heads=4, num_key_value_heads=2)
    assert cache_specs(llama)[1] is None and init_state_slab(llama, slots=3) is None
    assert init_paged_pool(llama, 5, 8)[0].shape == (3, 5, 2, 8, 8)
    assert init_decode_state(llama, 2, 16).state is None


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
    from benchmarks.references import _common, solar_open2 as copy

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


@pytest.fixture(scope="module")
def dense_served(tiny):
    """What the dense expert path serves: `(engine, requests, done)`."""
    model, variables = tiny
    with jax.default_matmul_precision("highest"):
        return run_engine(model, variables)


@pytest.mark.parametrize("variant", ["dense_experts", "grouped_experts_in_place", "grouped_experts_in_place_looped"])
def test_chunked_prefill_then_paged_decode_is_the_reference_forward(tiny, dense_served, variant):
    """Prompts of 19, 5, 11, 30 and 3 tokens in chunks of 8 (so chunks of
    unequal length, the last one padded), five requests through two slots (a
    recycled slot holds its last tenant's state until the first chunk reads
    it as zeros), a pool of 7 blocks (so one request is evicted mid-decode
    and re-prefilled from a zero state with its progress folded in): every
    served position against the reference's full forward. Also with the held
    experts multiplied by the grouped product that skips what has no rows
    (`moe_impl='ragged'`, the chip's path): every expert layer of the scanned
    periods through its stack, and of the looped stack through its own
    leaves, serving the dense path's tokens."""
    model, variables = tiny
    if variant == "dense_experts":
        engine, requests, done = dense_served
    else:
        looped = variant.endswith("looped")
        model = SolarOpen2(SolarOpen2Config(**{**TINY, "moe_impl": "ragged", "scan_layers": not looped}))
        with jax.default_matmul_precision("highest"):
            engine, requests, done = run_engine(model, looped_variables(variables) if looped else variables)
        assert [done[r["id"]]["tokens"] for r in requests] == [dense_served[2][r["id"]]["tokens"] for r in requests]
    assert all(done[r["id"]]["stop_reason"] == "max_tokens" for r in requests)
    assert engine.scheduler.evictions >= 1 and engine.allocator.blocks_in_use == 0
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    stats = engine.stats()
    # the stack's eight expert layers: four a period's body in two periods, or eight looped
    assert stats["decode/experts_in_place_layers"] == (0 if variant == "dense_experts" else 8)
    assert stats["decode/state_bytes"] == 6 * 2 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    from llm_training_tpu.telemetry import get_registry

    # a first chunk for every admission: five requests and each requeue
    resets = get_registry().counter("serve/state_resets").value
    assert resets >= len(requests) + engine.scheduler.evictions


def test_a_state_that_is_not_reset_on_admission_is_caught(tiny, monkeypatch):
    """The planted fault: a recycled slot's state read as it was left."""
    from llm_training_tpu.models.solar_open2 import model as program

    monkeypatch.setattr(
        program, "_slot_rows", lambda slab, slots, fresh: slab if slots is None else slab[slots]
    )
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


def test_bfloat16_serving_passes_and_the_fp8_control_does_not():
    model = SolarOpen2(SolarOpen2Config(**{**TINY, "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}))
    variables = seeded_variables(model, dtype_scale=0.1)
    engine, requests, done = run_engine(model, variables)
    gap, _, control = served_against_reference(variables, requests, done, quant="fp8")
    assert gap <= BF16_GAP < control, (gap, control)


def test_cli_model_provider_takes_the_family():
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.models.hf_io import conversion_module, model_class_for_hf
    from llm_training_tpu.models.solar_open2.hf_conversion import config_from_hf, config_to_hf

    provider = ModelProvider(model_class="llm_training_tpu.models.SolarOpen2", model_kwargs=TINY)
    assert isinstance(provider.get_model(), SolarOpen2)
    assert model_class_for_hf({"model_type": "solar_open2"}).endswith("SolarOpen2")
    published = json.loads((ROOT / "benchmarks/configs/solar-open2-250b-ep8.json").read_text())
    cfg = config_from_hf({**published, **published["reduced_from"]})
    assert (cfg.num_hidden_layers, cfg.n_routed_experts, cfg.vocab_size) == (48, 320, 196608)
    assert cfg.scan_period == 4 and sum(cfg.layer_kinds) == 12
    assert cfg.linear_num_heads == 64 and cfg.linear_head_dim == 128 and cfg.linear_conv_kernel_dim == 4
    back = config_to_hf(cfg)
    assert back["gqa_layers"] == published["gqa_layers"]
    assert back["linear_attn_config"] == published["linear_attn_config"]
    with pytest.raises(NotImplementedError, match="no HuggingFace weight map"):
        conversion_module(cfg).params_from_hf({}, cfg)
