"""LongCat-Flash (`models/longcat_flash`): latent attention in its two forms
against each other, the module against its plain reference, the double
layer's order, serving through the paged latent pool and the dense latent
buffer against the reference's full forward, the expert share with its
zero-compute experts against the uncut layer, and the two copies of the
reference against each other. Float32 on the CPU unless a test says otherwise.

Tolerances, with their reasons:
- float32 against float32 (`highest` products on both sides): 1e-4 on logits
  and log-probabilities of magnitude 1 to 10. The two sides sum in different
  orders (absorbed against expanded, pages a trip at a time with an online
  softmax against full [S, S] scores).
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
from llm_training_tpu.models.base import LatentCacheSpec
from llm_training_tpu.models.longcat_flash import LongcatFlash, LongcatFlashConfig, reference
from llm_training_tpu.models.longcat_flash.model import LongcatMoE
from llm_training_tpu.serve import ServeConfig, ServingEngine

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

F32_TOL = 1e-4
BF16_GAP = 0.08  # read here over 6 draws of the weights: bfloat16 0.0027 to 0.030, the fp8 control 0.159 to 0.490

TINY = dict(
    vocab_size=256, hidden_size=64, ffn_hidden_size=96, expert_ffn_hidden_size=32, num_layers=2,
    num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, qk_nope_head_dim=16,
    v_head_dim=16, n_routed_experts=16, zero_expert_num=8, moe_topk=4, experts_held=8, experts_first=4,
    param_dtype="float32", compute_dtype="float32", attention_impl="xla", moe_impl="dense",
)
# the same model as the reference's mapping (the source's keys)
REFERENCE_CFG = {
    "num_attention_heads": 4, "rms_norm_eps": 1e-5, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "kv_lora_rank": 32, "q_lora_rank": 48, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "rope_theta": 1e7, "zero_expert_num": 8, "moe_topk": 4, "routed_scaling_factor": 6.0,
    "experts_first": 4, "num_layers": 2,
}


def seeded_variables(model, scale=0.2, seed=1):
    """Random weights that exercise every term: a correction bias that moves
    the choice of experts, norm weights left at one."""
    variables = nn.meta.unbox(
        jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)))(jax.random.key(0))
    )

    def draw(path, leaf):
        name = path[-1].key
        # (crc32, not hash(): a str's hash differs from one process to the next)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(jax.tree_util.keystr(path).encode()))
        if name == "weight":
            return leaf
        width = 0.01 if name == "bias" else scale
        return (jax.random.normal(key, leaf.shape) * width).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(draw, variables)


@pytest.fixture(scope="module")
def tiny():
    model = LongcatFlash(LongcatFlashConfig(**TINY))
    return model, seeded_variables(model)


# ------------------------------------------------------- the two forms of MLA


def latent_case(width, seq, dtype=jnp.float32):
    """Three rows holding 0, 13 and 24 (a chunk: 0, 5, 11) cached tokens in
    pages of 8 of a pool of 14 blocks; both low-rank scale factors are in
    the queries and the rows already (they are the block's, not the cache's)."""
    rng = np.random.default_rng(0)
    rows, heads, nope, rope, latent, v, page = 3, 4, 16, 8, 32, 16, 8
    pool = jnp.asarray(rng.normal(size=(14, 1, page, width)), dtype).at[..., latent + rope:].set(0)
    tables = jnp.asarray(rng.permutation(np.arange(1, 13)).reshape(rows, 4), jnp.int32)
    lengths = jnp.asarray([0, 13, 24] if seq == 1 else [0, 5, 11], jnp.int32)
    q_nope = 2.0 * jnp.asarray(rng.normal(size=(rows, seq, heads, nope)), dtype)
    q_rope = 2.0 * jnp.asarray(rng.normal(size=(rows, seq, heads, rope)), dtype)
    row = jnp.asarray(rng.normal(size=(rows, seq, latent + rope)), dtype).at[..., :latent].multiply(3.4641)
    row = jnp.pad(row, ((0, 0), (0, 0), (0, width - latent - rope)))
    w_kvb = jnp.asarray(rng.normal(size=(latent, heads, nope + v)) * 0.2, dtype)
    return (q_nope, q_rope, row, w_kvb, pool, lengths, tables), jnp.ones((rows, seq), jnp.int32)


@pytest.mark.parametrize("seq", [1, 5], ids=["one_token", "chunk"])
@pytest.mark.parametrize("width", [40, 48], ids=["row_as_is", "row_padded"])
def test_absorbed_attention_is_expanded_attention(width, seq):
    """With cached tokens and without (row 0 holds none), through the XLA
    path in both forms and, absorbed, through the interpreted `mla_decode`
    kernel and page writer."""
    from llm_training_tpu.ops.latent_attention import paged_latent_attention

    args, seg = latent_case(width, seq)
    run = lambda **kw: jax.jit(
        lambda *a: paged_latent_attention(*a, segment_ids=seg, scale=24 ** -0.5, **kw)
    )(*args)
    with jax.default_matmul_precision("highest"):
        want, want_pool = run(impl="xla", absorbed=False)
        for kw in (dict(impl="xla", absorbed=True), dict(impl="pallas", absorbed=True)):
            got, got_pool = run(**kw)
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5 * np.abs(np.asarray(want)).max()
            assert np.array_equal(np.asarray(got_pool), np.asarray(want_pool))
    assert np.abs(np.asarray(want)).max() > 0.1


def test_the_dense_buffer_attends_as_the_paged_pool_does():
    """`LayerCache.attend_latent` on a dense latent buffer (a left-padded
    batch at one shared index) and on the paged pool give one answer."""
    from llm_training_tpu.infer.cache import init_decode_state
    from llm_training_tpu.models.base import PagedDecodeState
    from llm_training_tpu.models.cache import open_cache
    from llm_training_tpu.serve.paged_cache import init_paged_pool

    cfg = LongcatFlashConfig(**TINY)
    (q_nope, q_rope, row, w_kvb, _, _, _), _ = latent_case(40, 6)
    row = row[..., :40]
    seg = jnp.asarray([[0, 0, 1, 1, 1, 1], [1] * 6, [0, 1, 1, 1, 1, 1]], jnp.int32)  # left padding

    def dense(state):
        cache, ids = open_cache(state, seg, 3, 6)
        return cache.attend_latent(1, q_nope, q_rope, row, w_kvb, ids, scale=0.2)[0]

    def paged(pool):
        # the same tokens, each row's real ones from slot 0 of its own pages
        state = PagedDecodeState(
            k=pool, v=None, block_tables=jnp.asarray([[1], [2], [3]], jnp.int32),
            lengths=jnp.zeros((3,), jnp.int32),
        )
        shift = lambda x: jnp.stack([jnp.roll(x[b], -int(n), axis=0) for b, n in enumerate((2, 0, 1))])
        ids = jnp.asarray([[1, 1, 1, 1, 0, 0], [1] * 6, [1, 1, 1, 1, 1, 0]], jnp.int32)
        cache, ids = open_cache(state, ids, 3, 6)
        out = cache.attend_latent(1, shift(q_nope), shift(q_rope), shift(row), w_kvb, ids, scale=0.2)[0]
        return jnp.stack([jnp.roll(out[b], int(n), axis=0) for b, n in enumerate((2, 0, 1))])

    with jax.default_matmul_precision("highest"):
        want = jax.jit(dense)(init_decode_state(cfg, 3, 16))
        got = jax.jit(paged)(init_paged_pool(cfg, 4, 8)[0])
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 1e-5


# --------------------------------------------------------- module, reference


def packed_batch(rows=2, vocab=256):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(rows, 48)).astype(np.int32)
    seg = np.concatenate([np.full(20, 1), np.full(24, 2), np.zeros(4)]).astype(np.int32)
    pos = np.concatenate([np.arange(20), np.arange(24), np.zeros(4)]).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(np.tile(seg, (rows, 1))), jnp.asarray(np.tile(pos, (rows, 1)))


def test_module_logits_are_the_reference_logits(tiny):
    model, variables = tiny
    ids, seg, pos = packed_batch()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda v: model.apply(
            v, input_ids=ids, segment_ids=seg, position_ids=pos).logits)(variables)
    want = reference.logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < F32_TOL
    assert supports_decoding(model)


def test_the_shortcut_joins_at_the_end_of_the_double_layer(tiny, monkeypatch):
    """The order of the sub-blocks: with `m` joined one sub-block early (before
    the second MLA block, which would then read it) the reference no longer
    gives the module's logits."""
    model, variables = tiny
    ids, seg, pos = packed_batch()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda v: model.apply(
            v, input_ids=ids, segment_ids=seg, position_ids=pos).logits)(variables)

    def early(x, w, cfg, segment_ids, position_ids):
        r, eps = reference, cfg["rms_norm_eps"]
        norm = lambda sub, name, h: r.rms_norm(h, w[sub][name]["weight"], eps)
        h = x + r.mla_block(norm("sub_0", "input_layernorm", x), w["sub_0"]["self_attn"], cfg,
                            segment_ids, position_ids)
        u = norm("sub_0", "post_attention_layernorm", h)
        h = h + r.swiglu(u, w["sub_0"]["mlp"]) + r.moe_block(u, w["mlp"], cfg)  # joined here
        h = h + r.mla_block(norm("sub_1", "input_layernorm", h), w["sub_1"]["self_attn"], cfg,
                            segment_ids, position_ids)
        return h + r.swiglu(norm("sub_1", "post_attention_layernorm", h), w["sub_1"]["mlp"])

    monkeypatch.setattr(reference, "double_layer", early)
    wrong = reference.logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(wrong))[real].max() > 100 * F32_TOL


def test_the_benchmarks_copy_of_the_reference_is_the_same(tiny):
    from benchmarks.references import longcat_flash as copy

    _, variables = tiny
    ids, seg, pos = packed_batch()
    want = reference.logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    got = copy.logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 2e-5


def test_looped_stack_is_the_scanned_stack(tiny):
    model, variables = tiny
    looped = LongcatFlash(LongcatFlashConfig(**{**TINY, "scan_layers": False}))
    stacked = variables["params"]["layers"]["layer"]
    flat = {f"layers_{i}": jax.tree.map(lambda a: a[i], stacked) for i in range(2)}
    loop_vars = {"params": {k: v for k, v in variables["params"].items() if k != "layers"} | flat}
    ids, seg, pos = packed_batch()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v: model.apply(v, input_ids=ids, segment_ids=seg, position_ids=pos).logits)(variables)
        got = jax.jit(lambda v: looped.apply(v, input_ids=ids, segment_ids=seg, position_ids=pos).logits)(loop_vars)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL


def test_loss_and_gradients_are_finite(tiny):
    model, variables = tiny
    ids, seg, pos = packed_batch()

    def loss(v):
        logits = model.apply(v, input_ids=ids, segment_ids=seg, position_ids=pos).logits
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1))

    value, grads = jax.jit(jax.value_and_grad(loss))(variables)
    assert np.isfinite(float(value))
    assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))
    # no gradient reaches the correction bias: it sees the choice only
    assert not np.asarray(grads["params"]["layers"]["layer"]["mlp"]["router"]["bias"]).any()


# ------------------------------------------------------------------ the share


def moe_layer(**over):
    return LongcatMoE(LongcatFlashConfig(**{
        **TINY, "n_routed_experts": 64, "zero_expert_num": 32, "moe_topk": 12,
        "experts_held": None, "experts_first": 0, **over,
    }))


def moe_params(x, seed=3):
    params = nn.meta.unbox(jax.jit(moe_layer().init)(jax.random.key(seed), x))
    return jax.tree_util.tree_map_with_path(
        lambda p, a: jax.random.normal(
            jax.random.key(zlib.crc32(jax.tree_util.keystr(p).encode())), a.shape
        ) * (0.0 if p[-1].key == "bias" else 0.2), params,
    )["params"]


SHARE_CFG = {**REFERENCE_CFG, "zero_expert_num": 32, "moe_topk": 12, "experts_first": 0}


@pytest.mark.parametrize("impl", ["dense", "ragged"])
def test_eight_shares_with_the_zero_term_counted_once_add_up_to_the_uncut_layer(impl):
    """8 shares of 8 experts of 64, beside 32 zero-compute experts: every
    share computes the identity term of its own rows in full, so the eight
    partial outputs hold it eight times; counted ONCE they are the uncut
    layer of the reference."""
    x = jax.random.normal(jax.random.key(2), (2, 24, 64), jnp.float32)
    params = moe_params(x)
    names = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
    with jax.default_matmul_precision("highest"):
        want = reference.moe_block(x, params, SHARE_CFG)
        none_held = {**params, **{n: params[n][:0] for n in names}}
        zero_term = reference.moe_block(x, none_held, SHARE_CFG)  # no real expert: the identity term alone
        assert np.abs(np.asarray(zero_term)).max() > 0.1
        total = jnp.zeros_like(x)
        tally = []
        for share in range(8):
            part = moe_layer(experts_held=8, experts_first=8 * share, moe_impl=impl)
            mine = {**params, **{n: params[n][8 * share: 8 * share + 8] for n in names}}
            out, (sel_frac, _, dropped), counts = jax.jit(part.apply)({"params": mine}, x)
            assert sel_frac.shape == (96,) and float(dropped) == 0.0  # the router keeps all 96 outputs
            total = total + (out - zero_term)
            alone = reference.moe_block(x, mine, {**SHARE_CFG, "experts_first": 8 * share})
            assert np.abs(np.asarray(out) - np.asarray(alone)).max() < F32_TOL
            tally.append(np.asarray(counts))
    assert np.abs(np.asarray(total + zero_term) - np.asarray(want)).max() < F32_TOL
    # (held here, zero-compute, held elsewhere) of each share's 48 x 12 assignments: the zero-compute
    # ones are every share's own, and over the eight shares each real one was held exactly once
    tally = np.stack(tally)
    assert (tally.sum(axis=1) == 48 * 12).all() and (tally[:, 1] == tally[0, 1]).all()
    assert tally[:, 0].sum() + tally[0, 1] == 48 * 12


@pytest.mark.parametrize("choice", ["all_zero", "none_zero"])
def test_a_token_that_picks_only_zero_experts_and_one_that_picks_none(choice):
    x = jax.random.normal(jax.random.key(5), (1, 6, 64), jnp.float32)
    params = moe_params(x)
    # the bias moves the choice, never the weights: +1 lifts a group over any score
    lifted = jnp.zeros((96,)).at[64:].set(1.0) if choice == "all_zero" else jnp.zeros((96,)).at[:64].set(1.0)
    params = {**params, "router": {**params["router"], "bias": lifted}}
    layer = moe_layer(moe_topk=12)
    with jax.default_matmul_precision("highest"):
        out, _, counts = jax.jit(layer.apply)({"params": params}, x)
        want = reference.moe_block(x, params, SHARE_CFG)
        scores = jax.nn.softmax(x.reshape(-1, 64) @ params["router"]["kernel"], axis=-1)
    assert np.abs(np.asarray(out) - np.asarray(want)).max() < F32_TOL
    if choice == "all_zero":
        # exactly the identity: six times the twelve largest zero-expert scores, times the token
        weight = 6.0 * jax.lax.top_k(scores[:, 64:], 12)[0].sum(-1, keepdims=True)
        assert np.abs(np.asarray(out).reshape(-1, 64) - np.asarray(weight * x.reshape(-1, 64))).max() < 1e-6
        assert np.asarray(counts).tolist() == [0, 72, 0]
    else:
        assert np.asarray(counts).tolist() == [72, 0, 0]


# ---------------------------------------------------------------- the caches


def test_one_declaration_gives_the_latent_pool_and_the_dense_latent_buffer():
    from llm_training_tpu.infer.cache import cache_specs, init_decode_state, token_rows
    from llm_training_tpu.serve.paged_cache import init_paged_pool, init_state_slab, pool_bytes

    cfg = LongcatFlashConfig(**TINY)
    latent, recurrent = cache_specs(cfg)
    assert latent == LatentCacheSpec(layers=4, latent_dim=32, rope_dim=8) and recurrent is None
    # the row's 40 values are stored as whole 128-lane tiles, as the published 576 are as 640
    assert latent.width == 128 and LatentCacheSpec(56, 512, 64).width == 640
    assert token_rows(cfg) == (1, 4, 1, 128)
    k, v = init_paged_pool(cfg, num_blocks=5, block_size=8)
    assert k.shape == (4, 5, 1, 8, 128) and v is None and init_state_slab(cfg, slots=3) is None
    assert pool_bytes(k, v) == 4 * 5 * 8 * 128 * 4
    dense = init_decode_state(cfg, batch_size=3, max_length=32)
    assert dense.k.shape == (4, 3, 32, 1, 128) and dense.v is None and dense.state is None


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
    widest difference between the served log-probability and the reference's:
    the logits up to the constant a softmax removes)."""
    from benchmarks.references import _common, longcat_flash as copy

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


@pytest.mark.parametrize("variant", ["dense_experts", "grouped_experts_in_place"])
def test_chunked_prefill_then_paged_decode_is_the_reference_forward(tiny, variant):
    """Prompts of 19, 5, 11, 30 and 3 tokens in chunks of 8 (chunks of unequal
    length, the last one padded), five requests through two slots (the later
    ones join mid-flight into recycled blocks, whose stale latents lie past
    their lengths), a pool of 7 blocks (so one request is evicted mid-decode
    and re-prefilled with its progress folded in): every served position
    against the reference's full forward, so a stale latent page or a wrong
    rotary position fails. Also with the held experts multiplied in place by
    the grouped product (`moe_impl='ragged'`, the chip's path)."""
    _, variables = tiny
    over = {"dense_experts": {}, "grouped_experts_in_place": {"moe_impl": "ragged"}}[variant]
    model = LongcatFlash(LongcatFlashConfig(**{**TINY, **over}))
    with jax.default_matmul_precision("highest"):
        engine, requests, done = run_engine(model, variables)
    assert all(done[r["id"]]["stop_reason"] == "max_tokens" for r in requests)
    assert engine.scheduler.evictions >= 1 and engine.allocator.blocks_in_use == 0
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    stats = engine.stats()
    assert stats["decode/latent_pool_bytes"] == stats["decode/cache_bytes"] == 4 * 8 * 8 * 128 * 4
    if variant == "grouped_experts_in_place":
        assert stats["decode/experts_in_place_layers"] == 2


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
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert max(gap, logprob_gap) > 100 * F32_TOL


def test_generate_through_the_dense_latent_buffer_serves_the_same_tokens(tiny):
    model, variables = tiny
    requests = serve_requests()[:3]
    with jax.default_matmul_precision("highest"):
        _, _, done = run_engine(model, variables, num_blocks=None)
        out = InferenceEngine(model, variables).generate(
            [r["prompt"] for r in requests], GenerateConfig(max_new_tokens=9)
        )
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    for row, r in enumerate(requests):  # left-padded rows of 19, 5 and 11 tokens
        assert out["tokens"][row] == done[r["id"]]["tokens"][:9]
        assert np.allclose(out["logprobs"][row], done[r["id"]]["logprobs"][:9], atol=F32_TOL)


def test_bfloat16_serving_passes_and_the_fp8_control_does_not():
    model = LongcatFlash(LongcatFlashConfig(**{**TINY, "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}))
    variables = seeded_variables(model, scale=0.1)
    engine, requests, done = run_engine(model, variables)
    gap, _, control = served_against_reference(variables, requests, done, quant="fp8")
    assert gap <= BF16_GAP < control, (gap, control)


def test_cli_model_provider_takes_the_family():
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.models.hf_io import conversion_module, model_class_for_hf
    from llm_training_tpu.models.longcat_flash.hf_conversion import config_from_hf, config_to_hf

    provider = ModelProvider(model_class="llm_training_tpu.models.LongcatFlash", model_kwargs=TINY)
    assert isinstance(provider.get_model(), LongcatFlash)
    assert model_class_for_hf({"model_type": "longcat_flash"}).endswith("LongcatFlash")
    published = json.loads((ROOT / "benchmarks/configs/longcat-flash-omni-ep32.json").read_text())
    cfg = config_from_hf({**published, **published["reduced_from"]})
    assert (cfg.num_layers, cfg.n_routed_experts, cfg.vocab_size) == (28, 512, 131072)
    assert (cfg.router_width, cfg.moe_topk, cfg.routed_scaling_factor) == (768, 12, 6)
    assert (cfg.q_scale, round(cfg.kv_scale, 4)) == (2.0, 3.4641)
    assert cfg.cache_specs()[0] == LatentCacheSpec(56, 512, 64)
    back = config_to_hf(cfg)
    catalog_keys = set(published) - {
        "source", "model_type", "scope", "initializer_range", "experts_first", "reduced_from",
        "deployment", "assumed", "reference", "stated_precision", "control_precision", "program", "check",
    }
    assert all(back[k] == {**published, **published["reduced_from"]}[k] for k in catalog_keys)
    with pytest.raises(NotImplementedError, match="no HuggingFace weight map"):
        conversion_module(cfg).params_from_hf({}, cfg)
