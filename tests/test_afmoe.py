"""AFMoE (Trinity-Mini, `model_type: afmoe`) against its plain reference, at a
tiny size on the CPU with seeded weights, and the two PAGE GROUPS it forced.

- the module's logits on a packed batch, its loss and gradients through one
  `CLM` step, the looped stack against the scanned one, the benchmark's copy
  of the reference (computed in blocks) against the plain one;
- the 8 expert-parallel shares' routed parts plus the shared expert once add
  up to the uncut reference's layer;
- one declaration gives two pools and two dense buffers, and the ONE rule of
  the short table (`page % width`) reads what a full table reads;
- the engine, the scheduler's two allocators, pages given back and reused,
  the dense path: `tests/test_afmoe_serve.py`.

Tolerances: float32 against float32 at "highest" matmul precision is held to
1e-4 (observed under 1e-6 on logits of magnitude 0.4 to 3: the room is for
another BLAS's summation order); bfloat16 in the program's place reads 50 to
1000 times that and fails each of those comparisons (one test shows it). The
bfloat16 serving check has its own limit, read here over several draws.
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

from llm_training_tpu.infer.engine import supports_decoding
from llm_training_tpu.models.afmoe import Afmoe, AfmoeConfig, reference
from llm_training_tpu.models.base import KVCacheSpec
from llm_training_tpu.models.deepseek.model import DeepseekMoE
from llm_training_tpu.serve.paged_cache import window_page_budget

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

F32_TOL = 1e-4
# read here over 6 draws of the weights, a router of 64 with 8 a token and 16 held (the cell's hazard: a near-tie
# at the 8th score moves an eighth of the normalised sum): bfloat16 0.064 to 0.587, the fp8 control 0.788 to 2.81
BF16_GAP = 0.7

# 12 layers: 4 looped in front (dense, dense, MoE, MoE), then 2 scanned periods of
# [sliding, sliding, sliding, full]; a window of 16 = 2 pages of 8
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=12, num_dense_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=16, num_experts=16, num_experts_per_tok=4, experts_held=8,
    experts_first=4, param_dtype="float32", compute_dtype="float32", attention_impl="xla",
    moe_impl="dense",
)
# the same model as the reference's mapping (the source's keys)
REFERENCE_CFG = {
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "hidden_size": 64,
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "sliding_window": 16, "mup_enabled": True,
    "global_attn_every_n_layers": 4, "num_hidden_layers": 12, "num_dense_layers": 2,
    "num_experts_per_tok": 4, "route_norm": True, "route_scale": 2.826, "experts_first": 4,
}


def seeded_variables(model, scale=0.2, seed=1):
    """Random weights that exercise every term: norm scales drawn around one
    (four norms a layer and one a head all matter), an expert bias that moves
    the choice of experts."""
    variables = nn.meta.unbox(
        jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)))(jax.random.key(0))
    )

    def draw(path, leaf):
        name = path[-1].key
        # (crc32, not hash(): a str's hash differs from one process to the next)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(jax.tree_util.keystr(path).encode()))
        noise = jax.random.normal(key, leaf.shape)
        if name == "weight":
            return (1.0 + 0.1 * noise).astype(leaf.dtype)
        return (noise * (0.01 if name == "e_score_correction_bias" else scale)).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(draw, variables)


@pytest.fixture(scope="module")
def tiny():
    model = Afmoe(AfmoeConfig(**TINY))
    return model, seeded_variables(model)


def packed_batch(rows=2, vocab=256):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(rows, 48)).astype(np.int32)
    seg = np.concatenate([np.full(20, 1), np.full(24, 2), np.zeros(4)]).astype(np.int32)
    pos = np.concatenate([np.arange(20), np.arange(24), np.zeros(4)]).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(np.tile(seg, (rows, 1))), jnp.asarray(np.tile(pos, (rows, 1)))


def module_logits(model, variables, batch):
    ids, seg, pos = batch
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda v: model.apply(
            v, input_ids=ids, segment_ids=seg, position_ids=pos).logits)(variables)


# ---------------------------------------------------------------- the family


def test_module_logits_are_the_reference_logits(tiny):
    model, variables = tiny
    batch = packed_batch()
    got = module_logits(model, variables, batch)
    want = reference.logits(variables["params"], REFERENCE_CFG, *batch)
    real = np.asarray(batch[1]) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < F32_TOL
    assert supports_decoding(model)
    assert set(variables["params"]) == {"embed_tokens", "front", "layers", "norm", "lm_head"}
    assert variables["params"]["layers"]["slot3"]["mlp"]["experts_gate_proj"].shape == (2, 8, 64, 32)


@pytest.mark.parametrize("departure", ["rotary_on_full_layers", "window_one_wider", "bfloat16"])
def test_the_tolerance_catches_a_departure(tiny, monkeypatch, departure):
    """What the comparison above is tight enough to see: positions entering
    the full layers, a window that reaches one key further back, the module
    computing in bfloat16."""
    model, variables = tiny
    batch = packed_batch()
    cfg = dict(REFERENCE_CFG)
    if departure == "rotary_on_full_layers":
        attend = reference.attention_block
        monkeypatch.setattr(
            reference, "attention_block",
            lambda x, w, cfg, seg, pos, is_window: attend(
                x, w, {**cfg, "sliding_window": 10**6}, seg, pos, True) if not is_window
            else attend(x, w, cfg, seg, pos, True),
        )
    elif departure == "window_one_wider":
        cfg["sliding_window"] = 17
    else:
        model = Afmoe(AfmoeConfig(**{**TINY, "compute_dtype": "bfloat16"}))
    got = module_logits(model, variables, batch)
    wrong = reference.logits(variables["params"], cfg, *batch)
    real = np.asarray(batch[1]) > 0
    assert np.abs(np.asarray(got, np.float32) - np.asarray(wrong))[real].max() > 50 * F32_TOL


def test_the_benchmarks_copy_of_the_reference_is_the_same(tiny):
    from benchmarks.references import afmoe as copy

    _, variables = tiny
    batch = packed_batch()
    want = reference.logits(variables["params"], REFERENCE_CFG, *batch)
    real = np.asarray(batch[1]) > 0
    for block in (8, 16, 48):  # a band of three blocks, of two, the whole row at once
        got = copy.logits(variables["params"], REFERENCE_CFG, *batch, block=block)
        assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 2e-5, block


def test_looped_stack_is_the_scanned_stack(tiny):
    model, variables = tiny
    looped = Afmoe(AfmoeConfig(**{**TINY, "scan_layers": False}))
    assert looped.config.scan_plan == (12, 0, 0) and model.config.scan_plan == (4, 2, 4)
    params = variables["params"]
    front = dict(params["front"])
    for i in range(4, 12):
        front[f"slot{i}"] = jax.tree.map(lambda a: a[(i - 4) // 4], params["layers"][f"slot{i % 4}"])
    loop_vars = {"params": {k: v for k, v in params.items() if k != "layers"} | {"front": front}}
    batch = packed_batch()
    want, got = module_logits(model, variables, batch), module_logits(looped, loop_vars, batch)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < F32_TOL


def test_clm_loss_and_gradients_are_the_references(tiny):
    """One `CLM` step's loss and its gradient for every leaf against the
    reference's logits under a plain cross entropy, differentiated by jax.
    A leaf's gradient is held to 1e-4 of the largest entry of the reference's
    (1e-6 observed); the module in bfloat16 reads 3e-2 and fails."""
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider

    model, variables = tiny
    ids, seg, pos = packed_batch()
    batch = {"input_ids": ids, "segment_ids": seg, "position_ids": pos}
    provider = lambda **over: ModelProvider(
        model_class="llm_training_tpu.models.Afmoe", model_kwargs={**TINY, **over})

    def reference_loss(params):
        logits = reference.logits(params, REFERENCE_CFG, ids, seg, pos)
        labels = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
        next_seg = jnp.concatenate([seg[:, 1:], jnp.zeros_like(seg[:, :1])], axis=1)
        valid = (seg > 0) & (seg == next_seg)
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits), labels[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)

    want, want_grads = jax.value_and_grad(reference_loss)(variables["params"])

    def worst(over):
        objective = CLM(CLMConfig(model=provider(**over), ce_chunk_size=32))
        with jax.default_matmul_precision("highest"):
            (loss, metrics), grads = jax.jit(jax.value_and_grad(
                lambda v: objective.loss_and_metrics(v, batch, train=True), has_aux=True))(variables)
        assert int(metrics["target_tokens"]) == 2 * (19 + 23)
        gaps = jax.tree.map(
            lambda g, w: float(jnp.max(jnp.abs(g - w)) / (jnp.max(jnp.abs(w)) + 1e-12)),
            grads["params"], want_grads)
        return abs(float(loss) - float(want)), max(jax.tree.leaves(gaps)), grads["params"]

    loss_gap, grad_gap, grads = worst({})
    assert loss_gap < F32_TOL and grad_gap < F32_TOL, (loss_gap, grad_gap)
    # no gradient reaches the expert bias: it sees the choice only
    assert not np.asarray(grads["layers"]["slot0"]["mlp"]["e_score_correction_bias"]).any()
    assert np.asarray(grads["layers"]["slot0"]["self_attn"]["gate_proj"]["kernel"]).any()
    loss_gap, grad_gap, _ = worst({"compute_dtype": "bfloat16"})
    assert max(loss_gap, grad_gap) > 50 * F32_TOL


def test_a_tiny_clm_fit_learns_and_reports_its_routers():
    from conftest import fit_losses

    losses = fit_losses(
        "llm_training_tpu.models.Afmoe",
        {**TINY, "vocab_size": 128, "experts_held": None, "experts_first": 0}, max_steps=16, lr=3e-3,
    )
    # random tokens: the first pass over the 64 samples reads ln 128 = 4.85, the second has seen them
    assert len(losses) == 16 and np.isfinite(losses).all()
    assert abs(losses[0] - np.log(128)) < 0.05 and np.mean(losses[8:]) < np.mean(losses[:8]) - 0.02
    model = Afmoe(AfmoeConfig(**TINY))
    out = jax.jit(lambda v, ids: model.apply(v, input_ids=ids))(
        seeded_variables(model), packed_batch()[0])
    stats = out.router_stats
    assert stats.layer_ids == tuple(range(2, 12)) and stats.sel_frac.shape == (10, 16)
    assert np.allclose(np.asarray(stats.sel_frac).sum(-1), 4.0, atol=1e-5)  # 4 choices a token


# ------------------------------------------------------------------ the share


def moe_layer(**over):
    return DeepseekMoE(AfmoeConfig(**{
        **TINY, "num_experts": 64, "num_experts_per_tok": 8, "experts_held": None,
        "experts_first": 0, **over,
    }))


SHARE_CFG = {**REFERENCE_CFG, "num_experts_per_tok": 8, "experts_first": 0}


@pytest.mark.parametrize("impl", ["dense", "ragged"])
def test_eight_shares_with_the_shared_expert_counted_once_add_up_to_the_uncut_layer(impl):
    """8 shares of 8 experts of 64, 8 a token: every share computes the shared
    expert in full, so the eight partial outputs hold it eight times; counted
    ONCE they are the uncut layer of the reference."""
    x = jax.random.normal(jax.random.key(2), (2, 24, 64), jnp.float32)
    params = nn.meta.unbox(jax.jit(moe_layer().init)(jax.random.key(3), x))
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: jax.random.normal(
            jax.random.key(zlib.crc32(jax.tree_util.keystr(p).encode())), a.shape
        ) * (0.01 if p[-1].key == "e_score_correction_bias" else 0.2), params,
    )["params"]
    names = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
    with jax.default_matmul_precision("highest"):
        want = reference.moe_block(x, params, SHARE_CFG)
        shared = reference.swiglu(x, params["shared_experts"])
        assert np.abs(np.asarray(shared)).max() > 0.1
        total = jnp.zeros_like(x)
        for share in range(8):
            part = moe_layer(experts_held=8, experts_first=8 * share, moe_impl=impl)
            mine = {**params, **{n: params[n][8 * share: 8 * share + 8] for n in names}}
            out, (sel_frac, _, dropped) = jax.jit(part.apply)({"params": mine}, x)
            assert sel_frac.shape == (64,) and float(dropped) == 0.0  # the router keeps all 64 outputs
            alone = reference.moe_block(x, mine, {**SHARE_CFG, "experts_first": 8 * share})
            assert np.abs(np.asarray(out) - np.asarray(alone)).max() < F32_TOL
            total = total + (out - shared)
    assert np.abs(np.asarray(total + shared) - np.asarray(want)).max() < F32_TOL


# ------------------------------------------------------------ the page groups


def test_one_declaration_gives_two_pools_and_two_dense_buffers():
    from llm_training_tpu.infer.cache import cache_bytes, init_decode_state, kv_groups, token_rows
    from llm_training_tpu.models.llama import LlamaConfig
    from llm_training_tpu.serve.paged_cache import init_paged_pool, init_window_pool, pool_bytes

    cfg = AfmoeConfig(**TINY)
    full, window = kv_groups(cfg)
    assert full == KVCacheSpec(3, 2, 16) and window == KVCacheSpec(9, 2, 16, window=16)
    assert token_rows(cfg) == (2, 3, 2, 16)
    k, v = init_paged_pool(cfg, num_blocks=17, block_size=8)
    wk, wv = init_window_pool(cfg, num_blocks=11, block_size=8)
    assert k.shape == v.shape == (3, 17, 2, 8, 16) and wk.shape == wv.shape == (9, 11, 2, 8, 16)
    state = init_decode_state(cfg, batch_size=2, max_length=40)
    assert state.k.shape == (3, 2, 40, 2, 16) and state.window_k.shape == (9, 2, 40, 2, 16)
    assert cache_bytes(state) == 2 * 12 * 2 * 40 * 2 * 16 * 4
    assert pool_bytes(wk, wv) == 2 * 9 * 11 * 2 * 8 * 16 * 4
    # every other family keeps its declaration: one group, whatever window it masks with
    llama = LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, sliding_window=8,
    )
    assert kv_groups(llama) == (KVCacheSpec(3, 2, 8), None)
    assert init_window_pool(llama, 5, 8) is None and init_decode_state(llama, 1, 8).window_k is None
    # the budget: window + one chunk, rounded up to pages, + 1; capped at the other group's
    assert window_page_budget(2048, 512, 16, 800) == 161
    assert window_page_budget(16, 8, 8, 8) == 4 and window_page_budget(16, 16, 8, 8) == 5
    assert window_page_budget(2048, 512, 16, 100) == 100
    # the published model: 8 layers that keep everything, 24 that keep 2,048
    full, window = AfmoeConfig().cache_specs()[0]
    assert (full.layers, full.window, window.layers, window.window) == (8, None, 24, 2048)


@pytest.mark.parametrize("seq", [1, 5], ids=["one_token", "chunk"])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_a_ring_table_reads_what_a_full_table_reads_inside_the_window(seq, impl):
    """The ONE rule, `page % width`: rows of 3, 21 and 44 cached tokens, a
    window of 16, pages of 8. Against a table 8 pages wide that holds every
    page, and against a ring 4 wide (5 for a chunk) that holds the window's
    pages only and names the trash block elsewhere, the append writes the same
    page and the attention reads the same keys, in the XLA gather and in the
    decode kernel alike."""
    from llm_training_tpu.ops.paged_attention import paged_cached_attention

    rng = np.random.default_rng(0)
    rows, heads, kv_heads, dim, page, window = 3, 4, 2, 16, 8, 16
    lengths = np.asarray([3, 21, 44])
    budget = window_page_budget(window, seq, page, 8)
    blocks = rng.permutation(np.arange(1, 25)).reshape(rows, 8)
    ring = np.zeros((rows, budget), np.int32)
    for row, length in enumerate(lengths):
        first = max(0, length - window + 1) // page
        pages = np.arange(first, (length + seq - 1) // page + 1)
        ring[row, pages % budget] = blocks[row, pages]
    pool = jnp.asarray(rng.normal(size=(2, 25, kv_heads, page, dim)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(rows, seq, heads, dim)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(rows, seq, kv_heads, dim)), jnp.float32) for _ in range(2))
    segment_ids = jnp.ones((rows, seq), jnp.int32)

    def attend(tables, **ring_kw):
        return jax.jit(lambda pool: paged_cached_attention(
            q, k, v, (pool[0], pool[1]), jnp.asarray(lengths, jnp.int32), jnp.asarray(tables, jnp.int32),
            segment_ids=segment_ids, sliding_window=window, impl=impl, **ring_kw))(pool)

    want, (want_k, want_v) = attend(blocks)
    got, (got_k, got_v) = attend(ring, ring=True)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    assert np.array_equal(np.asarray(got_k), np.asarray(want_k))
    assert np.array_equal(np.asarray(got_v), np.asarray(want_v))


def test_cli_model_provider_takes_the_family():
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.models.afmoe.hf_conversion import (
        config_from_hf,
        config_to_hf,
        params_from_hf,
    )
    from llm_training_tpu.models.hf_io import conversion_module, model_class_for_hf

    provider = ModelProvider(model_class="llm_training_tpu.models.Afmoe", model_kwargs=TINY)
    assert isinstance(provider.get_model(), Afmoe)
    assert model_class_for_hf({"model_type": "afmoe"}).endswith("Afmoe")
    published = json.loads((ROOT / "benchmarks/configs/trinity-mini-ep8.json").read_text())
    cfg = config_from_hf({**published, **published["reduced_from"]})
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.vocab_size) == (32, 128, 200192)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok, cfg.routed_scaling_factor) == (128, 8, 2.826)
    assert cfg.norm_topk_prob is True and cfg.n_group is None and cfg.version == 3
    assert cfg.scan_plan == (4, 7, 4)
    assert [cfg.layer_is_window(i) for i in range(8)] == [True, True, True, False] * 2
    back = config_to_hf(cfg)
    catalog_keys = set(published) - {
        "source", "initializer_range", "experts_first", "reduced_from", "deployment", "assumed",
        "reference", "stated_precision", "control_precision", "program", "check", "use_grouped_mm",
    }
    assert {k: back[k] for k in catalog_keys} == {
        k: {**published, **published["reduced_from"]}[k] for k in catalog_keys}
    # the cut configuration builds as the benchmark builds it
    from benchmarks import common

    cut = common.build_model(published).config
    assert (cut.num_hidden_layers, cut.num_experts, cut.num_experts_held, cut.vocab_size) == (16, 128, 16, 25024)
    assert cut.scan_plan == (4, 3, 4)
    assert conversion_module(cut).__name__.endswith("afmoe.hf_conversion")
    with pytest.raises(NotImplementedError, match="no HuggingFace weight map"):
        params_from_hf({}, cut)
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf({**published, "rope_scaling": {"type": "yarn", "factor": 4}})
