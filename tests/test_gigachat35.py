"""GigaChat 3.5 (`model_type: gigachat3_5`): the module against its plain
reference (logits on packed rows; the loss and its gradients with TWO chained
multi-token-prediction modules), planted faults, bfloat16 against the float32
tolerance, the expert share against the uncut layer, 32 key heads against an
explicit repeat to 64, the clamp binding, the norm's scale, the stack's scan
plan and cache declaration, the config through the normal entry points.
Serving through the latent pool beside the slab is
`tests/test_gigachat35_serve.py`. Float32 on the CPU unless a test says
otherwise.

Tolerances, with their reasons:
- float32 against float32 (`highest` products on both sides): 1e-4 on logits
  of magnitude 1 to 10. The two sides sum in different orders (the chunked
  delta rule against the token-by-token recurrence, a fused chunked cross
  entropy against a log-softmax, grouped against dense experts).
- gradients: 5e-4 of the gradient's own largest entry, leaf by leaf (the same
  reordering, once more through the backward pass of a triangular solve).
- bfloat16 in place of float32 parts from the reference by over a hundred
  times the first tolerance (`test_bfloat16_compute_fails_the_float32_tolerance`).
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
from llm_training_tpu.models.base import LatentCacheSpec, RecurrentCacheSpec
from llm_training_tpu.models.deepseek.model import DeepseekMLP, DeepseekMoE
from llm_training_tpu.models.gigachat35 import GigaChat35, GigaChat35Config, reference
from llm_training_tpu.models.gigachat35.model import ZeroCenteredGatedNorm
from llm_training_tpu.models.olmo_hybrid.model import GatedDeltaNet

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

F32_TOL = 1e-4
YARN = {"type": "yarn", "factor": 8, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 32}

# the five-layer cut's shape at a tiny size: layer 0 delta rule + dense, layer
# 1 MLA + experts, layers 2 to 4 delta rule + experts; a share of the experts
TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=5, first_k_dense_replace=1, full_attention_layers=[1], num_attention_heads=4,
    kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, experts_held=8, experts_first=4,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=16,
    num_nextn_predict_layers=0, rope_scaling=YARN, max_position_embeddings=256, delta_chunk_size=16,
    param_dtype="float32", compute_dtype="float32", attention_impl="xla", moe_impl="dense",
)
# the same model as the reference's mapping (the source's keys)
REFERENCE_CFG = {
    "num_hidden_layers": 5, "num_attention_heads": 4, "rms_norm_eps": 1e-6, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "kv_lora_rank": 32, "rope_theta": 100000.0, "rope_scaling": YARN,
    "use_mla_scaling_factor": True, "gated_attention": True, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "experts_first": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4, "linear_sigmoid_gate_scale": 2,
    "linear_attn_o_norm_eps": 1e-6, "layernorm_gating_weight": 2, "swiglu_limit": 10,
    "num_nextn_predict_layers": 0,
}


def seeded_variables(model, scale=0.2, seed=1):
    """Random weights that exercise every term: a correction bias that moves
    the choice of experts, norm weights drawn around zero (where the gated
    norm's scale is 1) wide enough that a norm in the wrong place shows, the
    decay's two vectors as the initialiser draws them."""
    variables = nn.meta.unbox(
        jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)))(jax.random.key(0))
    )

    def draw(path, leaf):
        name = path[-1].key
        # (crc32, not hash(): a str's hash differs from one process to the next)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(jax.tree_util.keystr(path).encode()))
        if name == "weight":
            return leaf + 0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)
        if name in ("A_log", "dt_bias"):
            return leaf
        width = 0.01 if name == "e_score_correction_bias" else scale
        return (jax.random.normal(key, leaf.shape) * width).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(draw, variables)


@pytest.fixture(scope="module")
def tiny():
    model = GigaChat35(GigaChat35Config(**TINY))
    return model, seeded_variables(model)


@pytest.fixture(scope="module")
def tiny_mtp():
    model = GigaChat35(GigaChat35Config(**{**TINY, "num_nextn_predict_layers": 2}))
    return model, seeded_variables(model)


MTP_CFG = {**REFERENCE_CFG, "num_nextn_predict_layers": 2}


def packed_batch(rows=2, vocab=256):
    """Two documents of 20 and 24 tokens and 4 of padding a row."""
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, size=(rows, 48)).astype(np.int32)
    seg = np.concatenate([np.full(20, 1), np.full(24, 2), np.zeros(4)]).astype(np.int32)
    pos = np.concatenate([np.arange(20), np.arange(24), np.zeros(4)]).astype(np.int32)
    return jnp.asarray(ids), jnp.asarray(np.tile(seg, (rows, 1))), jnp.asarray(np.tile(pos, (rows, 1)))


def module_logits(model, variables, **kw):
    ids, seg, pos = packed_batch()
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda v: model.apply(
            v, input_ids=ids, segment_ids=seg, position_ids=pos, **kw))(variables)


# --------------------------------------------------------- module, reference


def test_module_logits_are_the_reference_logits(tiny):
    """Packed rows: the chunked delta rule starts each document from a zero
    state and no convolution tap crosses the boundary, as the reference's
    token-by-token recurrence does."""
    model, variables = tiny
    layers = variables["params"]
    assert set(layers) == {"embed_tokens", "layers_0", "periods", "norm", "lm_head"}
    assert "linear_attn" in layers["layers_0"] and "gate_proj" in layers["layers_0"]["mlp"]
    assert "self_attn" in layers["periods"]["slot0"] and "gate_kernel" in layers["periods"]["slot0"]["mlp"]
    assert all("linear_attn" in layers["periods"][f"slot{j}"] for j in (1, 2, 3))
    ids, seg, pos = packed_batch()
    got = module_logits(model, variables).logits
    want = reference.logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < F32_TOL
    assert np.abs(np.asarray(want)).max() > 1.0
    assert supports_decoding(model)


FAULTS = {
    "attention_gate_left_out": {"gated_attention": False},
    "scale_without_yarn": {"use_mla_scaling_factor": False},
    "router_scale_dropped": {"routed_scaling_factor": 1.0},
    "delta_rule_output_norm_eps_one": {"linear_attn_o_norm_eps": 1.0},
    "clamp_at_a_tenth": {"swiglu_limit": 0.1},
    "norm_as_one_plus_w": {},
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_comparison(tiny, fault, monkeypatch):
    """The reference with one thing wrong no longer gives the module's logits:
    the MLA block's output gate left out, the softmax scale without yarn's
    squared mscale, the router's 2.5 dropped, the delta-rule block's output
    norm with an epsilon of 1 (the gate's own 2 cannot show: the norm after
    the mixer divides it out), a clamp that binds everywhere, the norm read the
    other way ((1 + w), Qwen3-Next's)."""
    model, variables = tiny
    ids, seg, pos = packed_batch()
    got = module_logits(model, variables).logits
    if fault == "norm_as_one_plus_w":
        monkeypatch.setattr(
            reference, "gated_norm", lambda x, w, eps, g: reference.rms_norm(x, 1.0 + w, eps)
        )
    wrong = reference.logits(variables["params"], {**REFERENCE_CFG, **FAULTS[fault]}, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(wrong))[real].max() > 100 * F32_TOL


def test_bfloat16_compute_fails_the_float32_tolerance(tiny):
    """The tolerance is tight enough that the next precision down does not pass it."""
    _, variables = tiny
    model = GigaChat35(GigaChat35Config(**{**TINY, "compute_dtype": "bfloat16"}))
    ids, seg, pos = packed_batch()
    got = module_logits(model, variables).logits
    want = reference.logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got, np.float32) - np.asarray(want))[real].max() > 100 * F32_TOL


def test_the_benchmarks_copy_of_the_reference_is_the_same(tiny):
    """The benchmark's copy computes a row at a time in blocks, one document a
    row (a serving request): rows of 37 and 48 tokens, the first padded."""
    from benchmarks.references import gigachat3_5 as copy

    _, variables = tiny
    ids, _, _ = packed_batch()
    seg = jnp.asarray(np.stack([np.r_[np.ones(37), np.zeros(11)], np.ones(48)]).astype(np.int32))
    want = reference.logits(variables["params"], REFERENCE_CFG, ids, seg)
    got = copy.logits(variables["params"], REFERENCE_CFG, ids, seg)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < F32_TOL
    hidden = copy.hidden_states(variables["params"], REFERENCE_CFG, ids, seg)
    assert np.array_equal(np.asarray(copy.head(variables["params"], hidden)), np.asarray(got))


def test_looped_stack_is_the_scanned_stack(tiny):
    model, variables = tiny
    looped = GigaChat35(GigaChat35Config(**{**TINY, "scan_layers": False}))
    assert looped.config.scan_plan == (5, 0, 0) and model.config.scan_plan == (1, 4, 1)
    stacked = variables["params"]["periods"]
    flat = {f"layers_{j + 1}": jax.tree.map(lambda a: a[0], stacked[f"slot{j}"]) for j in range(4)}
    loop_vars = {"params": {k: v for k, v in variables["params"].items() if k != "periods"} | flat}
    want = module_logits(model, variables)
    got = module_logits(looped, loop_vars)
    assert np.abs(np.asarray(got.logits) - np.asarray(want.logits)).max() < F32_TOL
    assert np.array_equal(np.asarray(got.moe_assignments), np.asarray(want.moe_assignments))
    # 2 rows x 44 real tokens x 4 choices in each of the four layers with experts
    counts = np.asarray(want.moe_assignments)
    assert counts[1] == 0 and counts.sum() == 2 * 44 * 4 * 4 and counts[0] > 0 and counts[2] > 0
    assert want.router_stats.layer_ids == (1, 2, 3, 4) and want.router_stats.sel_frac.shape == (4, 16)


def test_the_published_stack_scans_nine_periods_between_two_loops():
    """Forty layers go through the code the five-layer file goes through:
    three dense layers looped, nine periods [MLA, delta rule x 3] scanned,
    layer 39 (MLA) looped at the end."""
    published = GigaChat35Config()
    assert published.scan_plan == (3, 4, 9) and published.num_scanned_layers == 36
    assert [i for i, full in enumerate(published.layer_kinds) if full] == list(range(3, 40, 4))
    assert published.cache_specs() == (
        LatentCacheSpec(layers=10, latent_dim=512, rope_dim=64),
        RecurrentCacheSpec(layers=30, heads=64, key_dim=128, value_dim=128, conv_taps=3, conv_channels=16384),
    )
    assert abs(published.attention_scale - 192 ** -0.5 * (0.1 * np.log(8) + 1) ** 2) < 1e-12
    assert published.n_group is None and GigaChat35Config(n_group=1, topk_group=1).n_group is None


def test_the_five_layer_file_declares_one_latent_layer_and_four_slab_layers():
    from benchmarks import common
    from llm_training_tpu.infer.cache import cache_specs, init_decode_state, token_rows
    from llm_training_tpu.serve.paged_cache import init_paged_pool, init_state_slab

    file = json.loads((ROOT / "benchmarks/configs/gigachat3.5-432b-a28b-ep16.json").read_text())
    config = common.build_model(file).config
    latent, recurrent = cache_specs(config)
    assert latent == LatentCacheSpec(layers=1, latent_dim=512, rope_dim=64)
    assert recurrent == RecurrentCacheSpec(
        layers=4, heads=64, key_dim=128, value_dim=128, conv_taps=3, conv_channels=16384
    )
    assert recurrent.abreast == 1 and recurrent.stored == (64, 128, 128)
    assert config.scan_plan == (1, 4, 1) and config.layer_kinds == [False, True, False, False, False]
    assert (config.experts_held, config.n_routed_experts, config.num_nextn_predict_layers) == (16, 256, 0)
    # the tiny stack: ONE latent buffer beside the slab, in both cache kinds
    tiny = GigaChat35Config(**TINY)
    assert token_rows(tiny) == (1, 1, 1, 128)
    k, v = init_paged_pool(tiny, num_blocks=5, block_size=8)
    state, tail = init_state_slab(tiny, slots=3)
    assert k.shape == (1, 5, 1, 8, 128) and v is None
    assert state.shape == (4, 3, 4, 8, 16) and state.dtype == jnp.float32 and tail.shape == (4, 3, 3, 96)
    dense = init_decode_state(tiny, batch_size=3, max_length=32)
    assert dense.k.shape == (1, 3, 32, 1, 128) and dense.v is None and dense.state.shape == state.shape


# ------------------------------------------------- multi-token prediction


def test_two_chained_mtp_modules_are_the_references(tiny_mtp):
    """Module k's row i is for the token at i + k + 2: compared wherever that
    token lies in i's own document. A forward that does not ask for the
    modules never runs them."""
    model, variables = tiny_mtp
    assert {"mtp_0", "mtp_1"} <= set(variables["params"])
    assert "self_attn" in variables["params"]["mtp_1"]["layer"]  # an MLA layer with a dense SwiGLU
    assert "gate_proj" in variables["params"]["mtp_1"]["layer"]["mlp"]
    ids, seg, pos = packed_batch()
    out = module_logits(model, variables, return_mtp=True)
    want, want_ahead = reference.mtp_logits(variables["params"], MTP_CFG, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(out.logits) - np.asarray(want))[real].max() < F32_TOL
    assert len(out.mtp_logits) == len(out.mtp_hidden_states) == 2
    for k in range(2):
        _, valid = reference.targets(ids, seg, k + 2)
        valid = np.asarray(valid)
        assert valid.sum() == 2 * (18 - k + 22 - k)
        assert np.abs(np.asarray(out.mtp_logits[k]) - np.asarray(want_ahead[k]))[valid].max() < F32_TOL
    assert np.abs(np.asarray(out.mtp_logits[1]) - np.asarray(out.mtp_logits[0]))[valid].max() > 0.1
    plain = module_logits(model, variables)
    assert plain.mtp_logits is None and plain.mtp_hidden_states is None
    assert np.array_equal(np.asarray(plain.logits), np.asarray(out.logits))
    with pytest.raises(ValueError, match="multi-token-prediction module"):
        GigaChat35Config(**{**TINY, "num_nextn_predict_layers": 3})


def test_clm_loss_and_gradients_with_two_mtp_modules_are_the_references(tiny_mtp):
    """`loss = CE + 0.3 mean(CE_mtp0, CE_mtp1)` through the fused cross
    entropy, on packed rows with a document boundary, and its gradient in
    every leaf, both modules' and the shared embedding, norm and head."""
    from llm_training_tpu.lms.clm import CLM, CLMConfig

    model, variables = tiny_mtp
    ids, seg, pos = packed_batch()
    batch = {"input_ids": ids, "segment_ids": seg, "position_ids": pos}
    clm = CLM(CLMConfig(ce_chunk_size=16), model=model)

    def program(v):
        return clm.loss_and_metrics(v, batch, train=True)

    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.jit(jax.value_and_grad(program, has_aux=True))(variables)
    (want, (ce, ce_mtp)), want_grads = jax.value_and_grad(
        lambda p: reference.loss(p, MTP_CFG, ids, seg, pos, mtp_weight=0.3), has_aux=True
    )(variables["params"])
    assert len(ce_mtp) == 2 and abs(float(metrics["mtp_loss"]) - float(sum(ce_mtp)) / 2) < F32_TOL
    assert abs(float(loss) - float(want)) < F32_TOL and abs(float(metrics["loss"]) - float(want)) < F32_TOL
    assert abs(float(want) - float(ce) - 0.15 * float(sum(ce_mtp))) < 1e-5 and float(ce_mtp[1]) > 1.0
    assert int(metrics["target_tokens"]) == 2 * (19 + 23)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, got in jax.tree_util.tree_leaves_with_path(grads["params"]):
        ref = np.asarray(flat_want[path])
        name = jax.tree_util.keystr(path)
        if name.endswith("['e_score_correction_bias']"):
            assert not np.asarray(got).any()  # the bias sees the choice only
            continue
        assert np.abs(ref).max() > 0, name
        assert np.abs(np.asarray(got) - ref).max() < 5e-4 * np.abs(ref).max(), name
    assert np.abs(np.asarray(grads["params"]["mtp_1"]["eh_proj"]["kernel"])).max() > 0


# ------------------------------------------------------------------ the share


def moe_layer(**over):
    return DeepseekMoE(GigaChat35Config(**{
        **TINY, "n_routed_experts": 256, "num_experts_per_tok": 8, "experts_held": None, "experts_first": 0,
        **over,
    }), count_assignments=over.get("experts_held") is not None)


def moe_params(x, seed=3):
    params = nn.meta.unbox(jax.jit(moe_layer().init)(jax.random.key(seed), x))
    return jax.tree_util.tree_map_with_path(
        lambda p, a: jax.random.normal(
            jax.random.key(zlib.crc32(jax.tree_util.keystr(p).encode())), a.shape
        ) * (0.01 if p[-1].key == "e_score_correction_bias" else 0.5), params,
    )["params"]


SHARE_CFG = {**REFERENCE_CFG, "num_experts_per_tok": 8, "experts_first": 0}
EXPERTS = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")


@pytest.mark.parametrize("impl", ["dense", "ragged"])
def test_the_sixteen_shares_with_the_shared_expert_counted_once_add_up_to_the_uncut_layer(impl):
    """The published router: 8 of 256, normalised, times 2.5; every expert the
    clamped SwiGLU (the weights are drawn wide enough that the clamp binds in
    some). 16 shares of 16 experts, the deployment's: every share computes the
    shared expert in full, so the partial outputs hold it once a share;
    counted ONCE they are the uncut layer of the reference. The weights are
    normalised over all 8 chosen BEFORE a share drops what it does not hold."""
    shares, held = 16, 16
    x = 3.0 * jax.random.normal(jax.random.key(2), (2, 24, 64), jnp.float32)
    params = moe_params(x)
    with jax.default_matmul_precision("highest"):
        want = reference.moe_block(x, params, SHARE_CFG)
        unclamped = reference.moe_block(x, params, {**SHARE_CFG, "swiglu_limit": None})
        assert np.abs(np.asarray(want) - np.asarray(unclamped)).max() > 0.1  # the clamp binds
        none_held = {**params, **{n: params[n][:0] for n in EXPERTS}}
        shared_term = reference.moe_block(x, none_held, SHARE_CFG)  # no routed expert: the shared one alone
        assert np.abs(np.asarray(shared_term)).max() > 0.1
        total, tally = jnp.zeros_like(x), []
        for share in range(shares):
            part = moe_layer(experts_held=held, experts_first=held * share, moe_impl=impl)
            mine = {**params, **{n: params[n][held * share: held * (share + 1)] for n in EXPERTS}}
            out, (sel_frac, _, dropped), counts = jax.jit(part.apply)({"params": mine}, x)
            assert sel_frac.shape == (256,) and float(dropped) == 0.0  # the router keeps all 256 outputs
            total = total + (out - shared_term)
            tally.append(np.asarray(counts))
    scale = np.abs(np.asarray(want)).max()
    assert np.abs(np.asarray(total + shared_term) - np.asarray(want)).max() < F32_TOL * max(scale, 1.0)
    tally = np.stack(tally)
    assert (tally.sum(axis=1) == 48 * 8).all() and not tally[:, 1].any() and tally[:, 0].sum() == 48 * 8


# ------------------------------------------------------------- the new pieces


def test_the_clamp_binds_where_the_inputs_are_large_and_only_there():
    """`silu(min(g, L)) * clip(u, -L, L)`: with inputs scaled so that both
    branches pass the limit the clamped MLP parts from the unclamped one and
    is the reference's; with small inputs the two are the same."""
    cfg = GigaChat35Config(**{**TINY, "swiglu_limit": 2.0})
    free = GigaChat35Config(**{**TINY, "swiglu_limit": None})
    x = jax.random.normal(jax.random.key(0), (2, 8, 64), jnp.float32)
    mlp = DeepseekMLP(cfg, 96)
    params = jax.tree.map(
        lambda a: 0.5 * jax.random.normal(jax.random.key(a.size), a.shape), nn.meta.unbox(mlp.init(jax.random.key(1), x))
    )
    run = lambda module, x: jax.jit(module.apply)(params, x)
    with jax.default_matmul_precision("highest"):
        big, small = 4.0 * x, 0.01 * x
        gate, up = (np.asarray(big @ params["params"][n]["kernel"]) for n in ("gate_proj", "up_proj"))
        assert (gate > 2.0).any() and (up > 2.0).any() and (up < -2.0).any()
        want = reference.swiglu(big, params["params"], 2.0)
        assert np.abs(np.asarray(run(mlp, big)) - np.asarray(want)).max() < F32_TOL
        assert np.abs(np.asarray(run(mlp, big)) - np.asarray(run(DeepseekMLP(free, 96), big))).max() > 0.1
        assert np.array_equal(np.asarray(run(mlp, small)), np.asarray(run(DeepseekMLP(free, 96), small)))


def test_the_norms_scale_is_one_at_zero_and_under_two_at_three():
    """`N(x) = x / rms(x) * 2 sigmoid(w)`: the scale is 1 at `w` = 0 (a plain
    RMS normalisation), 2 sigmoid(3) = 1.905 at `w` = 3, never 2."""
    norm = ZeroCenteredGatedNorm(1e-6, 2.0, jnp.float32)
    x = jax.random.normal(jax.random.key(0), (3, 64), jnp.float32) * 5.0
    params = nn.meta.unbox(norm.init(jax.random.key(1), x))
    assert not np.asarray(params["params"]["weight"]).any()  # learned from 0
    plain = np.asarray(x) / np.sqrt(np.mean(np.square(np.asarray(x)), axis=-1, keepdims=True) + 1e-6)
    assert np.abs(np.asarray(norm.apply(params, x)) - plain).max() < 1e-5
    at_three = norm.apply({"params": {"weight": jnp.full((64,), 3.0)}}, x)
    assert np.abs(np.asarray(at_three) - plain * 2 / (1 + np.exp(-3.0))).max() < 1e-5
    assert np.abs(np.asarray(at_three) / plain).max() < 2.0


def test_thirty_two_key_heads_are_an_explicit_repeat_to_sixty_four():
    """Half as many key heads as value heads: the mixer with 2 key heads and 4
    value heads is the mixer with 4 and 4 whose q and k projections and
    convolution taps are the narrow one's, each key head's repeated (key head
    j serves value heads 2j and 2j + 1); training (chunked) and one decoded
    token on a state (the step), whose slab keeps one state a VALUE head."""
    narrow = GigaChat35Config(**TINY)
    wide = GigaChat35Config(**{**TINY, "linear_num_key_heads": 4})
    make = lambda cfg: GatedDeltaNet(cfg, joint=True, beta_max=1.0)
    x = jax.random.normal(jax.random.key(0), (2, 24, 64), jnp.float32)
    params = nn.meta.unbox(jax.jit(make(narrow).init)(jax.random.key(1), x))["params"]
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: a if p[-1].key in ("A_log", "dt_bias") else 0.3 * jax.random.normal(
            jax.random.key(zlib.crc32(jax.tree_util.keystr(p).encode())), a.shape), params)

    def widen(kernel):  # [..., q 2x8 | k 2x8 | v 4x16] -> [..., q 4x8 | k 4x8 | v]
        q, k, v = jnp.split(kernel, (16, 32), axis=-1)
        twice = lambda a: jnp.repeat(a.reshape(*a.shape[:-1], 2, 8), 2, axis=-2).reshape(*a.shape[:-1], 32)
        return jnp.concatenate([twice(q), twice(k), v], axis=-1)

    repeated = {**params, "qkv_proj": {"kernel": widen(params["qkv_proj"]["kernel"])},
                "conv_kernel": widen(params["conv_kernel"])}
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(make(narrow).apply)({"params": params}, x)
        want, _ = jax.jit(make(wide).apply)({"params": repeated}, x)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5 and np.abs(np.asarray(want)).max() > 0.01
        rows = lambda channels: (
            jax.random.normal(jax.random.key(5), (2, 4, 8, 16), jnp.float32),
            jax.random.normal(jax.random.key(6), (2, 3, channels), jnp.float32),
        )
        widen_tail = lambda r: (r[0], widen(r[1]))
        got, (state, tail) = jax.jit(make(narrow).apply)({"params": params}, x[:, :1], None, rows(96))
        want, (want_state, _) = jax.jit(make(wide).apply)({"params": repeated}, x[:, :1], None, widen_tail(rows(96)))
        assert state.shape == (2, 4, 8, 16) and tail.shape == (2, 3, 96)
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
        assert np.abs(np.asarray(state) - np.asarray(want_state)).max() < 1e-5


# ------------------------------------------------------ the normal entry points


def test_cli_model_provider_and_hf_config_take_the_family():
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.models.gigachat35.hf_conversion import (
        config_from_hf, config_to_hf, params_from_hf, params_to_hf,
    )
    from llm_training_tpu.models.hf_io import model_class_for_hf

    provider = ModelProvider(model_class="llm_training_tpu.models.GigaChat35", model_kwargs=TINY)
    assert isinstance(provider.get_model(), GigaChat35)
    assert model_class_for_hf({"model_type": "gigachat3_5"}).endswith("GigaChat35")
    published = json.loads((ROOT / "benchmarks/configs/gigachat3.5-432b-a28b-ep16.json").read_text())
    uncut = {**published, **published["reduced_from"]}
    cfg = config_from_hf(uncut)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.n_routed_experts, cfg.vocab_size) == (
        40, 3, 256, 128256)
    assert (cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.routed_scaling_factor) == (
        64, 1536, 512, 2.5)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.num_nextn_predict_layers) == (32, 64, 2)
    # the class's defaults ARE the published config
    assert cfg == GigaChat35Config(full_attention_layers=list(range(3, 40, 4)))
    assert cfg.layer_kinds == GigaChat35Config().layer_kinds
    back = config_to_hf(cfg)
    catalog_keys = [
        k for k, v in published.items()
        if not isinstance(v, (dict, str)) and k not in (
            "initializer_range", "experts_first", "layer_types", "qk_head_dim", "num_key_value_heads",
            "tf_legacy_loss", "n_group", "topk_group")
    ] + ["model_type", "hidden_act", "norm_type", "layernorm_type", "linear_gating_type", "rope_scaling"]
    assert all(back[k] == uncut[k] for k in catalog_keys), [k for k in catalog_keys if back[k] != uncut[k]]
    with pytest.raises(NotImplementedError, match="norm_type"):
        config_from_hf({**uncut, "norm_type": "RMSNorm"})
    with pytest.raises(NotImplementedError, match="no HuggingFace weight map"):
        params_from_hf({}, cfg)
    with pytest.raises(NotImplementedError, match="no HuggingFace weight map"):
        params_to_hf({}, cfg)
