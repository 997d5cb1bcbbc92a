"""openPangu-Ultra-MoE (`model_type: pangu_ultra_moe`) on the `Deepseek`
stack: the module against its plain reference with and without
`sandwich_norm`, the multi-token-prediction module's logits, the summed loss
and its gradients against the reference on packed rows, the expert share
against the uncut layer, `mla_decode` at 128 heads, planted faults, the
HuggingFace config and state dict. Serving through the latent pool is
`tests/test_deepseek_serve.py`. Float32 on the CPU unless a test says
otherwise.

Tolerances, with their reasons:
- float32 against float32 (`highest` products on both sides): 1e-4 on logits
  and log-probabilities of magnitude 1 to 10. The two sides sum in different
  orders (absorbed against expanded, pages a trip at a time with an online
  softmax against full [S, S] scores, a fused chunked cross entropy against
  a log-softmax).
- gradients: 2e-4 of the gradient's own largest entry, leaf by leaf (the
  same reordering, once more through the backward pass).
- bfloat16 compute against the float32 reference: at most `FAR_SHARE` of the
  served tokens may have their reference logit more than `FAR_LEVEL` below
  the reference's best. The fp8 control (the reference's own products rounded
  through e4m3) must have more than that, so the limit separates the stated
  precision from the next one down.
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
from llm_training_tpu.models.base import LatentCacheSpec
from llm_training_tpu.models.deepseek import Deepseek, DeepseekConfig, reference
from llm_training_tpu.models.deepseek.model import DeepseekMoE

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

F32_TOL = 1e-4
# Read here over 6 draws of the weights, of 79 served tokens: over 0.05, bfloat16 0 to 4 of them (0 to 0.051), the
# fp8 control 16 to 22 (0.203 to 0.278). The WIDEST gap does not separate them at this size (bfloat16 0.02 to 0.61, fp8
# 0.52 to 1.0): a router's near-tie that falls the other way moves a quarter of the normalised routed sum x 2.5 in
# or out of the share, one token's accident for either precision (PERF.md section 2, Solar's and Trinity's hazard)
FAR_LEVEL, FAR_SHARE = 0.05, 0.12

TINY = dict(
    version=3, vocab_size=256, hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
    qk_rope_head_dim=8, qk_nope_head_dim=16, v_head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
    n_shared_experts=1, routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=25600000.0,
    experts_held=8, experts_first=4, sandwich_norm=True, max_position_embeddings=128,
    param_dtype="float32", compute_dtype="float32", attention_impl="xla", moe_impl="dense",
)
# the same model as the reference's mapping (the source's keys)
REFERENCE_CFG = {
    "num_attention_heads": 4, "rms_norm_eps": 1e-5, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "kv_lora_rank": 32, "q_lora_rank": 48, "rope_theta": 25600000.0, "num_experts_per_tok": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True, "experts_first": 4, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "sandwich_norm": True,
}


def seeded_variables(model, scale=0.2, seed=1):
    """Random weights that exercise every term: a correction bias that moves
    the choice of experts, norm weights drawn around one (four norms a layer
    with weights of exactly one would hide a norm applied in the wrong place
    less well)."""
    variables = nn.meta.unbox(
        jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)))(jax.random.key(0))
    )

    def draw(path, leaf):
        name = path[-1].key
        # (crc32, not hash(): a str's hash differs from one process to the next)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(jax.tree_util.keystr(path).encode()))
        if name == "weight":
            return leaf + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        width = 0.01 if name == "e_score_correction_bias" else scale
        return (jax.random.normal(key, leaf.shape) * width).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(draw, variables)


@pytest.fixture(scope="module")
def tiny():
    model = Deepseek(DeepseekConfig(**TINY))
    return model, seeded_variables(model)


@pytest.fixture(scope="module")
def tiny_mtp():
    model = Deepseek(DeepseekConfig(**{**TINY, "num_nextn_predict_layers": 1}))
    return model, seeded_variables(model)


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


@pytest.mark.parametrize("sandwich", [True, False], ids=["sandwich_norm", "pre_norm"])
def test_module_logits_are_the_reference_logits(sandwich):
    """With the four norms (pangu_ultra_moe) and with DeepSeek-V3's two; the
    pre-norm tree has no `pre_mlp_layernorm` / `post_mlp_layernorm` at all."""
    model = Deepseek(DeepseekConfig(**{**TINY, "sandwich_norm": sandwich}))
    variables = seeded_variables(model)
    layer = variables["params"]["layers_0"]
    assert ("post_mlp_layernorm" in layer) is sandwich and ("pre_mlp_layernorm" in layer) is sandwich
    ids, seg, pos = packed_batch()
    got = module_logits(model, variables).logits
    want = reference.logits(variables["params"], {**REFERENCE_CFG, "sandwich_norm": sandwich}, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < F32_TOL
    assert np.abs(np.asarray(want)).max() > 1.0
    assert supports_decoding(model)


@pytest.mark.parametrize("fault", ["post_norms_left_out", "scale_dropped", "rotary_in_halves"])
def test_a_planted_fault_fails_the_comparison(tiny, fault, monkeypatch):
    """The reference with one thing wrong no longer gives the module's logits:
    the two norms after the attention and the MLP left out (DeepSeek's layer
    under this model's name), the router's 2.5 dropped, the rotary pairs laid
    out in halves."""
    model, variables = tiny
    ids, seg, pos = packed_batch()
    got = module_logits(model, variables).logits
    cfg = dict(REFERENCE_CFG)
    if fault == "post_norms_left_out":

        def layer(x, w, cfg, segment_ids, position_ids):
            eps = cfg["rms_norm_eps"]
            norm = lambda name, h: reference.rms_norm(h, w[name]["weight"], eps)
            mlp = reference.moe_block if "gate_kernel" in w["mlp"] else lambda u, m, _: reference.swiglu(u, m)
            h = x + reference.mla_block(norm("input_layernorm", x), w["self_attn"], cfg, segment_ids, position_ids)
            return h + mlp(norm("pre_mlp_layernorm", h), w["mlp"], cfg)

        monkeypatch.setattr(reference, "layer", layer)
    else:
        cfg.update({"scale_dropped": {"routed_scaling_factor": 1.0},
                    "rotary_in_halves": {"rope_interleave": False}}[fault])
    wrong = reference.logits(variables["params"], cfg, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(wrong))[real].max() > 100 * F32_TOL


def test_the_benchmarks_copy_of_the_reference_is_the_same(tiny_mtp):
    from benchmarks.references import pangu_ultra_moe as copy

    _, variables = tiny_mtp
    ids, seg, pos = packed_batch()
    want, want_ahead = reference.mtp_logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    got, got_ahead = copy.mtp_logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 2e-5
    _, ahead_valid = reference.targets(ids, seg, 2)
    assert np.abs(np.asarray(got_ahead) - np.asarray(want_ahead))[np.asarray(ahead_valid)].max() < 2e-5
    alone = copy.logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    assert np.array_equal(np.asarray(alone), np.asarray(got))


def test_looped_stack_is_the_scanned_stack(tiny):
    model, variables = tiny
    looped = Deepseek(DeepseekConfig(**{**TINY, "scan_layers": False}))
    stacked = variables["params"]["moe_layers"]["layer"]
    flat = {f"layers_{i + 1}": jax.tree.map(lambda a: a[i], stacked) for i in range(2)}
    loop_vars = {"params": {k: v for k, v in variables["params"].items() if k != "moe_layers"} | flat}
    want = module_logits(model, variables)
    got = module_logits(looped, loop_vars)
    assert np.abs(np.asarray(got.logits) - np.asarray(want.logits)).max() < F32_TOL
    assert np.array_equal(np.asarray(got.moe_assignments), np.asarray(want.moe_assignments))
    # 2 rows x 44 real tokens x 4 choices in each of the two MoE layers: held here, (no zero-compute), elsewhere
    counts = np.asarray(want.moe_assignments)
    assert counts[1] == 0 and counts.sum() == 2 * 44 * 4 * 2 and counts[0] > 0 and counts[2] > 0
    assert want.router_stats.layer_ids == (1, 2) and want.router_stats.sel_frac.shape == (2, 16)


# ------------------------------------------------- multi-token prediction


def test_mtp_logits_are_the_reference_logits(tiny_mtp):
    """Position i of the module's output is for the token at i + 2: compared
    wherever that token lies in i's own document (a row of two documents: 18
    and 22 such positions). A forward that does not ask for the module never
    runs it, and gives the logits it gives without one."""
    model, variables = tiny_mtp
    ids, seg, pos = packed_batch()
    out = module_logits(model, variables, return_mtp=True)
    want, want_ahead = reference.mtp_logits(variables["params"], REFERENCE_CFG, ids, seg, pos)
    real = np.asarray(seg) > 0
    _, valid = reference.targets(ids, seg, 2)
    valid = np.asarray(valid)
    assert valid.sum() == 2 * (18 + 22)
    assert np.abs(np.asarray(out.logits) - np.asarray(want))[real].max() < F32_TOL
    assert np.abs(np.asarray(out.mtp_logits) - np.asarray(want_ahead))[valid].max() < F32_TOL
    assert np.abs(np.asarray(out.mtp_logits) - np.asarray(out.logits))[valid].max() > 0.1
    plain = module_logits(model, variables)
    assert plain.mtp_logits is None and plain.mtp_hidden_states is None
    assert np.array_equal(np.asarray(plain.logits), np.asarray(out.logits))
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        Deepseek(DeepseekConfig(**TINY)).apply(
            {"params": {}}, input_ids=ids, return_mtp=True)
    with pytest.raises(ValueError, match="1 multi-token-prediction module"):
        DeepseekConfig(**{**TINY, "num_nextn_predict_layers": 2})


def test_clm_loss_and_gradients_are_the_references(tiny_mtp):
    """`loss = CE + 0.3 CE_mtp` through the fused cross entropy, on packed
    rows with a document boundary, and its gradient in every leaf, the
    module's and the shared embedding, norm and head among them."""
    from llm_training_tpu.lms.clm import CLM, CLMConfig

    model, variables = tiny_mtp
    ids, seg, pos = packed_batch()
    batch = {"input_ids": ids, "segment_ids": seg, "position_ids": pos}
    clm = CLM(CLMConfig(ce_chunk_size=16), model=model)

    def program(v):
        loss, metrics = clm.loss_and_metrics(v, batch, train=True)
        return loss, metrics

    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(jax.value_and_grad(program, has_aux=True)).lower(variables).compile()
        (loss, metrics), grads = compiled(variables)
    (want, (ce, ce_mtp)), want_grads = jax.value_and_grad(
        lambda p: reference.loss(p, REFERENCE_CFG, ids, seg, pos, mtp_weight=0.3), has_aux=True
    )(variables["params"])
    assert abs(float(metrics["mtp_loss"]) - float(ce_mtp)) < F32_TOL
    assert abs(float(loss) - float(want)) < F32_TOL and abs(float(metrics["loss"]) - float(want)) < F32_TOL
    assert abs(float(want) - float(ce) - 0.3 * float(ce_mtp)) < 1e-6 and float(ce_mtp) > 1.0
    assert abs(float(metrics["perplexity"]) - np.exp(float(ce))) < 1e-2  # of the main loss alone
    assert int(metrics["target_tokens"]) == 2 * (19 + 23)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, got in jax.tree_util.tree_leaves_with_path(grads["params"]):
        ref = np.asarray(flat_want[path])
        name = jax.tree_util.keystr(path)
        if name.endswith("['e_score_correction_bias']"):
            assert not np.asarray(got).any()  # the bias sees the choice only
            continue
        assert np.abs(ref).max() > 0, name
        assert np.abs(np.asarray(got) - ref).max() < 2e-4 * np.abs(ref).max(), name
    assert np.abs(np.asarray(grads["params"]["mtp_0"]["eh_proj"]["kernel"])).max() > 0
    # the scope the train step's readers would sort the second loss by (docs/observability.md)
    import re

    names = set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))
    assert any("/mtp/mtp_0/layer/self_attn/mla_attend" in n for n in names)
    assert any("/mtp/norm/rms_norm" in n for n in names) and any("/mtp/embed_tokens" in n for n in names)

    # a prediction across the boundary is none: with the second document's first two tokens changed,
    # the first document's losses stay what they were
    other = ids.at[:, 20:22].set((ids[:, 20:22] + 1) % 256)
    first_only = jnp.where(seg == 1, 1, 0)
    losses = [
        float(reference.loss(variables["params"], REFERENCE_CFG, tokens, first_only, pos)[0])
        for tokens in (ids, other)
    ]
    assert abs(losses[0] - losses[1]) < 1e-6
    first_loss = jax.jit(lambda v, t: clm.loss_and_metrics(
        v, {"input_ids": t, "segment_ids": first_only, "position_ids": pos})[0])
    with jax.default_matmul_precision("highest"):
        mine = [float(first_loss(variables, tokens)) for tokens in (ids, other)]
    assert abs(mine[0] - mine[1]) < 1e-6 and abs(mine[0] - losses[0]) < F32_TOL


# ------------------------------------------------------------------ the share


def moe_layer(**over):
    return DeepseekMoE(DeepseekConfig(**{
        **TINY, "n_routed_experts": 256, "num_experts_per_tok": 8, "experts_held": None, "experts_first": 0,
        **over,
    }), count_assignments=over.get("experts_held") is not None)


def moe_params(x, seed=3):
    params = nn.meta.unbox(jax.jit(moe_layer().init)(jax.random.key(seed), x))
    return jax.tree_util.tree_map_with_path(
        lambda p, a: jax.random.normal(
            jax.random.key(zlib.crc32(jax.tree_util.keystr(p).encode())), a.shape
        ) * (0.01 if p[-1].key == "e_score_correction_bias" else 0.2), params,
    )["params"]


SHARE_CFG = {**REFERENCE_CFG, "num_experts_per_tok": 8, "experts_first": 0}
EXPERTS = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")


@pytest.mark.parametrize("impl,shares", [("dense", 32), ("ragged", 8)])
def test_the_shares_with_the_shared_expert_counted_once_add_up_to_the_uncut_layer(impl, shares):
    """The published router: 8 of 256, normalised, times 2.5. 32 shares of 8
    experts (the deployment's; 8 of 32 through the grouped products): every
    share computes the shared expert in full, so the partial outputs hold it
    once a share; counted ONCE they are the uncut layer of the reference.
    The weights are normalised over all 8 chosen BEFORE a share drops what
    it does not hold: a share that normalised over its own would not add up."""
    held = 256 // shares
    x = jax.random.normal(jax.random.key(2), (2, 24, 64), jnp.float32)
    params = moe_params(x)
    with jax.default_matmul_precision("highest"):
        want = reference.moe_block(x, params, SHARE_CFG)
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
        alone = reference.moe_block(x, mine, {**SHARE_CFG, "experts_first": held * (shares - 1)})
        assert np.abs(np.asarray(out) - np.asarray(alone)).max() < F32_TOL
    assert np.abs(np.asarray(total + shared_term) - np.asarray(want)).max() < F32_TOL
    # (held here, zero-compute, held elsewhere) of each share's 48 x 8 assignments: over the shares
    # each assignment was held exactly once
    tally = np.stack(tally)
    assert (tally.sum(axis=1) == 48 * 8).all() and not tally[:, 1].any() and tally[:, 0].sum() == 48 * 8


# ---------------------------------------------------------------- the caches


def test_one_declaration_gives_the_latent_pool_and_the_dense_latent_buffer():
    from llm_training_tpu.infer.cache import cache_specs, init_decode_state, token_rows
    from llm_training_tpu.serve.paged_cache import init_paged_pool, init_state_slab

    cfg = DeepseekConfig(**TINY)
    latent, recurrent = cache_specs(cfg)
    # one block a layer, the dense prefix's and the MoE suffix's alike; none for an MTP module
    assert latent == LatentCacheSpec(layers=3, latent_dim=32, rope_dim=8) and recurrent is None
    assert cache_specs(DeepseekConfig(**{**TINY, "num_nextn_predict_layers": 1}))[0] == latent
    assert token_rows(cfg) == (1, 3, 1, 128)
    k, v = init_paged_pool(cfg, num_blocks=5, block_size=8)
    assert k.shape == (3, 5, 1, 8, 128) and v is None and init_state_slab(cfg, slots=3) is None
    dense = init_decode_state(cfg, batch_size=3, max_length=32)
    assert dense.k.shape == (3, 3, 32, 1, 128) and dense.v is None and dense.state is None
    with pytest.raises(ValueError, match="not among the 16 routed experts"):
        DeepseekConfig(**{**TINY, "experts_first": 9})


def test_mla_decode_at_128_heads_is_the_paged_attention():
    """The kernel's shape at this model's head count (a [128, trip] score
    tile), interpreted, against the XLA path in both forms, at the published
    latent and rotary widths; the page writer's pool against the XLA append's."""
    from llm_training_tpu.ops.latent_attention import paged_latent_attention

    rng = np.random.default_rng(0)
    rows, heads, nope, rope, latent, v, page, width = 3, 128, 128, 64, 512, 128, 16, 640
    pool = jnp.asarray(rng.normal(size=(9, 1, page, width)), jnp.float32).at[..., latent + rope:].set(0)
    tables = jnp.asarray(rng.permutation(np.arange(1, 9))[:6].reshape(rows, 2), jnp.int32)
    lengths = jnp.asarray([0, 13, 31], jnp.int32)
    q_nope = jnp.asarray(rng.normal(size=(rows, 1, heads, nope)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(rows, 1, heads, rope)), jnp.float32)
    row = jnp.pad(jnp.asarray(rng.normal(size=(rows, 1, latent + rope)), jnp.float32),
                  ((0, 0), (0, 0), (0, width - latent - rope)))
    w_kvb = jnp.asarray(rng.normal(size=(latent, heads, nope + v)) * 0.05, jnp.float32)
    seg = jnp.ones((rows, 1), jnp.int32)
    run = lambda **kw: jax.jit(lambda *a: paged_latent_attention(
        *a, segment_ids=seg, scale=192 ** -0.5, **kw))(q_nope, q_rope, row, w_kvb, pool, lengths, tables)
    with jax.default_matmul_precision("highest"):
        want, want_pool = run(impl="xla", absorbed=False)
        for kw in (dict(impl="xla", absorbed=True), dict(impl="pallas", absorbed=True)):
            got, got_pool = run(**kw)
            assert got.shape == (rows, 1, heads, v)
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5 * np.abs(np.asarray(want)).max()
            assert np.array_equal(np.asarray(got_pool), np.asarray(want_pool))
    assert np.abs(np.asarray(want)).max() > 0.1


# ------------------------------------------------------ the normal entry points


def test_cli_model_provider_and_hf_config_take_the_family():
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.models.deepseek.hf_conversion import config_from_hf, config_to_hf
    from llm_training_tpu.models.hf_io import model_class_for_hf

    provider = ModelProvider(model_class="llm_training_tpu.models.Deepseek", model_kwargs=TINY)
    assert isinstance(provider.get_model(), Deepseek)
    assert model_class_for_hf({"model_type": "pangu_ultra_moe"}).endswith("Deepseek")
    published = json.loads((ROOT / "benchmarks/configs/openpangu-ultra-moe-718b-ep32.json").read_text())
    cfg = config_from_hf({**published, **published["reduced_from"]})
    assert (cfg.version, cfg.sandwich_norm, cfg.num_nextn_predict_layers, cfg.n_group) == (3, True, 1, None)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace, cfg.n_routed_experts, cfg.vocab_size) == (
        61, 3, 256, 153600)
    assert (cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.routed_scaling_factor) == (
        128, 1536, 512, 2.5)
    assert abs(cfg.attention_scale - 192 ** -0.5) < 1e-12 and cfg.rope_theta == 25.6e6
    assert cfg.cache_specs()[0] == LatentCacheSpec(61, 512, 64)
    back = config_to_hf(cfg)
    assert back["model_type"] == "pangu_ultra_moe" and back["sandwich_norm"] is True
    catalog_keys = [
        k for k, v in published.items()
        if not isinstance(v, (dict, str)) and k not in ("initializer_range", "experts_first")
    ] + ["model_type", "hidden_act"]
    uncut = {**published, **published["reduced_from"]}
    assert all(back[k] == uncut[k] for k in catalog_keys), [k for k in catalog_keys if back[k] != uncut[k]]
    # a DeepSeek-V3 config names a module too; its layer was never loaded here, and is not now
    assert config_from_hf({**uncut, "model_type": "deepseek_v3"}).num_nextn_predict_layers == 0


def test_hf_state_dict_round_trips_the_four_norms_and_refuses_what_is_not_mapped(tiny):
    from llm_training_tpu.models.deepseek.hf_conversion import params_from_hf, params_to_hf

    model, variables = tiny
    whole = DeepseekConfig(**{**TINY, "experts_held": None, "experts_first": 0})
    full = seeded_variables(Deepseek(whole))
    state = params_to_hf(full, whole)
    assert {f"model.layers.{i}.{n}.weight" for i in range(3)
            for n in ("pre_mlp_layernorm", "post_mlp_layernorm", "post_attention_layernorm")} <= set(state)
    back = params_from_hf(state, whole)
    for (path, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(full["params"]), jax.tree_util.tree_leaves_with_path(back["params"])
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), jax.tree_util.keystr(path)
    with pytest.raises(NotImplementedError, match="a share of the experts"):
        params_to_hf(variables, model.config)
    with pytest.raises(NotImplementedError, match="multi-token-prediction layer"):
        params_from_hf(state, DeepseekConfig(**{**TINY, "experts_held": None, "experts_first": 0,
                                                "num_nextn_predict_layers": 1}))


