"""What PR 32 adds to the benchmark, on the CPU: the LongCat-Flash reference
against the program, a whole rehearsal of a tiny copy of
`longcat-serve-longctx` (sound, with the fp8 control, and with a fault planted
in the program's latent cache), the `mla_decode` cost against a hand count,
and the configuration's file against the catalog entry and the sizes it
states. (The benchmark's older test files are not edited by a `model_config`
PR, so these cases live here.)"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common
from benchmarks.costs import mla_decode
from benchmarks.references import longcat_flash
from benchmarks.run import run_cell
from conftest import REPO, make_root

TINY_LONGCAT = {
    "source": "test", "model_type": "longcat_flash", "attention_bias": False, "vocab_size": 256,
    "hidden_size": 64, "ffn_hidden_size": 96, "expert_ffn_hidden_size": 32, "num_layers": 2,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "qk_nope_head_dim": 16, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "routed_scaling_factor": 6, "n_routed_experts": 8, "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 32,
    "zero_expert_type": "identity", "moe_topk": 12, "initializer_range": 0.02, "experts_first": 4,
    "reduced_from": {"n_routed_experts": 64},
    "reference": "longcat_flash", "control_precision": "fp8",
    # read over 5 seeds: sound 0.0017 to 0.0039, fp8 0.026 to 0.115, the fault 0.24 to 0.50. (With 4 choices of
    # 24 router outputs, each weighing a quarter, one near-tie that falls the other way under bfloat16 moved
    # the sound reading to 0.22: the router here has the cell's 12 of 96, so a flip moves a sixteenth.)
    "check": {"served_logit_gap": 0.01},
    "program": {"model_class": "LongcatFlash", "model_kwargs": {
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "n_routed_experts": 64, "experts_held": 8,
        "experts_first": 4}},
}
TINY_TRAFFIC = {
    "kind": "serve_closed", "clients": 4,
    "engine": {"max_batch": 4, "prefill_chunk": 16, "max_model_len": 64, "block_size": 8},
    "prompt_lengths": [8, 32, 16, 24], "output_lengths": [4, 16, 8, 12, 10],
    "stagger_first_output": True, "eos": None,
}
CELL = "tiny-longcat-serve"


@pytest.fixture
def longcat_root(tmp_path):
    """The tiny checkout of conftest.py with one more configuration and cell,
    added as the real one is: a file, and entries at the ends of the lists."""
    root = make_root(tmp_path)
    (root / "benchmarks" / "configs" / "tiny-longcat.json").write_text(json.dumps(TINY_LONGCAT))
    (root / "benchmarks" / "traffic" / "tiny-longcat-closed.json").write_text(json.dumps(TINY_TRAFFIC))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-longcat", "source": "test", "file": "benchmarks/configs/tiny-longcat.json",
                             "reduced": [], "why": "tiny, for the CPU"})
    bench["workloads"].append({"name": CELL, "config": "tiny-longcat", "traffic": "tiny-longcat-closed", "chips": 1,
                               "why": "tiny, for the CPU"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if "longcat-serve-longctx" in metric.get("workloads", ()):
                metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_reference_logits_agree_with_the_module():
    cfg = {**TINY_LONGCAT, "program": {**TINY_LONGCAT["program"], "model_kwargs": {
        **TINY_LONGCAT["program"]["model_kwargs"], "param_dtype": "float32", "compute_dtype": "float32",
        "attention_impl": "xla"}}}
    model = common.build_model(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, size=(2, 48)).astype(np.int32)
    seg = np.tile(np.concatenate([np.full(20, 1), np.full(24, 2), np.zeros(4)]).astype(np.int32), (2, 1))
    pos = np.tile(np.concatenate([np.arange(20), np.arange(24), np.zeros(4)]).astype(np.int32), (2, 1))
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    # a wider draw than the runs' 0.02: the router and both scale factors all matter
    variables = nn.meta.unbox(jax.jit(lambda k: common.seeded_tree(k, abstract, 0.3))(common.base_key(7)))
    assert not np.asarray(variables["params"]["layers"]["layer"]["mlp"]["router"]["bias"]).any()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v: model.apply(
            v, input_ids=jnp.asarray(ids), segment_ids=jnp.asarray(seg), position_ids=jnp.asarray(pos)).logits)(variables)
    got = longcat_flash.logits(variables["params"], cfg, jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos))
    assert np.abs(np.asarray(got) - np.asarray(want))[seg > 0].max() < 1e-4


def test_the_cell_is_found_and_rehearsed_and_its_control_is_not_correct(longcat_root):
    cell = common.Cell(longcat_root, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert {"mla_decode_roofline_pct", "prefill_mla_device_ms", "decode_scmoe_device_ms",
            "moe_dispatch_device_ms", "decode_attn_device_ms", "compile_s"} <= set(names)
    assert not {"paged_decode_roofline_pct", "kda_decode_roofline_pct"} & set(names)
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["serve_tok_s", "itl_p95_ms", "setup_s"]
    for name in ("mla_decode_roofline_pct", "prefill_mla_device_ms", "decode_scmoe_device_ms"):
        assert callable(cell.module("layer_metrics", name).read)
    runner = cell.module("runners", "serve_closed")
    outcome = runner.run(cell, 3_000_000_037, 2.0, False, require_tpu=False)
    common.restore_host()
    assert outcome["correct"] is True and outcome["failed"] == 0 and outcome["attempted"] > 0
    limit = cell.config["check"]["served_logit_gap"]
    sound, control = outcome["readings"], outcome["control"]("fp8")
    assert sound["served_logit_gap"] <= limit < control["control_fp8"], (sound, control)


def test_a_decoded_token_that_never_reaches_its_page_is_not_correct(longcat_root, monkeypatch):
    """The planted fault: a decode step's latent row is not appended, so every
    later token of the row attends to a page that is stale at that slot."""
    from llm_training_tpu.ops import latent_attention as program

    append = program.latent_append
    monkeypatch.setattr(
        program, "latent_append",
        lambda pool, rows, *rest: pool if rows.shape[1] == 1 else append(pool, rows, *rest),
    )
    result = run_cell(longcat_root, CELL, 3_000_000_041, 2.0, False, require_tpu=False)
    common.restore_host()
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0


def test_the_pool_is_one_array_the_runner_can_drop(longcat_root):
    cell = common.Cell(longcat_root, CELL)
    runner = cell.module("runners", "serve_closed")
    _, engine = runner.build_engine(cell, 2**31 + 5)
    assert all(hasattr(engine, n) for n in runner.ENGINE_INTERNALS)
    assert engine._pool_v is None and engine._pool_k.shape == (4, 4 * 8 + 1, 1, 8, 128)
    jax.block_until_ready(engine._pool_k)
    engine._pool_k = engine._pool_v = None
    assert engine._pool_k is None


def test_mla_decode_cost_is_the_hand_count():
    # the cell: 32 rows of 4,096 live tokens, 64 heads, a row of 512 + 64 bfloat16 values
    one = mla_decode.cost(32 * 4096, 32, 64, 512, 64, 2)
    rows = 32 * 4096 * 576 * 2
    assert rows == 150_994_944  # 1,152 bytes a live token, read once
    queries, outputs = 32 * 64 * 576 * 2, 32 * 64 * 512 * 2
    assert one["bytes"] == rows + queries + outputs == 155_451_392
    assert one["flops"] == 32 * 4096 * 64 * 2 * (576 + 512) == 18_253_611_008
    # about 117 operations a byte: under the v5e's ridge (197e12 / 819e9 = 240), so bytes bind
    assert 110 < one["flops"] / one["bytes"] < 125
    # half the live tokens: half the cache bytes, the same queries
    assert mla_decode.cost(16 * 4096, 32, 64, 512, 64, 2)["bytes"] == rows // 2 + queries + outputs


def test_the_configuration_states_the_published_widths_and_its_cut():
    cfg = json.loads((REPO / "benchmarks/configs/longcat-flash-omni-ep32.json").read_text())
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "longcat-flash-omni-ep32")
    assert entry["reduced"] == list(cfg["reduced_from"]) == ["num_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == cfg["source"]
    assert (cfg["num_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (4, 16, 16384)
    assert cfg["reduced_from"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    # every number of the catalog's entry under the same key, but for the three cut
    catalog = {
        "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144, "ffn_hidden_size": 12288,
        "expert_ffn_hidden_size": 2048, "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
        "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
        "n_routed_experts": 512, "max_position_embeddings": 131072, "rms_norm_eps": 1e-05,
        "rope_theta": 10000000, "attention_method": "MLA", "zero_expert_num": 256,
        "zero_expert_type": "identity", "moe_topk": 12,
    }
    assert {k: {**cfg, **cfg["reduced_from"]}[k] for k in catalog} == catalog
    cell = next(w for w in bench["workloads"] if w["name"] == "longcat-serve-longctx")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("longcat-flash-omni-ep32", "serve-longctx-closed", 1)
    traffic = json.loads((REPO / "benchmarks/traffic/serve-longctx-closed.json").read_text())
    assert traffic["engine"] == {"max_batch": 32, "prefill_chunk": 512, "max_model_len": 5632, "block_size": 16}
    assert traffic["prompt_lengths"] == [4096, 2048, 5120, 3072] and traffic["clients"] == 32
    assert traffic["output_lengths"] == [128, 512, 256, 384, 320]
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) == traffic["engine"]["max_model_len"]

    model = common.build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    size = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(nn.meta.unbox(tree)))
    layer = nn.meta.unbox(shapes["layers"]["layer"])
    assert set(layer) == {"sub_0", "sub_1", "mlp"}  # one double layer, scanned four times
    assert layer["mlp"]["router"]["kernel"].shape == (4, 6144, 768)  # the router keeps its 768 outputs
    assert layer["mlp"]["experts_gate_proj"].shape == (4, 16, 6144, 2048)  # 16 of 512 held
    attn = layer["sub_0"]["self_attn"]
    assert attn["kv_a_proj_with_mqa"]["kernel"].shape == (4, 6144, 576)
    assert attn["kv_b_proj"].shape == (4, 512, 64, 256) and attn["q_b_proj"]["kernel"].shape == (4, 1536, 12288)
    mla = 6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 + 8192 * 6144 + 1536 + 512
    ffn = 3 * 6144 * 12288
    router, experts = 6144 * 768 + 768, 16 * 3 * 6144 * 2048
    assert size(layer) == 4 * (2 * (mla + ffn + 2 * 6144) + router + experts)
    assert size(shapes) == size(layer) + 2 * 16384 * 6144 + 6144
    assert 10.3e9 < 2 * size(shapes) < 10.4e9  # bytes in bfloat16: 65% of the chip
    # the latent pool: 8 MLA blocks, 32 requests of 5,632 tokens and the trash block, 640 values a row
    spec = model.config.cache_specs()[0]
    assert (spec.layers, spec.latent_dim, spec.rope_dim, spec.width) == (8, 512, 64, 640)
    assert 8 * (32 * 352 + 1) * 16 * 640 * 2 == 1_845_657_600
