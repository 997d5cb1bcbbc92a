"""What PR 27 adds to the benchmark, on the CPU: the Solar-Open2 reference
against the program, a whole rehearsal of a tiny copy of `solar2-serve-longdoc`
(sound, with the fp8 control, and with a fault planted in the program), the
KDA decode cost against a hand count, and the configuration's file against
the sizes it states. (The benchmark's older test files are not edited by a
`model_config` PR, so these cases live here.)"""

import json

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common
from benchmarks.costs import kda_decode
from benchmarks.references import solar_open2
from benchmarks.run import run_cell
from conftest import REPO, TINY, make_root

TINY_SOLAR = {
    "source": "test", "model_type": "solar_open2", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 8,
    "vocab_size": 256, "max_position_embeddings": 4096, "rms_norm_eps": 1e-05, "use_rope": False,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
    "gqa_interval": 3, "gqa_layers": [0, 4, 8], "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "routed_scaling_factor": 1, "first_k_dense_replace": 0,
    "tie_word_embeddings": False, "initializer_range": 0.02, "experts_first": 4,
    "reduced_from": {"n_routed_experts": 16},
    "reference": "solar_open2", "control_precision": "fp8", "check": {"served_logit_gap": 0.012},  # read: sound 0.0020 to 0.0044, fp8 0.033 to 0.058, the fault 0.23 to 0.31
    "program": {"model_class": "SolarOpen2", "model_kwargs": {
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "n_routed_experts": 16, "experts_held": 8,
        "experts_first": 4, "linear_num_heads": 4, "linear_head_dim": 16, "linear_conv_kernel_dim": 4}},
}
# prompts of a few tokens: the initialiser draws `A_log` and `dt_bias` near 0, so a
# KDA state forgets half of itself a token, and what a slot's last tenant left
# is gone from the served positions of a long prompt (PERF.md, section 7)
TINY_TRAFFIC = {
    "kind": "serve_closed", "clients": 4,
    "engine": {"max_batch": 4, "prefill_chunk": 4, "max_model_len": 32, "block_size": 8},
    "prompt_lengths": [2, 6, 3, 5], "output_lengths": [4, 16, 8, 12, 10],
    "stagger_first_output": True, "eos": None,
}
CELL = "tiny-solar-serve"


@pytest.fixture
def solar_root(tmp_path):
    """The tiny checkout of conftest.py with one more configuration and cell,
    added as the real one is: a file, and entries at the ends of the lists."""
    root = make_root(tmp_path)
    (root / "benchmarks" / "configs" / "tiny-solar.json").write_text(json.dumps(TINY_SOLAR))
    (root / "benchmarks" / "traffic" / "tiny-solar-closed.json").write_text(json.dumps(TINY_TRAFFIC))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-solar", "source": "test", "file": "benchmarks/configs/tiny-solar.json",
                             "reduced": [], "why": "tiny, for the CPU"})
    bench["workloads"].append({"name": CELL, "config": "tiny-solar", "traffic": "tiny-solar-closed", "chips": 1,
                               "why": "tiny, for the CPU"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if "solar2-serve-longdoc" in metric.get("workloads", ()):
                metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_reference_logits_agree_with_the_module():
    cfg = {**TINY_SOLAR, "program": {**TINY_SOLAR["program"], "model_kwargs": {
        **TINY_SOLAR["program"]["model_kwargs"], "param_dtype": "float32", "compute_dtype": "float32",
        "attention_impl": "xla"}}}
    model = common.build_model(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, size=(2, 48)).astype(np.int32)
    seg = np.tile(np.concatenate([np.full(20, 1), np.full(24, 2), np.zeros(4)]).astype(np.int32), (2, 1))
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    # a wider draw than the runs' 0.02: the conv, the decays and the router all matter
    variables = nn.meta.unbox(jax.jit(lambda k: common.seeded_tree(k, abstract, 0.3))(common.base_key(7)))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v: model.apply(v, input_ids=jnp.asarray(ids), segment_ids=jnp.asarray(seg)).logits)(variables)
    got = solar_open2.logits(variables["params"], cfg, jnp.asarray(ids), jnp.asarray(seg), None)
    assert np.abs(np.asarray(got) - np.asarray(want))[seg > 0].max() < 1e-4


def test_the_cell_is_found_and_rehearsed_and_its_control_is_not_correct(solar_root):
    cell = common.Cell(solar_root, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert {"decode_linear_attn_device_ms", "kda_decode_roofline_pct", "prefill_linear_attn_device_ms",
            "paged_decode_roofline_pct", "moe_dispatch_device_ms", "compile_s"} <= set(names)
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["serve_tok_s", "itl_p95_ms", "setup_s"]
    runner = cell.module("runners", "serve_closed")
    outcome = runner.run(cell, 3_000_000_037, 2.0, False, require_tpu=False)
    common.restore_host()
    assert outcome["correct"] is True and outcome["failed"] == 0 and outcome["attempted"] > 0
    limit = cell.config["check"]["served_logit_gap"]
    sound, control = outcome["readings"], outcome["control"]("fp8")
    assert sound["served_logit_gap"] <= limit < control["control_fp8"], (sound, control)


def test_a_state_not_reset_on_admission_is_not_correct(solar_root, monkeypatch):
    """The planted fault: a recycled decode slot's KDA state and conv tail are
    read as its last tenant left them."""
    from llm_training_tpu.models.solar_open2 import model as program

    monkeypatch.setattr(program, "_slot_rows", lambda slab, slots, fresh: slab if slots is None else slab[slots])
    result = run_cell(solar_root, CELL, 3_000_000_041, 2.0, False, require_tpu=False)
    common.restore_host()
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0


def test_the_slab_dies_with_the_pool(solar_root):
    """The runner drops the pool by setting it to None and cannot free what
    else the engine holds (its own wrappers keep the engine alive)."""
    cell = common.Cell(solar_root, CELL)
    runner = cell.module("runners", "serve_closed")
    _, engine = runner.build_engine(cell, 5)
    assert engine._slab is not None and all(hasattr(engine, n) for n in runner.ENGINE_INTERNALS)
    engine._pool_k = engine._pool_v = None
    assert engine._slab is None


def test_kda_decode_cost_is_the_hand_count():
    # the cell: 32 rows, 64 heads, a [128, 128] float32 state a head
    one = kda_decode.cost(32, 64, 128, 128)
    state = 32 * 64 * 128 * 128 * 4
    assert state == 134_217_728
    vectors = 32 * 64 * (128 + 128 + 128 + 128 + 128 + 1) * 4  # q, k, log alpha, v, out, beta
    assert one["bytes"] == 2 * state + vectors == 273_686_528
    assert one["flops"] == 32 * 64 * 128 * 128 * 7 == 234_881_024
    # half the rows idle: half the work
    assert kda_decode.cost(16, 64, 128, 128)["bytes"] * 2 == one["bytes"]


def test_the_configuration_states_the_published_widths_and_its_cut():
    cfg = json.loads((REPO / "benchmarks/configs/solar-open2-250b-ep8.json").read_text())
    entry = next(c for c in json.loads((REPO / "BENCHMARK.json").read_text())["configs"]
                 if c["name"] == "solar-open2-250b-ep8")
    assert entry["reduced"] == list(cfg["reduced_from"]) == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (4, 40, 24576)
    assert cfg["reduced_from"] == {"num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608}
    model = common.build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    size = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(nn.meta.unbox(tree)))
    layers = shapes["layers"]
    assert set(layers) == {"slot0", "slot1", "slot2", "slot3"}  # one period, scanned once
    assert "self_attn" in layers["slot0"] and all("linear_attn" in layers[f"slot{j}"] for j in (1, 2, 3))
    mlp = nn.meta.unbox(layers["slot1"]["mlp"])
    assert mlp["gate_kernel"].shape == (1, 4096, 320)  # the router keeps its 320 outputs
    assert mlp["experts_gate_proj"].shape == (1, 40, 4096, 1280)  # 40 held, width 1280
    assert mlp["shared_experts"]["gate_proj"]["kernel"].shape == (1, 4096, 1280)
    experts = 40 * 3 * 4096 * 1280
    kda = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 + 4 * 3 * 8192 + 64 + 8192 + 128
    gqa = 2 * 4096 * 8192 + 2 * 4096 * 1024 + 4096 * 8192  # q, o; k, v; the gate
    rest = 4096 * 320 + 320 + 3 * 4096 * 1280 + 2 * 4096  # router, bias, shared expert, two norms
    assert size(layers["slot1"]) == experts + kda + rest and size(layers["slot0"]) == experts + gqa + rest
    assert size(shapes) == 4 * (experts + rest) + gqa + 3 * kda + 2 * 24576 * 4096 + 4096
    assert 6.6e9 < 2 * size(shapes) < 6.8e9  # bytes in bfloat16: 41% of the chip
    assert TINY["traffic"]["tiny-closed"]["kind"] == json.loads(
        (REPO / "benchmarks/traffic/serve-longdoc-closed.json").read_text())["kind"]
