"""What PR 49 adds to the benchmark, on the CPU: the configuration's file
against the published widths and the cut it states, the reference's copy
against the family's on one input and against a hand-written two-token
recurrence, a whole rehearsal of a tiny copy of
`gigachat35-serve-longgen-doctail` through `serve_closed_blocked` (sound, with
the fp8 control, and with each of four faults planted in the program), that
the cell, its traffic and each reader that lists it ARE LISTED, and the new
reader on a hand-made trace and on a program without its scope. (The
benchmark's older test files are not edited by a `model_config` PR, so these
cases live here.)"""

import copy
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, span_reduce
from benchmarks.costs import gdn_decode
from benchmarks.references import gigachat3_5
from benchmarks.run import run_cell
from conftest import REPO, make_root

LAYER_METRICS = REPO / "benchmarks" / "layer_metrics"
REAL_CELL, REAL_CONFIG, REAL_TRAFFIC = (
    "gigachat35-serve-longgen-doctail", "gigachat3.5-432b-a28b-ep16", "serve-longgen-doctail-closed",
)
YARN = {"type": "yarn", "factor": 8, "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16}
# the five-layer cut's shape: layer 0 delta rule + dense, layer 1 MLA + experts, layers 2 to 4 delta rule + experts
TINY_GIGA = {
    "source": "test", "model_type": "gigachat3_5", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 5, "first_k_dense_replace": 1, "full_attention_layers": [1],
    "layer_types": ["linear_attention", "full_attention"] + ["linear_attention"] * 3,
    "num_attention_heads": 4, "kv_lora_rank": 32, "q_lora_rank": 48, "qk_rope_head_dim": 8, "qk_nope_head_dim": 16,
    "v_head_dim": 16, "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5, "rope_interleave": True,
    "rope_theta": 100000, "rope_scaling": YARN, "rms_norm_eps": 1e-06, "max_position_embeddings": 4096,
    "layernorm_gating_weight": 2, "gated_attention": True, "use_mla_scaling_factor": True, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8, "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06, "swiglu_limit": 10, "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "initializer_range": 0.05, "experts_first": 4,
    "initializer_scales": {"e_score_correction_bias": {"value": 0.0}},  # the real file's: every seed routes alike
    "reference": "gigachat3_5", "control_precision": "fp8",
    # read here (two seeds, 1 s windows): the widest gap sound 1.33 and 1.56, the fp8 control 1.96 and 2.21: it does NOT
    # separate them (a router's near-tie, one token's accident: the real cell's hazard), so its limit is for the sound
    # runs alone. The share of the tokens over 0.1: sound 0.056 and 0.057, the control 0.51 and 0.52, the four faults
    # below 0.6 to 0.94: the limit stands three times over the sound and three times under the control
    "check": {"served_logit_gap": 4.0, "far_level": 0.1, "far_token_share": 0.17},
    "program": {"model_class": "GigaChat35", "model_kwargs": {
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "n_routed_experts": 16, "experts_held": 8,
        "experts_first": 4, "rope_scaling": YARN, "delta_chunk_size": 4}},
}
TINY_TRAFFIC = {
    "kind": "serve_closed_blocked", "clients": 4,
    "engine": {"max_batch": 4, "prefill_chunk": 4, "max_model_len": 32, "block_size": 8},
    "prompt_lengths": [2, 6, 3, 14], "output_lengths": [4, 16, 8, 12, 10],
    "stagger_first_output": True, "eos": None,
}
CELL = "tiny-gigachat35-serve"


@pytest.fixture
def giga_root(tmp_path):
    """The tiny checkout of conftest.py with one more configuration and cell,
    added as the real one is: a file, and entries at the ends of the lists."""
    root = make_root(tmp_path)
    (root / "benchmarks" / "configs" / "tiny-gigachat35.json").write_text(json.dumps(TINY_GIGA))
    (root / "benchmarks" / "traffic" / "tiny-gigachat35-closed.json").write_text(json.dumps(TINY_TRAFFIC))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-gigachat35", "source": "test", "file": "benchmarks/configs/tiny-gigachat35.json",
                             "reduced": [], "why": "tiny, for the CPU"})
    bench["workloads"].append({"name": CELL, "config": "tiny-gigachat35", "traffic": "tiny-gigachat35-closed", "chips": 1,
                               "why": "tiny, for the CPU"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if REAL_CELL in metric.get("workloads", ()):
                metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


# ------------------------------------------------------------ the configuration


def test_the_configuration_states_the_published_widths_and_its_cut():
    import flax.linen as nn

    cfg = json.loads((REPO / f"benchmarks/configs/{REAL_CONFIG}.json").read_text())
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == REAL_CONFIG)
    assert entry["source"] == cfg["source"] == "https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace", "full_attention_layers",
                                "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    assert len(entry["why"]) <= 200 and entry["file"] == f"benchmarks/configs/{REAL_CONFIG}.json"
    published = {  # the catalog's row, every key but the six that are cut
        "max_position_embeddings": 262144, "hidden_size": 7168, "intermediate_size": 18432,
        "moe_intermediate_size": 2048, "nextn_is_sparse": False, "num_attention_heads": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "qk_nope_head_dim": 128, "qk_head_dim": 192, "n_group": 1, "topk_group": 1,
        "num_experts_per_tok": 8, "norm_topk_prob": True, "rope_interleave": True, "num_key_value_heads": 64,
        "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 100000, "attention_bias": False,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 32768, "type": "yarn"},
        "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
        "gated_attention": True, "use_shared_expert_sigmoid": False, "use_mla_scaling_factor": True,
        "linear_attention_type": "GigaChat35GatedDeltaNet", "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32, "linear_num_value_heads": 64,
        "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered", "linear_sigmoid_gate_scale": 2,
        "linear_attn_o_norm_eps": 1e-06, "swiglu_limit": 10, "tie_word_embeddings": False,
        "model_type": "gigachat3_5", "tf_legacy_loss": False,
    }
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": (5, 40), "first_k_dense_replace": (1, 3), "n_routed_experts": (16, 256),
           "vocab_size": (16032, 128256), "num_nextn_predict_layers": (0, 2),
           "full_attention_layers": ([1], list(range(3, 40, 4)))}
    assert {k: (cfg[k], cfg["reduced_from"][k]) for k in cut} == cut and set(cfg["reduced_from"]) == set(entry["reduced"])
    assert cfg["vocab_size"] * 8 == 128256 and cfg["n_routed_experts"] * 16 == 256
    # the derived list the accepted readers read, and the floors of a cut: a whole period after the dense layer
    assert cfg["layer_types"] == ["linear_attention", "full_attention"] + ["linear_attention"] * 3
    assert [i for i, kind in enumerate(cfg["layer_types"]) if kind == "full_attention"] == cfg["full_attention_layers"]
    for key in ("deployment", "arithmetic", "check", "stated_precision", "control_precision", "reference"):
        assert cfg[key]
    assert "16 that share each layer" in cfg["deployment"] and "8 pipeline stages" in cfg["deployment"]
    assert "4 : 1" in cfg["deployment"] and "3 : 1" in cfg["deployment"]
    assert {"norm", "gated_attention", "delta-rule output", "beta and decay", "swiglu_limit", "mtp wiring",
            "initializer_range", "initializer_scales"} <= set(cfg["assumed"])
    # the correction bias at zero on every seed: drawn, it decides which experts are popular, and the seed with it
    assert cfg["initializer_scales"] == {"e_score_correction_bias": {"value": 0.0}}
    # the program the file builds: its share, its caches, and the parameters the file's arithmetic states
    model = common.build_model(cfg)
    config = model.config
    assert (config.n_routed_experts, config.experts_held, config.experts_first, config.n_group) == (256, 16, 0, None)
    latent, slab = config.cache_specs()
    assert (latent.layers, latent.width, slab.layers, slab.stored, slab.conv_channels) == (1, 640, 4, (64, 128, 128), 16384)
    abstract = nn.meta.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(abstract))
    assert count == 4_731_722_752 and "4,731,722,752 parameters" in cfg["arithmetic"]
    held = abstract["params"]["periods"]["slot2"]["mlp"]["experts_gate_proj"]
    assert held.shape == (1, 16, 7168, 2048) and held.dtype == jnp.bfloat16
    # slab 1.074 GB, tails 0.025 GB, latent pool 0.839 GB, as the file reckons them
    assert 4 * 64 * 64 * 128 * 128 * 4 == 1_073_741_824 and 4 * 64 * 3 * 16384 * 2 == 25_165_824
    assert (64 * 640 + 1) * 16 * 640 * 2 == 838_881_280


def test_the_cell_its_traffic_and_every_reader_that_lists_it_are_listed():
    """LISTED, wherever in their lists: a later PR's entries go after these."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (REAL_CONFIG, REAL_TRAFFIC, 1) and len(cell["why"]) <= 200
    lists = lambda group: {m["name"] for m in bench[group] if REAL_CELL in m.get("workloads", ())}
    assert lists("end_to_end") == {"serve_tok_s"}  # and setup_s, which lists no cell; NOT itl_p95_ms
    readers = {
        "compile_s", "serve_batch_occupancy_pct", "decode_step_device_ms", "device_idle_pct.serve",
        "engine_batch_occupancy_pct", "engine_step_host_ms", "serve_idle_outside_spans_pct", "decode_attn_device_ms",
        "decode_mlp_device_ms", "decode_rest_device_ms", "decode_norm_device_ms", "moe_dispatch_device_ms",
        "decode_linear_attn_device_ms", "decode_gdn_conv_device_ms", "gdn_decode_roofline_pct", "decode_mla_device_ms",
        "decode_moe_shared_device_ms", "mla_decode_roofline_pct", "mla_prefill_roofline_pct",
        "prefill_gdn_chunk_device_ms",
    }
    assert lists("per_layer") == readers
    for name in readers:
        assert (LAYER_METRICS / f"{name}.py").is_file(), name
    new = next(m for m in bench["per_layer"] if m["name"] == "prefill_gdn_chunk_device_ms")
    assert new == {"name": "prefill_gdn_chunk_device_ms", "unit": "ms", "better": "lower", "source": "device_trace",
                   "layer": "model step (models/* decode program)", "moves": "serve_tok_s", "workloads": [REAL_CELL]}
    found = common.Cell(REPO, REAL_CELL)
    assert [m["name"] for m in found.metrics("end_to_end")] == ["serve_tok_s", "setup_s"]
    assert {m["name"] for m in found.metrics("per_layer")} == readers


def test_the_traffic_file_is_the_issues_table():
    traffic = json.loads((REPO / f"benchmarks/traffic/{REAL_TRAFFIC}.json").read_text())
    assert traffic["kind"] == "serve_closed_blocked" and traffic["clients"] == 64 and traffic["eos"] is None
    assert traffic["engine"] == {"max_batch": 64, "prefill_chunk": 512, "max_model_len": 10240, "block_size": 16}
    assert traffic["prompt_lengths"] == [1024, 512, 1024, 8192, 768, 1024, 512, 2048]
    assert traffic["output_lengths"] == [1024, 2048, 1536, 1280, 1792]
    assert traffic["stagger_first_output"] is True and "rate" not in traffic
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) == traffic["engine"]["max_model_len"]


# -------------------------------------------------------------- the reference


def test_reference_recurrence_is_the_two_token_hand_count():
    """One head, a 2 x 2 state: S_1 = beta_1 k_1 v_1^T; S_2 = a_2 S_1 + beta_2 k_2 (v_2 - a_2 S_1^T k_2)^T."""
    q = jnp.asarray([[[1.0, 0.0]], [[0.0, 1.0]]])
    k = jnp.asarray([[[1.0, 0.0]], [[0.6, 0.8]]])
    v = jnp.asarray([[[2.0, -1.0]], [[0.5, 3.0]]])
    alpha, beta = jnp.asarray([[0.9], [0.5]]), jnp.asarray([[1.0], [0.5]])
    out = np.asarray(gigachat3_5.delta_rule(q, k, v, alpha, beta))[:, 0]
    s1 = np.outer([1.0, 0.0], [2.0, -1.0])
    decayed = 0.5 * s1
    s2 = decayed + 0.5 * np.outer([0.6, 0.8], np.asarray([0.5, 3.0]) - decayed.T @ [0.6, 0.8])
    assert np.allclose(out[0], s1.T @ [1.0, 0.0]) and np.allclose(out[1], s2.T @ [0.0, 1.0], atol=1e-6)


def test_the_references_copy_is_the_familys_on_one_input():
    import flax.linen as nn

    from llm_training_tpu.models.gigachat35 import reference

    model = common.build_model({**TINY_GIGA, "program": {**TINY_GIGA["program"], "model_kwargs": {
        **TINY_GIGA["program"]["model_kwargs"], "param_dtype": "float32", "compute_dtype": "float32"}}})
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    variables = nn.meta.unbox(jax.jit(lambda k: common.seeded_tree(k, abstract, 0.2))(common.base_key(5)))
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, 256, size=(2, 24)), jnp.int32)
    seg = jnp.asarray(np.stack([np.r_[np.ones(17), np.zeros(7)], np.ones(24)]).astype(np.int32))
    want = reference.logits(variables["params"], TINY_GIGA, ids, seg)
    got = gigachat3_5.logits(variables["params"], TINY_GIGA, ids, seg)
    real = np.asarray(seg) > 0
    assert np.abs(np.asarray(got) - np.asarray(want))[real].max() < 1e-4 and np.abs(np.asarray(want)).max() > 0.5
    # the fp8 control is another function of the same weights
    low = gigachat3_5.logits(variables["params"], TINY_GIGA, ids, seg, None, gigachat3_5.c.QUANTS["fp8"])
    assert np.abs(np.asarray(low) - np.asarray(want))[real].max() > 0.05


# ---------------------------------------------------------------- the rehearsal


@pytest.mark.parametrize("seed", [11, 3_000_000_037])
def test_every_seed_serves_with_the_correction_bias_at_zero(giga_root, seed):
    """`initializer_scales`: every expert layer's `e_score_correction_bias` is
    zero in the tree the engine serves and the reference reads, whatever the
    seed; every other leaf is the array `serve_closed.build_engine` drew."""
    cell = common.Cell(giga_root, CELL)
    runner = cell.module("runners", "serve_closed_blocked")
    theirs = common.load_module(REPO / "benchmarks" / "runners" / "serve_closed.py")
    plain, engine = theirs.build_engine(cell, seed)
    engine.close()
    variables, engine = runner.build_engine(cell, seed)
    assert engine.variables is variables and engine.weights_generation == 1
    engine.close()
    changed = []
    for (path, new), old in zip(jax.tree_util.tree_leaves_with_path(variables), jax.tree.leaves(plain)):
        assert new.shape == old.shape and new.dtype == old.dtype
        if not np.array_equal(new, old):
            changed.append(common.path_str(path))
            assert not np.asarray(new).any() and np.asarray(old).any()
    assert changed == [f"params/periods/slot{i}/mlp/e_score_correction_bias" for i in range(4)]


def test_the_cell_is_found_and_rehearsed_and_its_control_is_not_correct(giga_root):
    cell = common.Cell(giga_root, CELL)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"gdn_decode_roofline_pct", "mla_decode_roofline_pct", "mla_prefill_roofline_pct", "decode_mla_device_ms",
            "decode_gdn_conv_device_ms", "moe_dispatch_device_ms", "prefill_gdn_chunk_device_ms", "compile_s"} <= names
    assert not {"paged_decode_roofline_pct", "kda_decode_roofline_pct", "decode_ssm_device_ms"} & names
    # 64 closed clients on 64 rows are AT capacity: the rate is the end-to-end metric
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["serve_tok_s", "setup_s"]
    runner = cell.module("runners", "serve_closed_blocked")
    runner.BLOCK_POSITIONS = 8
    outcome = runner.run(cell, 3_000_000_037, 2.0, False, require_tpu=False)
    common.restore_host()
    assert outcome["correct"] is True and outcome["failed"] == 0 and outcome["attempted"] > 0
    check = cell.config["check"]
    sound, control = outcome["readings"], outcome["control"]("fp8")
    assert sound["served_logit_gap"] <= check["served_logit_gap"], sound
    assert sound["far_token_share"] <= check["far_token_share"] < control["control_fp8_far_token_share"], (sound, control)
    assert sound["tokens_compared"] == control["tokens_compared"] > 0
    counters = outcome["counters"]
    assert counters["prefill_steps"] > 0 and counters["decode_rows"] > 0


def _state_never_written(monkeypatch):
    """A delta-rule layer's new state and convolution tail dropped: every
    token starts from the slot's zeros."""
    from llm_training_tpu.models import cache as cache_module

    monkeypatch.setattr(
        cache_module.LayerCache, "put_recurrent_rows", lambda self, layer, rows, in_place=False: self
    )


def _latent_rows_not_appended(monkeypatch):
    """The MLA layer attends over what the pool held plus its own chunk, and
    the pool it hands on is the one it was handed: no token is ever kept."""
    from llm_training_tpu.models import cache as cache_module

    proper = cache_module.LayerCache.attend_latent
    monkeypatch.setattr(
        cache_module.LayerCache, "attend_latent",
        lambda self, *args, **kwargs: (proper(self, *args, **kwargs)[0], self),
    )


def _attention_gate_forced_to_one(monkeypatch):
    from llm_training_tpu.models.deepseek import model as module

    monkeypatch.setattr(module, "_output_gate", lambda projected: jnp.ones(projected.shape, jnp.float32))


def _beta_forced_to_zero(monkeypatch):
    """No token writes to a state: the delta-rule layers read zeros."""
    from llm_training_tpu.models.gigachat35 import model as module

    proper = module.GatedDeltaNet
    monkeypatch.setattr(module, "GatedDeltaNet", lambda cfg, **kw: proper(cfg, **{**kw, "beta_max": 0.0}))


FAULTS = [_state_never_written, _latent_rows_not_appended, _attention_gate_forced_to_one, _beta_forced_to_zero]


@pytest.mark.parametrize("plant", FAULTS)
def test_a_fault_planted_in_the_program_is_not_correct(giga_root, monkeypatch, plant):
    """Each through the whole cell as the harness runs it."""
    plant(monkeypatch)
    result = run_cell(giga_root, CELL, 3_000_000_041, 2.0, False, require_tpu=False)
    common.restore_host()
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0
    limits = TINY_GIGA["check"]
    # by the second number, as the control: the widest gap is one token's accident
    assert result["readings"]["far_token_share"] > 2 * limits["far_token_share"], result["readings"]


# ------------------------------------------------------------------ the readers


def _scope(block, part, body="periods/while/body"):
    return f"jit(prefill_chunk)/jit(main)/GigaChat35/{body}/{block}/{part}"


def _chunk(at):
    """One chunk's ops from `at` ns: the q, k, v projection 300, the conv 120,
    the gates 40, the chunked rule 900 and the state's write 100 under it, the
    output 200; the MLA layer's chunk attention 500 and its gate 30; an MLP 250."""
    return [
        ["while.1 s32[]", at, 2440.0, ""],
        ["fusion.1 bf16[4,96]", at, 300.0, _scope("slot1/linear_attn", "qkv_proj/dot_general")],
        ["fusion.2 f32[4,96]", at + 300, 120.0, _scope("slot1/linear_attn", "gdn_conv/mul")],
        ["fusion.3 f32[4,4]", at + 420, 40.0, _scope("slot1/linear_attn", "gdn_gates/softplus")],
        ["fusion.4 f32[1,4,1,4,16]", at + 460, 900.0, _scope("slot1/linear_attn", "gdn_chunk/while/body/dot_general")],
        ["fusion.5 f32[16,4,8,16]", at + 1360, 100.0, _scope("slot1", "linear_attn/gdn_chunk/dynamic_update_slice")],
        ["fusion.6 bf16[4,64]", at + 1460, 200.0, _scope("slot1/linear_attn", "gdn_out/o_proj/dot_general")],
        ["mla_prefill.1 bf16[4,4,16]", at + 1660, 500.0, _scope("slot0/self_attn", "mla_attend/")],
        ["fusion.7 bf16[4,64]", at + 2160, 30.0, _scope("slot0/self_attn", "attn_gate/mul")],
        ["fusion.8 bf16[4,64]", at + 2190, 250.0, _scope("slot1/mlp", "moe_shared/shared_experts/down_proj/dot_general")],
    ]


TRACE = {
    "spans": [{"name": "serve/engine_step", "thread": "python3", "start": 0.0, "dur": 3000.0,
               "args": {"step": 1, "prefill_chunks": 1, "prefill_start": 0, "prefill_tokens": 4}}],
    "devices": {"0": {
        "programs": [["jit_prefill_chunk(1)", 100.0, 2440.0], ["jit_prefill_chunk(1)", 3100.0, 2440.0]],
        "ops": _chunk(100.0) + _chunk(3100.0),
    }},
}


def _read(name, trace, monkeypatch):
    monkeypatch.setattr(span_reduce, "for_cell", lambda cell: trace)
    cell = SimpleNamespace(
        config=TINY_GIGA, traffic=TINY_TRAFFIC, device={"kind": "TPU v5 lite"},
        peaks=lambda kind: {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )
    reader = common.load_module(LAYER_METRICS / f"{name}.py")
    return reader.read({"devices": trace["devices"]}, {"traced": {}}, cell)


def test_the_new_reader_gives_the_hand_worked_number(monkeypatch, capsys):
    # the chunked rule 900 ns and the state's write 100 ns a chunk, in ms
    assert _read("prefill_gdn_chunk_device_ms", TRACE, monkeypatch) == pytest.approx(1000e-6)
    logged = capsys.readouterr().out
    assert "gdn_conv 0.0001, gdn_gates 0.0000, gdn_chunk 0.0010, gdn_out 0.0002" in logged
    assert "the rest of /linear_attn/ 0.0003 of 0.0017; mla_attend 0.0005" in logged and "(2 chunks)" in logged


def test_the_new_reader_on_a_program_without_its_scope_is_not_a_reading(monkeypatch):
    """The parent, or any stack without these layers: -1 or nothing, never a number."""
    plain = copy.deepcopy(TRACE)
    for op in plain["devices"]["0"]["ops"]:
        op[3] = op[3].replace("gdn_", "kda_")
    assert _read("prefill_gdn_chunk_device_ms", plain, monkeypatch) == span_reduce.NOT_A_READING < 0
    empty = copy.deepcopy(TRACE)
    empty["devices"]["0"]["programs"] = []
    assert _read("prefill_gdn_chunk_device_ms", empty, monkeypatch) is None


def test_gdn_decode_cost_at_this_cells_state_is_the_hand_count():
    # the cell: 64 rows, 64 value heads of 128 x 128 float32: 4.19 MB a row a layer
    one = gdn_decode.cost(64, 64, 128, 128)
    state = 64 * 64 * 128 * 128 * 4
    assert state == 268_435_456 and one["bytes"] == 2 * state + 64 * 64 * (4 * 128 + 2) * 4
    # the chip's 819 GB/s: 0.666 ms a layer (0.656 of it the state), 2.66 ms the four
    assert one["bytes"] / 819e9 == pytest.approx(0.6658e-3, rel=1e-3)
