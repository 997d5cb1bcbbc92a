"""What PR 39 adds to the benchmark, on the CPU: the Olmo-Hybrid reference
against a hand-written two-token recurrence and against the program, a whole
rehearsal of a tiny copy of `olmoh-serve-longgen` (sound, with the fp8
control, and with two faults planted in the program), the delta rule's decode
cost against a hand count, the two new readers on a hand-made trace (and -1
on one without their scopes), and the configuration's file against the sizes
it states. (The benchmark's older test files are not edited by a
`model_config` PR, so these cases live here.)"""

import copy
import json
from types import SimpleNamespace

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, span_reduce
from benchmarks.costs import gdn_decode
from benchmarks.references import olmo_hybrid
from benchmarks.run import run_cell
from conftest import REPO, TINY, make_root

LAYER_METRICS = REPO / "benchmarks" / "layer_metrics"
# key_dim != value_dim, a head count 8 does not divide, two heads abreast (2 x 64 = 128 lanes)
TINY_OLMOH = {
    "source": "test", "model_type": "olmo_hybrid", "hidden_size": 60, "intermediate_size": 96,
    "num_attention_heads": 6, "num_key_value_heads": 6, "num_hidden_layers": 8,
    "vocab_size": 256, "max_position_embeddings": 4096, "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "attention_bias": False, "tie_word_embeddings": False, "rope_parameters": {"rope_theta": None},
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 2,
    "linear_num_key_heads": 6, "linear_num_value_heads": 6, "linear_key_head_dim": 12,
    "linear_value_head_dim": 64, "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "initializer_range": 0.02, "reference": "olmo_hybrid", "control_precision": "fp8",
    "check": {"served_logit_gap": 0.3},  # read over 6 seeds: sound 0.064 to 0.121, fp8 0.634 to 0.824, the faults 0.715 to 1.081
    "program": {"model_class": "OlmoHybrid", "model_kwargs": {
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "delta_chunk_size": 4}},
}
# prompts of a few tokens: the initialiser draws `A_log` and `dt_bias` near 0, so a
# state forgets half of itself a token, and what a slot's last tenant left is
# gone from the served positions of a long prompt (PERF.md, section 7)
TINY_TRAFFIC = {
    "kind": "serve_closed", "clients": 4,
    "engine": {"max_batch": 4, "prefill_chunk": 4, "max_model_len": 32, "block_size": 8},
    "prompt_lengths": [2, 6, 3, 5], "output_lengths": [4, 16, 8, 12, 10],
    "stagger_first_output": True, "eos": None,
}
CELL = "tiny-olmoh-serve"


@pytest.fixture
def olmoh_root(tmp_path):
    """The tiny checkout of conftest.py with one more configuration and cell,
    added as the real one is: a file, and entries at the ends of the lists."""
    root = make_root(tmp_path)
    (root / "benchmarks" / "configs" / "tiny-olmoh.json").write_text(json.dumps(TINY_OLMOH))
    (root / "benchmarks" / "traffic" / "tiny-olmoh-closed.json").write_text(json.dumps(TINY_TRAFFIC))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-olmoh", "source": "test", "file": "benchmarks/configs/tiny-olmoh.json",
                             "reduced": [], "why": "tiny, for the CPU"})
    bench["workloads"].append({"name": CELL, "config": "tiny-olmoh", "traffic": "tiny-olmoh-closed", "chips": 1,
                               "why": "tiny, for the CPU"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if "olmoh-serve-longgen" in metric.get("workloads", ()):
                metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


# -------------------------------------------------------------- the reference


def test_reference_recurrence_is_the_two_token_hand_count():
    """One head, key_dim 2, value_dim 3, two tokens, every number worked here:
    S_1 = beta_1 k_1 v_1^T (from zero); S_2 = alpha_2 S_1 + beta_2 k_2 (v_2 -
    alpha_2 S_1^T k_2)^T; o_t = S_t^T q_t."""
    k = np.array([[1.0, 0.0], [0.6, 0.8]])
    q = np.array([[0.5, 0.5], [1.0, -1.0]])
    v = np.array([[1.0, 2.0, 3.0], [-1.0, 0.5, 2.0]])
    alpha, beta = np.array([0.9, 0.5]), np.array([1.0, 1.5])  # beta above 1: a negative eigenvalue
    s1 = beta[0] * np.outer(k[0], v[0])  # [[1, 2, 3], [0, 0, 0]]
    o1 = s1.T @ q[0]  # [0.5, 1.0, 1.5]
    decayed = alpha[1] * s1  # [[0.5, 1.0, 1.5], [0, 0, 0]]
    seen = decayed.T @ k[1]  # [0.3, 0.6, 0.9]
    s2 = decayed + beta[1] * np.outer(k[1], v[1] - seen)
    assert np.allclose(s2, [[0.5 + 0.9 * -1.3, 1.0 + 0.9 * -0.1, 1.5 + 0.9 * 1.1],
                            [1.2 * -1.3, 1.2 * -0.1, 1.2 * 1.1]])
    o2 = s2.T @ q[1]
    lead = lambda a: jnp.asarray(a, jnp.float32)[None, :, None]  # [B=1, S=2, H=1, ...]
    got = olmo_hybrid.delta_rule(lead(q), lead(k), lead(v), lead(alpha), lead(beta), jnp.zeros((1, 2), bool))
    assert np.allclose(np.asarray(got)[0, :, 0], [o1, o2], atol=1e-6)
    assert np.allclose(o1, [0.5, 1.0, 1.5]) and np.allclose(o2, [-0.67 + 1.56, 0.91 + 0.12, 2.49 - 1.32])
    # a start in front of the second token: it sees a zero state
    alone = olmo_hybrid.delta_rule(lead(q), lead(k), lead(v), lead(alpha), lead(beta), jnp.asarray([[False, True]]))
    assert np.allclose(np.asarray(alone)[0, 1, 0], (beta[1] * np.outer(k[1], v[1])).T @ q[1], atol=1e-6)


def test_reference_logits_agree_with_the_module():
    cfg = {**TINY_OLMOH, "program": {**TINY_OLMOH["program"], "model_kwargs": {
        **TINY_OLMOH["program"]["model_kwargs"], "param_dtype": "float32", "compute_dtype": "float32",
        "attention_impl": "xla"}}}
    model = common.build_model(cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 256, size=(2, 48)).astype(np.int32)
    seg = np.tile(np.concatenate([np.full(20, 1), np.full(24, 2), np.zeros(4)]).astype(np.int32), (2, 1))
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    # a wider draw than the runs' 0.02: the convolutions and the decays all matter
    variables = nn.meta.unbox(jax.jit(lambda k: common.seeded_tree(k, abstract, 0.3))(common.base_key(7)))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda v: model.apply(v, input_ids=jnp.asarray(ids), segment_ids=jnp.asarray(seg)).logits)(variables)
    got = olmo_hybrid.logits(variables["params"], cfg, jnp.asarray(ids), jnp.asarray(seg), None)
    # float32's noise through eight layers whose every sub-block ends in a norm (tests/test_olmo_hybrid.py)
    assert np.abs(np.asarray(got) - np.asarray(want))[seg > 0].max() < 2e-3


# ------------------------------------------------------------------- the cell


def test_the_cell_is_found_and_rehearsed_and_its_control_is_not_correct(olmoh_root):
    cell = common.Cell(olmoh_root, CELL)
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert {"decode_linear_attn_device_ms", "gdn_decode_roofline_pct", "decode_gdn_conv_device_ms",
            "paged_decode_roofline_pct", "decode_norm_device_ms", "engine_step_host_ms", "compile_s"} <= set(names)
    assert not {"kda_decode_roofline_pct", "moe_dispatch_device_ms"} & set(names)
    # 32 closed clients on 32 rows are AT capacity: the rate is the end-to-end metric. The
    # tail is a plain decode step plus the host, spread over half of `itl_p95_ms`'s bound
    # (the driver's two sets, PR 39), so neither it nor a reader that moves it lists the cell
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["serve_tok_s", "setup_s"]
    assert not [m["name"] for m in bench["per_layer"]
                if m["moves"] == "itl_p95_ms" and "olmoh-serve-longgen" in m.get("workloads", ())]
    runner = cell.module("runners", "serve_closed")
    outcome = runner.run(cell, 3_000_000_037, 2.0, False, require_tpu=False)
    common.restore_host()
    assert outcome["correct"] is True and outcome["failed"] == 0 and outcome["attempted"] > 0
    limit = cell.config["check"]["served_logit_gap"]
    sound, control = outcome["readings"], outcome["control"]("fp8")
    assert sound["served_logit_gap"] <= limit < control["control_fp8"], (sound, control)


@pytest.mark.parametrize("fault", ["state_not_reset", "tail_one_tap_off"])
def test_a_fault_planted_in_the_slab_is_not_correct(olmoh_root, monkeypatch, fault):
    """A recycled decode slot's state and conv tail read as its last tenant
    left them; the conv tail read one tap off."""
    from llm_training_tpu.models.olmo_hybrid import model as program

    if fault == "state_not_reset":
        monkeypatch.setattr(program, "_slot_rows", lambda slab, slots, fresh: slab if slots is None else slab[slots])
    else:
        proper = program._slot_rows

        def shifted(slab, slots, fresh):
            rows = proper(slab, slots, fresh)
            return jnp.roll(rows, 1, axis=1) if rows.ndim == 3 else rows  # the tail: [B, taps, channels]

        monkeypatch.setattr(program, "_slot_rows", shifted)
    result = run_cell(olmoh_root, CELL, 3_000_000_041, 2.0, False, require_tpu=False)
    common.restore_host()
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0


# ---------------------------------------------------------- cost and readers


def test_gdn_decode_cost_is_the_hand_count():
    # the cell: 32 rows, 30 heads, a [96, 192] float32 state a head
    one = gdn_decode.cost(32, 30, 96, 192)
    state = 32 * 30 * 96 * 192 * 4
    assert state == 70_778_880
    vectors = 32 * 30 * (96 + 96 + 192 + 192 + 1 + 1) * 4  # q, k, v, out, beta, g
    assert one["bytes"] == 2 * state + vectors == 143_777_280
    assert one["flops"] == 32 * 30 * 96 * 192 * 7 == 123_863_040
    # half the rows idle: half the work; the chip's 819 GB/s: 0.1756 ms a layer
    assert gdn_decode.cost(16, 30, 96, 192)["bytes"] * 2 == one["bytes"]
    assert one["bytes"] / 819e9 == pytest.approx(0.17555e-3, rel=1e-3)


def _scope(block, part=""):
    return f"jit(decode_step)/jit(main)/OlmoHybrid/layers/while/body/slot0/{block}/{part}"


# two decode steps; under `/linear_attn/`: conv 100 + 100, gates 20 + 20,
# the recurrence 400 + 600 (two fusions a step), out 80 + 80, a projection 60 + 60
TRACE = {
    "spans": [{"name": "serve/engine_step", "thread": "python3", "start": 0.0, "dur": 5000.0,
               "args": {"step": 1, "decode_rows": 4, "live_tokens": 40}}],
    "devices": {"0": {
        "programs": [["jit_decode_step(1)", 990.0, 1010.0], ["jit_decode_step(1)", 2990.0, 1010.0]],
        "ops": [
            ["while.1 s32[]", 990.0, 1010.0, ""],
            ["fusion.1 bf16[4,528]", 990.0, 60.0, _scope("linear_attn", "q_proj/dot_general")],
            ["fusion.2 f32[4,528]", 1050.0, 100.0, _scope("linear_attn", "gdn_conv/mul")],
            ["fusion.3 f32[4,6]", 1150.0, 20.0, _scope("linear_attn", "gdn_gates/softplus")],
            ["fusion.4 f32[4,3,128]", 1170.0, 150.0, _scope("linear_attn", "gdn_recurrence/reduce_sum")],
            ["fusion.5 f32[12,3,12,128]", 1320.0, 250.0, _scope("linear_attn", "gdn_recurrence/add")],
            ["fusion.6 bf16[4,60]", 1570.0, 80.0, _scope("linear_attn", "gdn_out/o_proj/dot_general")],
            ["fusion.7 bf16[4,60]", 1650.0, 200.0, _scope("mlp", "down_proj/dot_general")],
            ["while.1 s32[]", 2990.0, 1010.0, ""],
            ["fusion.1 bf16[4,528]", 2990.0, 60.0, _scope("linear_attn", "q_proj/dot_general")],
            ["fusion.2 f32[4,528]", 3050.0, 100.0, _scope("linear_attn", "gdn_conv/mul")],
            ["fusion.3 f32[4,6]", 3150.0, 20.0, _scope("linear_attn", "gdn_gates/softplus")],
            ["fusion.4 f32[4,3,128]", 3170.0, 250.0, _scope("linear_attn", "gdn_recurrence/reduce_sum")],
            ["fusion.5 f32[12,3,12,128]", 3420.0, 350.0, _scope("linear_attn", "gdn_recurrence/add")],
            ["fusion.6 bf16[4,60]", 3770.0, 80.0, _scope("linear_attn", "gdn_out/o_proj/dot_general")],
            ["fusion.7 bf16[4,60]", 3850.0, 150.0, _scope("mlp", "down_proj/dot_general")],
        ],
    }},
}
COUNTERS = {"traced": {"decode_steps": 2, "decode_rows": 8, "live_tokens": 80}}


def _read(name, trace, monkeypatch):
    monkeypatch.setattr(span_reduce, "for_cell", lambda cell: trace)
    cell = SimpleNamespace(
        config=TINY_OLMOH, device={"kind": "TPU v5 lite"},
        peaks=lambda kind: {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )
    reader = common.load_module(LAYER_METRICS / f"{name}.py")
    return reader.read({"devices": trace["devices"]}, COUNTERS, cell)


def test_the_new_readers_give_the_hand_worked_numbers(monkeypatch, capsys):
    assert _read("decode_gdn_conv_device_ms", TRACE, monkeypatch) == pytest.approx(100e-6)  # 100 ns a step, in ms
    logged = capsys.readouterr().out
    assert "gdn_conv 0.0001, gdn_gates 0.0000, gdn_recurrence 0.0005, gdn_out 0.0001" in logged
    assert "the rest of /linear_attn/ 0.0001 of 0.0008" in logged  # the projection: 60 of 760 ns
    # 4 rows a step, 6 heads of [12, 64], 6 linear layers: bytes a layer over the chip's 819 GB/s,
    # 2 steps x 6 layers of it over the 1000 ns the two steps' recurrences took
    one = gdn_decode.cost(4, 6, 12, 64)
    assert one["bytes"] == 2 * 4 * 6 * 12 * 64 * 4 + 4 * 6 * (24 + 128 + 2) * 4 == 162_240
    want = 100.0 * (one["bytes"] / 819e9) * 2 * 6 / 1000e-9
    assert _read("gdn_decode_roofline_pct", TRACE, monkeypatch) == pytest.approx(want)
    assert "gdn_recurrence: 2 decode steps x 6 layers, 0.0001 ms a layer, 0.2 MB" in capsys.readouterr().out


@pytest.mark.parametrize("name", ["gdn_decode_roofline_pct", "decode_gdn_conv_device_ms"])
def test_a_program_without_the_scopes_is_not_a_reading(monkeypatch, name):
    """The parent, or any stack without these layers: -1, never a number."""
    plain = copy.deepcopy(TRACE)
    for op in plain["devices"]["0"]["ops"]:
        op[3] = op[3].replace("gdn_", "kda_")
    assert _read(name, plain, monkeypatch) == span_reduce.NOT_A_READING < 0
    # and with no decode step in the trace at all there is nothing to read
    empty = copy.deepcopy(TRACE)
    empty["devices"]["0"]["programs"] = []
    assert _read(name, empty, monkeypatch) is None


# ------------------------------------------------------------ the configuration


def test_the_configuration_states_the_published_widths_and_its_cut():
    cfg = json.loads((REPO / "benchmarks/configs/olmo-hybrid-7b.json").read_text())
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b")
    assert entry["reduced"] == list(cfg["reduced_from"]) == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8 and cfg["reduced_from"] == {"num_hidden_layers": 32}
    assert cfg["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 2
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]) == (3840, 11008, 100352)
    assert (cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], cfg["linear_num_value_heads"]) == (96, 192, 30)
    model = common.build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    size = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(nn.meta.unbox(tree)))
    layers = shapes["layers"]
    assert set(layers) == {"slot0", "slot1", "slot2", "slot3"}  # one period, scanned twice
    assert "self_attn" in layers["slot3"] and all("linear_attn" in layers[f"slot{j}"] for j in (0, 1, 2))
    linear = nn.meta.unbox(layers["slot0"]["linear_attn"])
    assert linear["q_proj"]["kernel"].shape == (2, 3840, 2880) and linear["v_proj"]["kernel"].shape == (2, 3840, 5760)
    assert linear["v_conv_kernel"].shape == (2, 4, 5760) and linear["A_log"].shape == (2, 30)
    mlp = 3 * 3840 * 11008
    delta = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30 + 4 * (2 * 2880 + 5760) + 2 * 30 + 192
    full = 4 * 3840 * 3840 + 2 * 3840
    norms = 2 * 3840
    assert size(layers["slot0"]) == 2 * (mlp + delta + norms) and size(layers["slot3"]) == 2 * (mlp + full + norms)
    assert size(shapes) == 2 * (3 * delta + full + 4 * (mlp + norms)) + 2 * 100352 * 3840 + 3840
    assert 4.8e9 < 2 * size(shapes) < 4.9e9  # bytes in bfloat16: 29% of the chip
    # the cell and its traffic, as the issue names them
    cell = next(w for w in bench["workloads"] if w["name"] == "olmoh-serve-longgen")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmo-hybrid-7b", "serve-longgen-closed", 1)
    traffic = json.loads((REPO / "benchmarks/traffic/serve-longgen-closed.json").read_text())
    assert traffic["kind"] == TINY["traffic"]["tiny-closed"]["kind"] == "serve_closed"
    assert traffic["engine"] == {"max_batch": 32, "prefill_chunk": 512, "max_model_len": 3072, "block_size": 16}
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) == 3072
    # the slab, stored two heads abreast, and the pool for 32 rows of 3,072
    from llm_training_tpu.infer.cache import cache_specs

    kv, recurrent = cache_specs(model.config)
    assert recurrent.stored == (15, 96, 384) and 15 * 96 * 384 == 30 * 96 * 192
    assert recurrent.layers * 32 * 30 * 96 * 192 * 4 == 424_673_280
    assert kv.layers * 2 * kv.kv_heads * kv.head_dim * 2 == 30_720  # bytes a token
