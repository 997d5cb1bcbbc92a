"""step_reduce and the seven readers built on it, on a hand-made trace
(fixtures/step_scopes_small.json, in the shape `span_reduce._read` returns
with the three fields `step_reduce._read` adds an op): two executions of a
train step whose ops lie in every bucket and every pass, two of a decode step
and one of a prefill chunk. A step's ops, ns (1,000 in all):

    collective  all-gather 10 (its scope says /mlp/: the op name wins)
    loss_ce     forward 40, backward 60
    rms_norm    forward 20 + 10 (q_norm, INSIDE /self_attn/), recompute 10, backward 20
    self_attn   forward 60, recompute 50, backward 90
    mlp         forward 150 + 50 (a scopeless ragged-dot), recompute 100, backward 250
    optimizer   30;  embed_tokens 10;  none 40 (a convert with no scope)
"""

import copy
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import common, span_reduce as sr, step_reduce as st
from benchmarks.costs import mlp_matmul

HERE = Path(__file__).parent
FIXTURE = HERE / "fixtures" / "step_scopes_small.json"
TPU_FIXTURE = HERE / "fixtures" / "serve_tpu_small.xplane.pb"
ROOT = HERE.resolve().parents[1]
LAYER_METRICS = ROOT / "benchmarks" / "layer_metrics"
BY_HAND = {
    "collective": (10, 0, 0), "loss_ce": (40, 60, 0), "rms_norm": (30, 20, 10), "self_attn": (60, 90, 50),
    "mlp": (200, 250, 100), "optimizer": (30, 0, 0), "embed_tokens": (10, 0, 0), "none": (40, 0, 0),
}  # forward, backward, recompute
TRAIN = ("train_norm_device_pct", "train_recompute_device_pct", "train_mlp_roofline_pct",
         "train_optimizer_device_pct", "train_unaccounted_device_pct")
SERVE = ("decode_norm_device_ms", "prefill_norm_device_ms")
SERVE_CELLS = ["phi3m-serve-rollout", "olmoe-serve-rollout", "solar2-serve-longdoc", "longcat-serve-longctx",
               "trinity-serve-mixedlen"]
# 4 tokens a chip a step through one dense MLP of 8 x 32: 18 x 8 x 32 x 4 operations
CELL = SimpleNamespace(
    config={"hidden_size": 8, "intermediate_size": 32, "num_hidden_layers": 1, "num_attention_heads": 2,
            "num_key_value_heads": 1, "vocab_size": 64},
    traffic={"seq_len": 4, "documents": [4]}, device={"kind": "a chip"},
    peaks=lambda kind: {"bf16_flops_per_s": 1e11, "hbm_bytes_per_s": 1e12},
)
COUNTERS = {"rows_per_chip": 1}


@pytest.fixture()
def trace():
    return json.loads(FIXTURE.read_text())


def read(name, trace, monkeypatch):
    """One reader on `trace`, as run.py calls it (the trace_reduce dict first)."""
    monkeypatch.setattr(st, "for_cell", lambda cell: trace)
    reader = common.load_module(LAYER_METRICS / f"{name}.py")
    return reader.read({"devices": trace["devices"]}, COUNTERS, CELL)


def without(trace, *scopes):
    """The trace with, of every op's name stack, the segments named `scopes` gone."""
    out = copy.deepcopy(trace)
    for op in out["devices"]["0"]["ops"]:
        op[3] = "/".join(part for part in op[3].split("/") if part not in scopes)
    return out


def test_every_op_of_the_step_lands_in_one_bucket_and_one_pass(trace):
    found = st.table(trace)
    assert found["steps"] == 2
    for bucket, by_hand in BY_HAND.items():
        got = [found["seconds"][bucket][p] for p in ("forward", "backward", "recompute")]
        assert got == pytest.approx([2e-9 * ns for ns in by_hand]), bucket
    assert tuple(found["seconds"]) == st.BUCKETS == tuple(BY_HAND)
    # the container and the other program's op are in no sum; nothing overlaps
    assert found["total_s"] == pytest.approx(found["busy_s"]) == pytest.approx(2000e-9)
    assert sum(st.share_pct(found, b) for b in st.BUCKETS) == pytest.approx(100.0)
    assert st.flops_of(found, "mlp") == 2 * (2048 + 2048 + 4096) and st.flops_of(found, None, "recompute") == 2 * 2624


def test_the_log_says_what_an_op_known_by_its_first_result_is(trace, capsys):
    found = st.table(trace)
    # `fusion f32[4]` is what trace_reduce.top_ops calls four different things
    assert {k: v * 1e9 / 2 for k, v in found["landed"]["fusion f32[4]"].items()} == pytest.approx({
        ("mlp", "backward"): 250, ("self_attn", "recompute"): 50, ("rms_norm", "forward"): 20, ("rms_norm", "recompute"): 10,
    })
    longest = found["longest"]["mlp"][0]
    assert longest[:3] == ("fusion", "(f32[4], bf16[4,8], bf16[8,32])", "convolution fusion")
    assert longest[3].endswith("mlp/down_proj/dot_general") and longest[4:] == (pytest.approx(500e-9), 2)
    st.log_table(found)
    out = capsys.readouterr().out
    assert "2 executions" in out and "(+0.0000%)" in out
    assert "mlp: 0.0000 s a step in 1 calls of fusion (f32[4], bf16[4,8], bf16[8,32]) [convolution fusion, backward]" in out
    assert "`fusion f32[4]`" in out and "mlp/backward" in out


def test_each_reader_gives_the_number_worked_by_hand(trace, monkeypatch, capsys):
    assert read("train_norm_device_pct", trace, monkeypatch) == pytest.approx(6.0)
    assert read("train_recompute_device_pct", trace, monkeypatch) == pytest.approx(16.0)
    assert read("train_optimizer_device_pct", trace, monkeypatch) == pytest.approx(3.0)
    assert read("train_unaccounted_device_pct", trace, monkeypatch) == pytest.approx(4.0)
    # 18,432 operations at 1e11 a second: 184.32 ns of the bucket's 550
    assert read("train_mlp_roofline_pct", trace, monkeypatch) == pytest.approx(100 * 184.32 / 550)
    out = capsys.readouterr().out
    assert out.count("train step on device 0") == 1  # five readers, one table
    assert "for 1.8432e+04 required operations (the traced ops ran 8.1920e+03)" in out
    assert read("decode_norm_device_ms", trace, monkeypatch) == pytest.approx(30e-6)
    assert read("prefill_norm_device_ms", trace, monkeypatch) == pytest.approx(80e-6)
    assert "(f32[512], bf16[512,8])" in capsys.readouterr().out


def test_the_buckets_and_what_no_scope_holds_sum_to_100(trace, monkeypatch):
    found = st.table(trace)
    named = sum(st.share_pct(found, b) for b in st.BUCKETS if b != "none")
    assert named + read("train_unaccounted_device_pct", trace, monkeypatch) == pytest.approx(100.0)
    assert sum(st.share_pct(found, None, p) for p in st.PASSES) == pytest.approx(100.0)


def test_a_program_older_than_the_scopes_is_not_a_reading_and_the_pass_readers_read_the_same(trace, monkeypatch, capsys):
    older = without(trace, "rms_norm", "optimizer")
    for name in ("train_norm_device_pct", "train_optimizer_device_pct", "train_unaccounted_device_pct", *SERVE):
        assert read(name, older, monkeypatch) == sr.NOT_A_READING, name
    assert "not a reading" in capsys.readouterr().out
    assert read("train_recompute_device_pct", older, monkeypatch) == pytest.approx(16.0)
    assert read("train_mlp_roofline_pct", older, monkeypatch) == pytest.approx(100 * 184.32 / 550)
    # the norms' time went where it was before: q_norm's to /self_attn/, the layer's own to no needle
    found = st.table(older)
    assert st.seconds_of(found, "self_attn") == pytest.approx(2 * 210e-9)
    assert st.seconds_of(found, "none") == pytest.approx(2 * 120e-9)


def test_a_scope_gone_from_a_program_that_had_it_ends_the_run(trace, monkeypatch):
    no_norm = without(trace, "rms_norm")
    assert read("train_norm_device_pct", no_norm, monkeypatch) is None
    assert read("decode_norm_device_ms", no_norm, monkeypatch) is None
    assert read("train_optimizer_device_pct", no_norm, monkeypatch) == pytest.approx(3.0)
    assert read("train_optimizer_device_pct", without(trace, "optimizer"), monkeypatch) is None
    gone = copy.deepcopy(trace)
    gone["devices"]["0"]["programs"] = [p for p in gone["devices"]["0"]["programs"] if "train_step" not in p[0]]
    for name in TRAIN:
        assert read(name, gone, monkeypatch) is None, name
    assert read("prefill_norm_device_ms", gone, monkeypatch) == pytest.approx(80e-6)


def test_a_real_capture_keeps_the_whole_result_type():
    """The same file `span_reduce` reads: the first four fields agree op for
    op, and a tuple's elements are all there, layouts dropped."""
    mine, theirs = st.load(TPU_FIXTURE), sr.load(TPU_FIXTURE)
    ops = mine["devices"]["0"]["ops"]
    assert [op[:4] for op in ops] == theirs["devices"]["0"]["ops"]
    assert mine["devices"]["0"]["programs"] == theirs["devices"]["0"]["programs"]
    tuples = {op[4] for op in ops if op[0].startswith("sine_convert_fusion ")}
    assert tuples == {"(bf16[1,512,1,128], bf16[1,512,1,128])", "(bf16[32,1,1,128], bf16[32,1,1,128])"}
    assert all(op[4].startswith(op[0].partition(" ")[2]) or op[4].startswith("(") for op in ops)
    assert {op[5] for op in ops} >= {"convolution fusion", "loop fusion"}
    # a capture of PR 25's program: no norm scope in it, so not a reading
    assert st.older_program(mine) == sr.NOT_A_READING


def test_whole_result_reads_an_instruction_as_the_device_plane_names_it():
    text = ("%fusion.597 = (f32[4,4096]{1,0:T(4,128)S(1)}, bf16[4,4096,5120]{2,1,0:T(8,128)(2,1)}, /*index=2*/s32[2]{0:S(4)}, "
            "u32[]{:S(2)}) fusion(f32[4,4096]{1,0} %a, bf16[1,1280,17920]{2,1,0} %b), kind=kOutput, calls=%fused_computation.1")
    assert st.whole_result(text) == "(f32[4,4096], bf16[4,4096,5120], s32[2], u32[])"
    assert st.whole_result("%copy.7 = bf16[13,3073,10,16,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%x)") == "bf16[13,3073,10,16,128]"
    assert st.whole_result("no instruction") == ""


def test_the_mlp_cost_is_the_issues_reckoning():
    # phi3-medium's widths, 6 layers, 4 rows of 4096 a chip
    one = mlp_matmul.cost(4 * 4096, 6, 5120, 17920, 2)
    assert one["flops"] == 18 * 5120 * 17920 * 6 * 16384 == 162_349_763_788_800  # 1.62e14
    assert one["flops"] / 197e12 == pytest.approx(0.824, abs=5e-4)
    weights = 3 * 5120 * 17920
    assert one["bytes"] == 6 * (3 * weights + 5 * 16384 * 5120) * 2  # 12.4 GB: 15 ms at 819 GB/s, operations bind
    assert one["bytes"] / 819e9 < 0.02
    # olmoe: 8 of 64 experts of 1024 and the router, a token
    assert mlp_matmul.params_per_token(2048, 1024, 64, 8) == 8 * 3 * 2048 * 1024 + 2048 * 64
    sparse = mlp_matmul.cost(1000, 2, 2048, 1024, 2, 64, 8)
    assert sparse["flops"] == 6 * (8 * 3 * 2048 * 1024 + 2048 * 64) * 1000 * 2
    assert sparse["bytes"] == 2 * (3 * (64 * 3 * 2048 * 1024 + 2048 * 64) + 5 * 1000 * 2048) * 2


def test_benchmark_json_lists_the_seven_readers_last_with_their_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = bench["per_layer"][-7:]
    assert [m["name"] for m in added] == [*TRAIN, *SERVE]
    for metric in added:
        reader = common.load_module(LAYER_METRICS / f"{metric['name']}.py")
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (metric["layer"], metric["unit"], metric["moves"])
        assert metric["source"] == "device_trace" and set(metric) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert all(m["workloads"] == ["phi3m-train-4k-fsdp4"] and m["moves"] == "train_tok_s_chip" for m in added[:5])
    assert [m["better"] for m in added[:5]] == ["lower", "lower", "higher", "lower", "lower"]
    assert [(m["workloads"], m["moves"]) for m in added[5:]] == [(SERVE_CELLS, "serve_tok_s"), (SERVE_CELLS, "itl_p95_ms")]
