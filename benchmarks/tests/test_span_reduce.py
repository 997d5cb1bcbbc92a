"""span_reduce and the readers built on it, on a hand-made trace
(fixtures/serve_spans_small.json) whose numbers are worked by hand here:
self times, the idle table, device time by scope; every reader gives a
number on it and nothing once its span or scope is taken out."""

import copy
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks import common, span_reduce as sr, trace_reduce as tr

FIXTURE = Path(__file__).parent / "fixtures" / "serve_spans_small.json"
# two engine steps (one with a prefill chunk) cut out of a traced run of
# `olmoe-serve-rollout` on a TPU v5e (my chip run, PR 25): the device's ops
# and programs, the program's `llmt/` spans and the frames of step() and
# device_get beside them; of the ops' metadata the scope and three plain stats
TPU_FIXTURE = Path(__file__).parent / "fixtures" / "serve_tpu_small.xplane.pb"
LAYER_METRICS = Path(__file__).resolve().parents[1] / "layer_metrics"
NS_MS = 1e-6  # the fixture's times are ns; the metrics' are ms

SERVE_READERS = (
    "engine_batch_occupancy_pct", "engine_prefill_step_share_pct", "engine_step_host_ms",
    "serve_idle_outside_spans_pct", "decode_attn_device_ms", "decode_mlp_device_ms",
    "decode_rest_device_ms", "moe_dispatch_device_ms",
)
TRAIN_READERS = ("train_attn_device_pct", "train_mlp_device_pct", "train_loss_device_pct")


@pytest.fixture()
def trace():
    return json.loads(FIXTURE.read_text())


def read(name, trace, counters=None, monkeypatch=None):
    """One reader on `trace`, as run.py calls it (the trace_reduce dict first)."""
    monkeypatch.setattr(sr, "for_cell", lambda cell: trace)
    cell = SimpleNamespace(traffic={"engine": {"max_batch": 2}})
    reader = common.load_module(LAYER_METRICS / f"{name}.py")
    return reader.read({"devices": trace["devices"]}, counters or {}, cell)


def without(trace, *, spans=(), scopes=(), every_scope=False, ops=()):
    """The trace with the named spans gone, the ops whose own name starts
    with one of `ops` gone, and, of every op's scope path, the segments that
    hold one of `scopes` (or the whole path)."""
    out = copy.deepcopy(trace)
    out["spans"] = [s for s in out["spans"] if s["name"] not in spans]
    line = out["devices"]["0"]
    line["ops"] = [op for op in line["ops"] if not op[0].startswith(tuple(ops) or ("\0",))]
    for op in line["ops"]:
        op[3] = "" if every_scope else "/".join(
            part for part in op[3].split("/") if not any(scope in part for scope in scopes)
        )
    return out


def step(trace, index):
    return [s for s in sr.spans_named(trace, "serve/engine_step") if s["args"]["step"] == index][0]


def test_a_real_tpu_capture_is_read_as_the_profilers_own_reader_reads_it():
    """`_read` parses the file as the XSpace message it is; jax's
    `ProfileData` (through `trace_reduce.load`) reads the same file: the
    device's ops and programs agree event for event, and the scope that only
    `_read` sees is the HLO op_name of the op."""
    mine, theirs = sr.load(TPU_FIXTURE), tr.load(TPU_FIXTURE)
    assert list(mine["devices"]) == list(theirs["devices"]) == ["0"]
    device = mine["devices"]["0"]

    def whole_ns(events):  # ProfileData hands out whole nanoseconds, the file holds picoseconds
        return [[name, pytest.approx(start, abs=1), pytest.approx(dur, abs=1)] for name, start, dur in events]

    assert [op[:3] for op in device["ops"]] == whole_ns(theirs["devices"]["0"]["ops"])
    assert device["programs"] == whole_ns(theirs["devices"]["0"]["programs"])
    assert len(device["ops"]) > 3000
    assert {name.split("(")[0] for name, _, _ in device["programs"]} >= {"jit_decode_step", "jit_prefill_chunk"}
    by_name = {op[0].split(" ")[0].split(".")[0]: op[3] for op in device["ops"]}
    assert by_name["paged_decode"].startswith("jit(decode_step)/") and sr.ATTN in by_name["paged_decode"]
    # the chip's compiler leaves the grouped matmul no scope: why GROUPED_MATMUL exists
    assert by_name["ragged-dot-none"] == "ragged-dot-none"
    scopes = {op[3] for op in device["ops"]}
    for scope in sr.MOE_DISPATCH + (sr.SAMPLE, sr.MLP, "jit(prefill_chunk)/"):
        assert any(scope in path for path in scopes), scope
    # the program's spans, with their args typed as the program gave them
    steps = sr.spans_named(mine, "serve/engine_step")
    assert [s["args"]["prefill_chunks"] for s in steps] == [1, 0]
    assert steps[1]["args"]["step"] == steps[0]["args"]["step"] + 1
    assert all(s["thread"] == "python3" and not s["name"].startswith("llmt/") for s in mine["spans"])
    (chunk,) = sr.spans_named(mine, "serve/prefill_chunk")
    assert isinstance(chunk["args"]["request_id"], str) and chunk["args"]["tokens"] == steps[0]["args"]["prefill_tokens"]
    assert {s["name"] for s in sr.inside(mine, steps[1])} == {
        f"serve/{n}" for n in ("housekeeping", "schedule", "decode_blocks", "decode_inputs",
                               "decode_dispatch", "decode_fetch", "decode_emit")
    }
    # and what is made of them holds together on real numbers
    split = sr.decode_split_ms(mine)
    assert split["calls"] == 2 and split["attn"] > split["rest"] > split["mlp"] > split["sample"] > 0
    assert split["attn"] + split["mlp"] + split["rest"] == pytest.approx(tr.program_device_ms(theirs, r"decode_step"), rel=0.01)
    lo, hi = tr.window_ns(theirs)
    assert sum(sr.idle_by_span(mine, prefix="serve/").values()) == pytest.approx(
        (hi - lo) * 1e-9 - tr.busy_s(theirs)["0"], rel=1e-3
    )


def test_self_times_are_the_span_less_what_lies_inside_it(trace):
    # step 1 runs 900..2700 and its children tile 900..2690: 10 ns are its own
    assert sr.self_ns(trace, step(trace, 1)) == pytest.approx(10.0)
    # step 2 runs 3000..5200, children 3000..5190
    assert sr.self_ns(trace, step(trace, 2)) == pytest.approx(10.0)
    chunk = sr.spans_named(trace, "serve/prefill_chunk")[0]
    # 480 ns, of them 90 in the dispatch and 340 in the fetch
    assert sr.self_ns(trace, chunk) == pytest.approx(50.0)
    # the other thread's decode_fetch (1000..2000) is nobody's child
    assert all(s["thread"] == "python3" for s in sr.inside(trace, step(trace, 1)))
    # a step less its waits for the device: 1800 - 340 - 1030 and 2200 - 1000
    assert sr.less_ns(trace, step(trace, 1), sr.FETCHES) == pytest.approx(430.0)
    assert sr.less_ns(trace, step(trace, 2), sr.FETCHES) == pytest.approx(1200.0)


def test_step_counts_are_the_engine_steps_closing_args(trace):
    assert sr.step_counts(trace) == {
        "steps": 2, "prefill_steps": 1, "prefill_tokens": 8,
        "decode_steps": 2, "decode_rows": 3, "live_tokens": 32,
    }
    longest = sr.longest_steps(trace, n=1)[0]
    assert longest["step"] == 2 and longest["seconds"] == pytest.approx(2200e-9)
    assert longest["inside"]["serve/decode_fetch"] == pytest.approx(1000e-9, abs=1e-12)
    assert list(longest["inside"])[0] == "serve/decode_fetch"


def test_idle_table_sums_to_the_idle_time(trace):
    # gaps: 1400..1500, 2500..4000, 5000..6000. The bubbles inside the decode
    # calls (2300..2350, 4600..4700) lie under the `while` that holds the
    # layers: busy, as trace_reduce counts it
    assert sr.idle_gaps(trace) == [(1400.0, 1500.0), (2500.0, 4000.0), (5000.0, 6000.0)]
    # each gap is cut along the innermost spans open through it:
    #   1400..1500: prefill_fetch 40, schedule 20, decode_blocks 10, decode_inputs
    #               20, decode_dispatch 10 (the other thread's span started
    #               earlier than each of these: never the innermost)
    #   2500..4000: decode_fetch 50, decode_emit 140, the step itself 10, no
    #               span 300, housekeeping 10, schedule 90 + 10, decode_blocks
    #               190, decode_inputs 600, decode_dispatch 100
    #   5000..6000: decode_fetch 50, decode_emit 140, the step 10, no span 800
    table = sr.idle_by_span(trace, prefix="serve/")
    assert table == pytest.approx({
        "outside": 1100e-9, "serve/decode_inputs": 620e-9, "serve/decode_emit": 280e-9,
        "serve/decode_blocks": 200e-9, "serve/schedule": 120e-9,
        "serve/decode_dispatch": 110e-9, "serve/decode_fetch": 100e-9,
        "serve/prefill_fetch": 40e-9, "serve/engine_step": 20e-9, "serve/housekeeping": 10e-9,
    })
    assert list(table)[:2] == ["outside", "serve/decode_inputs"]
    three = {"devices": {"0": {"ops": [op[:3] for op in trace["devices"]["0"]["ops"]],
                                "programs": trace["devices"]["0"]["programs"]}}}
    lo, hi = tr.window_ns(three)
    assert sum(table.values()) == pytest.approx((hi - lo) * 1e-9 - tr.busy_s(three)["0"])
    # spans of another category do not name a gap
    assert sr.idle_by_span(trace, prefix="train/") == pytest.approx({"outside": 2600e-9})


def test_device_time_by_scope_inside_decode_step(trace):
    split = sr.decode_split_ms(trace)
    # two calls: attention 300 + 300; the sparse block 500 + 400; the pool's
    # copy and the sampling 150 + 200, the sampling alone 50 + 50
    assert split == pytest.approx({
        "attn": 300 * NS_MS, "mlp": 450 * NS_MS, "rest": 175 * NS_MS, "sample": 50 * NS_MS, "calls": 2,
    })
    three = {"devices": {"0": {"ops": [op[:3] for op in trace["devices"]["0"]["ops"]],
                                "programs": trace["devices"]["0"]["programs"]}}}
    # trace_reduce's time a call is a union that the `while` fills: the
    # blocks' sum is short of it by the bubbles between the ops, 50 + 100
    assert split["attn"] + split["mlp"] + split["rest"] == pytest.approx(
        tr.program_device_ms(three, r"decode_step") - 75 * NS_MS
    )
    ops, calls = sr.scoped_ops(trace, program=r"decode_step")
    assert calls == 2 and not any(op[0].startswith("while") for op in ops)
    # the grouped matmul keeps no scope on the chip: known by its own name
    assert [op[3] for op in ops if op[0].startswith("ragged-dot")] == ["ragged-dot-none"] * 2
    assert sr.seconds_under(ops, "moe_experts") == pytest.approx(300e-9)
    assert sr.seconds_under(ops, *sr.MOE_DISPATCH) == pytest.approx(600e-9)
    # the whole device line: the prefill's attention op too
    everything, _ = sr.scoped_ops(trace)
    assert sr.seconds_under(everything, sr.ATTN) == pytest.approx(900e-9)
    assert sr.train_share_pct(trace, "loss_ce") == pytest.approx(100 * 100 / 2350)


def test_every_reader_gives_the_hand_worked_number(trace, monkeypatch):
    expected = {
        "engine_batch_occupancy_pct": 100.0 * 3 / (2 * 2),
        "engine_prefill_step_share_pct": 50.0,
        "engine_step_host_ms": (430 + 1200) / 2 * NS_MS,
        "serve_idle_outside_spans_pct": 100.0 * 1100 / 2600,
        "decode_attn_device_ms": 300 * NS_MS,
        "decode_mlp_device_ms": 450 * NS_MS,
        "decode_rest_device_ms": 175 * NS_MS,
        "moe_dispatch_device_ms": 300 * NS_MS,
        "train_attn_device_pct": 100.0 * 900 / 2350,
        "train_mlp_device_pct": 100.0 * 900 / 2350,
        "train_loss_device_pct": 100.0 * 100 / 2350,
    }
    assert set(expected) == set(SERVE_READERS + TRAIN_READERS)
    for name, value in expected.items():
        assert read(name, trace, monkeypatch=monkeypatch) == pytest.approx(value), name


@pytest.mark.parametrize("name,gone", [
    ("engine_batch_occupancy_pct", {"spans": ["serve/engine_step"]}),
    ("engine_prefill_step_share_pct", {"spans": ["serve/engine_step"]}),
    ("engine_step_host_ms", {"spans": ["serve/engine_step"]}),
    ("decode_attn_device_ms", {"every_scope": True}),
    ("decode_attn_device_ms", {"scopes": ["self_attn"]}),
    ("decode_mlp_device_ms", {"scopes": ["mlp"], "ops": ["ragged-dot"]}),
    ("decode_mlp_device_ms", {"every_scope": True}),
    ("decode_rest_device_ms", {"every_scope": True}),
    ("moe_dispatch_device_ms", {"scopes": ["moe_"]}),
    ("train_attn_device_pct", {"scopes": ["self_attn"]}),
    ("train_mlp_device_pct", {"scopes": ["mlp"], "ops": ["ragged-dot"]}),
    ("train_loss_device_pct", {"scopes": ["loss_ce"]}),
])
def test_a_reader_finds_nothing_once_its_span_or_scope_is_gone(trace, monkeypatch, name, gone):
    assert read(name, without(trace, **gone), monkeypatch=monkeypatch) is None


def test_idle_reader_finds_nothing_without_device_ops(trace, monkeypatch):
    empty = copy.deepcopy(trace)
    empty["devices"]["0"]["ops"] = []
    assert read("serve_idle_outside_spans_pct", empty, monkeypatch=monkeypatch) is None


def test_what_a_reader_logs_beside_its_number(trace, monkeypatch, capsys):
    """The closing arg and the scope no metric is made of still have a reader."""
    read("engine_prefill_step_share_pct", trace, monkeypatch=monkeypatch)
    read("decode_rest_device_ms", trace, monkeypatch=monkeypatch)
    logged = capsys.readouterr().out
    assert "8 prompt tokens in 1 chunks of 2 steps: 8.0 a chunk" in logged
    assert "under `sample` 0.0001" in logged  # 50 ns a call, in ms


OLDER = (
    "engine_batch_occupancy_pct", "engine_prefill_step_share_pct", "engine_step_host_ms",
    "serve_idle_outside_spans_pct", "moe_dispatch_device_ms", "train_loss_device_pct",
)


@pytest.mark.parametrize("name", SERVE_READERS + TRAIN_READERS)
def test_a_program_older_than_the_annotator_gets_one_answer(trace, monkeypatch, name):
    """The parent of the PR that brought the spans has none, and run.py ends a
    traced run on a reader that finds nothing: with no `llmt/` span at all,
    every reader of what that PR added says NOT_A_READING, whatever else the
    trace holds. The flax module scopes were there before and read as ever."""
    old = without(trace, scopes=["moe_", "loss_ce", "sample"])
    old["spans"] = []
    outside = {"traced": {"steps": 2, "prefill_steps": 1, "decode_steps": 2, "decode_rows": 3}}
    value = read(name, old, outside, monkeypatch)
    if name in OLDER:
        assert value == sr.NOT_A_READING < 0
    else:
        assert value == pytest.approx(read(name, trace, monkeypatch=monkeypatch)) and value > 0


def test_benchmark_json_lists_each_new_reader_with_its_cells():
    bench = json.loads((LAYER_METRICS.parents[1] / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in bench["per_layer"]}
    serve = ["phi3m-serve-rollout", "olmoe-serve-rollout"]
    for name in SERVE_READERS + TRAIN_READERS:
        reader = common.load_module(LAYER_METRICS / f"{name}.py")
        entry = listed[name]
        assert (entry["layer"], entry["unit"], entry["moves"]) == (reader.LAYER, reader.UNIT, reader.MOVES)
        if name == "moe_dispatch_device_ms":
            assert entry["workloads"] == ["olmoe-serve-rollout"]
        elif name in TRAIN_READERS:
            assert entry["workloads"] == ["phi3m-train-4k-fsdp4"]
        else:
            assert entry["workloads"] == serve
    assert {listed[n]["source"] for n in SERVE_READERS[:4]} == {"program_span"}
    assert {listed[n]["source"] for n in SERVE_READERS[4:] + TRAIN_READERS} == {"device_trace"}
