"""`costs/mla_prefill.py` against a brute-force mask, and
`mla_prefill_roofline_pct` on a hand-made trace
(fixtures/mla_prefill_kernel_small.json) whose least times are worked out
here; on a chip run's result line the share lies under 100."""

import copy
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import common, span_reduce as sr
from benchmarks.costs import mla_prefill

FIXTURE = Path(__file__).parent / "fixtures" / "mla_prefill_kernel_small.json"
READER = Path(__file__).resolve().parents[1] / "layer_metrics" / "mla_prefill_roofline_pct.py"
HEADS, LATENT, NOPE, ROPE, V = 4, 16, 8, 4, 8
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
CONFIG = {
    "num_attention_heads": HEADS, "kv_lora_rank": LATENT, "qk_nope_head_dim": NOPE,
    "qk_rope_head_dim": ROPE, "v_head_dim": V,
}


def mask(start, tokens):
    """[tokens, start + tokens] bool: query i at `start + i` sees key j."""
    return np.arange(start + tokens)[None, :] <= start + np.arange(tokens)[:, None]


@pytest.mark.parametrize("heads", [64, 128])
@pytest.mark.parametrize("start,tokens", [(0, 1), (0, 7), (5, 7), (100, 33), (8192 - 512, 512), (3072, 512)])
def test_cost_counts_exactly_the_visible_pairs_and_rows(start, tokens, heads):
    seen = mask(start, tokens)
    one = mla_prefill.cost(start, tokens, heads, 512, 128, 64, 128, 2)
    assert one["pairs"] == int(seen.sum())
    # a pair costs a head 192 multiply-adds for its score and 128 for its value; the expansion is not counted
    assert one["flops"] == 2 * int(seen.sum()) * heads * (128 + 64 + 128)
    rows = int(seen.any(axis=0).sum())
    assert rows == start + tokens
    assert one["bytes"] == 2 * (rows * 576 + 512 * heads * 256 + tokens * heads * (192 + 128))


def test_an_empty_chunk_costs_nothing():
    assert mla_prefill.cost(7, 0, 64, 512, 128, 64, 128, 2) == {"pairs": 0, "flops": 0, "bytes": 0}


def test_the_mean_chunk_of_the_issue_is_139_gflop_a_layer():
    """ISSUE 42's arithmetic: 512 queries on a row of about 3,070 tokens see
    about 1.70 M pairs; at 128 heads that is 139 GFLOP a layer."""
    one = mla_prefill.cost(3072, 512, 128, 512, 128, 64, 128, 2)
    assert one["pairs"] == 512 * 3072 + 512 * 513 // 2 == 1_704_192
    assert round(one["flops"] / 1e9, 1) == 139.6


@pytest.fixture()
def trace():
    return json.loads(FIXTURE.read_text())


def read(trace, monkeypatch, config=CONFIG):
    monkeypatch.setattr(sr, "for_cell", lambda cell: trace)
    cell = SimpleNamespace(config=config, device={"kind": "toy"}, peaks=lambda kind: PEAKS)
    return common.load_module(READER).read({"devices": trace["devices"]}, {}, cell)


def least(start, tokens):
    """Worked by hand from the mask: the larger of operations and bytes."""
    seen = mask(start, tokens)
    flops = 2 * int(seen.sum()) * HEADS * (NOPE + ROPE + V)
    moved = 2 * (
        (start + tokens) * (LATENT + ROPE) + LATENT * HEADS * (NOPE + V) + tokens * HEADS * (NOPE + ROPE + V)
    )
    return max(flops / PEAKS["bf16_flops_per_s"], moved / PEAKS["hbm_bytes_per_s"])


def test_reader_takes_a_call_a_block_a_chunk(trace, monkeypatch):
    # two chunks: 8 tokens from 0, 4 from 8; two MLA blocks; 600 ns of kernel a chunk
    want = 100.0 * 2 * (least(0, 8) + least(8, 4)) / (2 * 600e-9)
    got = read(trace, monkeypatch)
    assert got == pytest.approx(want) and 0 < got <= 100
    module = common.load_module(READER)
    assert (module.LAYER, module.UNIT, module.MOVES) == ("kernels (ops/pallas/mla_prefill.py)", "%", "serve_tok_s")


def test_reader_says_not_a_reading_for_a_program_whose_chunks_attend_in_xla(trace, monkeypatch):
    """This PR's parent: `mla_attend` holds XLA's trips, no kernel. -1,
    logged, and the traced run goes on (`benchmarks/run.py` ends it on nothing)."""
    older = copy.deepcopy(trace)
    for op in older["devices"]["0"]["ops"]:
        if op[0].startswith("mla_prefill"):
            op[0] = op[0].replace("mla_prefill", "fusion") + " f32[4,8,8]"
            op[3] = op[3].replace("pallas_call", "while/body/reduce_max")
    assert read(older, monkeypatch) == sr.NOT_A_READING == -1.0
    # and a trace with no llmt/ span at all, as every reader since PR 25
    assert read({**older, "spans": []}, monkeypatch) == sr.NOT_A_READING


def test_reader_returns_nothing_only_when_the_chunks_or_the_scope_are_gone(trace, monkeypatch):
    assert read(trace, monkeypatch) is not None
    # neither the kernel nor the scope its call sits under, in a program with chunks
    gone = copy.deepcopy(trace)
    line = gone["devices"]["0"]
    line["ops"] = [op for op in line["ops"] if "mla_attend" not in op[3]]
    assert read(gone, monkeypatch) is None
    # the decode step's `mla_attend` is not a chunk's
    elsewhere = copy.deepcopy(gone)
    elsewhere["devices"]["0"]["ops"].append(
        ["mla_prefill.7 bf16[1]", 12700.0, 100.0, "jit(decode_step)/x/mla_attend/pallas_call"]
    )
    assert read(elsewhere, monkeypatch) is None
    # no step with a chunk in the spans
    idle = copy.deepcopy(trace)
    idle["spans"] = [s for s in idle["spans"] if not s["args"].get("prefill_chunks")]
    assert read(idle, monkeypatch) is None


def test_benchmark_json_lists_the_reader_last_with_both_latent_cells():
    listed = json.loads((Path(__file__).resolve().parents[2] / "BENCHMARK.json").read_text())
    mine = [m for m in listed["per_layer"] if m["name"] == "mla_prefill_roofline_pct"]
    assert mine == [{
        "name": "mla_prefill_roofline_pct", "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels (ops/pallas/mla_prefill.py)", "moves": "serve_tok_s",
        "workloads": ["longcat-serve-longctx", "pangu-serve-longctx8k"],
    }]
    cells = {w["name"] for w in listed["workloads"]}
    assert set(mine[0]["workloads"]) <= cells


@pytest.mark.parametrize("cell", ["longcat-serve-longctx", "pangu-serve-longctx8k"])
def test_the_share_lies_under_100_on_the_chip_run(cell):
    """The traced runs of the PR that brought the kernel (`chiprun_out/pr42/`,
    where a builder's call left them): a lower bound on the kernel's work
    cannot pass 100. Skipped where no chip run's line is at hand."""
    line = Path(__file__).resolve().parents[2] / "chiprun_out" / "pr42" / f"{cell}.traced.change.json"
    if not line.exists():
        pytest.skip(f"no chip run's result line at {line}")
    value = json.loads(line.read_text())["metrics"]["mla_prefill_roofline_pct"]["value"]
    assert 0 < value < 100
