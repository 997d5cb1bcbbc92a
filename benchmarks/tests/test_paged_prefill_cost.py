"""`costs/paged_prefill.py` against a brute-force mask, and
`paged_prefill_roofline_pct` on a hand-made trace
(fixtures/prefill_kernel_small.json) whose least times are worked out here."""

import copy
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import common, span_reduce as sr
from benchmarks.costs import paged_prefill

FIXTURE = Path(__file__).parent / "fixtures" / "prefill_kernel_small.json"
READER = Path(__file__).resolve().parents[1] / "layer_metrics" / "paged_prefill_roofline_pct.py"
PAGE, HEADS, KV_HEADS, DIM, WINDOW = 4, 4, 2, 8, 6
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
TWO_GROUPS = {
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention", "sliding_attention"],
    "num_hidden_layers": 3, "sliding_window": WINDOW, "num_attention_heads": HEADS,
    "num_key_value_heads": KV_HEADS, "head_dim": DIM,
}


def mask(start, tokens, window):
    """[tokens, start + tokens] bool: query i at `start + i` sees key j."""
    q = start + np.arange(tokens)[:, None]
    k = np.arange(start + tokens)[None, :]
    seen = k <= q
    return seen if window is None else seen & (q - k < window)


@pytest.mark.parametrize("window", [None, 1, 6, 2048], ids=lambda w: f"window-{w}")
@pytest.mark.parametrize("start,tokens", [(0, 1), (0, 7), (5, 7), (100, 33), (12288, 512), (2000, 512)])
def test_cost_counts_exactly_the_visible_pairs_and_their_pages(start, tokens, window):
    seen = mask(start, tokens, window)
    assert paged_prefill.visible_pairs(start, tokens, window) == int(seen.sum())
    for page in (4, 16):
        pages = {j // page for j in np.flatnonzero(seen.any(axis=0))}
        # every page from the oldest visible key's to the last query's, none skipped
        assert paged_prefill.visible_pages(start, tokens, window, page) == len(pages) == max(pages) - min(pages) + 1
    one = paged_prefill.cost(start, tokens, window, 16, 32, 4, 128, 2)
    assert one["pairs"] == int(seen.sum()) and one["flops"] == 4 * one["pairs"] * 32 * 128
    assert one["bytes"] == len({j // 16 for j in np.flatnonzero(seen.any(axis=0))}) * 16 * 4 * 128 * 2 * 2 \
        + 2 * tokens * 32 * 128 * 2


def test_an_empty_chunk_costs_nothing():
    assert paged_prefill.cost(7, 0, None, 16, 32, 4, 128, 2) == {"pairs": 0, "flops": 0, "bytes": 0}


@pytest.fixture()
def trace():
    return json.loads(FIXTURE.read_text())


def read(trace, monkeypatch, config=TWO_GROUPS):
    monkeypatch.setattr(sr, "for_cell", lambda cell: trace)
    cell = SimpleNamespace(
        config=config, traffic={"engine": {"block_size": PAGE}}, device={"kind": "toy"},
        peaks=lambda kind: PEAKS,
    )
    return common.load_module(READER).read({"devices": trace["devices"]}, {}, cell)


def least(start, tokens, window):
    """Worked by hand from the mask: the larger of operations and bytes."""
    seen = mask(start, tokens, window)
    flops = 2 * 2 * int(seen.sum()) * HEADS * DIM
    pages = len({j // PAGE for j in np.flatnonzero(seen.any(axis=0))})
    moved = pages * PAGE * KV_HEADS * DIM * 2 * 2 + 2 * tokens * HEADS * DIM * 2
    return max(flops / PEAKS["bf16_flops_per_s"], moved / PEAKS["hbm_bytes_per_s"])


def test_reader_sums_both_groups_calls_by_their_layer_counts(trace, monkeypatch):
    # two chunks: 8 tokens from 0, 4 from 8; two window layers and a global one; 800 ns of kernel a chunk
    chunks = [(0, 8), (8, 4)]
    want = 100.0 * sum(2 * least(s, n, WINDOW) + least(s, n, None) for s, n in chunks) / (2 * 800e-9)
    got = read(trace, monkeypatch)
    assert got == pytest.approx(want) and 0 < got <= 100
    # a window layer's call of the second chunk sees 6 keys a query, a global layer's 9 to 12
    assert paged_prefill.visible_pairs(8, 4, WINDOW) == 24 < paged_prefill.visible_pairs(8, 4, None) == 42


def test_reader_takes_a_stack_with_one_group_as_one(trace, monkeypatch):
    plain = {k: v for k, v in TWO_GROUPS.items() if k != "layer_types"}
    want = 100.0 * 3 * (least(0, 8, WINDOW) + least(8, 4, WINDOW)) / (2 * 800e-9)
    assert read(trace, monkeypatch, plain) == pytest.approx(want)
    assert read(trace, monkeypatch, {**plain, "sliding_window": None}) == pytest.approx(
        100.0 * 3 * (least(0, 8, None) + least(8, 4, None)) / (2 * 800e-9)
    )


def test_reader_returns_nothing_only_when_the_kernel_or_the_chunks_are_gone(trace, monkeypatch):
    assert read(trace, monkeypatch) is not None
    # the kernel renamed or no longer run, in a program that says where its chunks start
    gone = copy.deepcopy(trace)
    line = gone["devices"]["0"]
    line["ops"] = [op for op in line["ops"] if not op[0].startswith("paged_prefill")]
    assert read(gone, monkeypatch) is None
    # the kernel's calls in a decode step are not a chunk's
    elsewhere = copy.deepcopy(gone)
    elsewhere["devices"]["0"]["ops"].append(["paged_prefill.7 bf16[1]", 12700.0, 100.0, "jit(decode_step)/x"])
    assert read(elsewhere, monkeypatch) is None
    # no step with a chunk in the spans
    idle = copy.deepcopy(trace)
    idle["spans"] = [s for s in idle["spans"] if not s["args"].get("prefill_chunks")]
    assert read(idle, monkeypatch) is None


def test_reader_says_not_a_reading_for_a_program_older_than_the_kernel(trace, monkeypatch):
    """This PR's parent: its engine steps carry no `prefill_start` (and its
    chunks attend on the gather path). -1, logged, and the traced run goes on."""
    older = copy.deepcopy(trace)
    for span in older["spans"]:
        span["args"].pop("prefill_start", None)
    line = older["devices"]["0"]
    line["ops"] = [op for op in line["ops"] if not op[0].startswith("paged_prefill")]
    assert read(older, monkeypatch) == sr.NOT_A_READING == -1.0
    # and a trace with no llmt/ span at all, as every reader since PR 25
    assert read({**older, "spans": []}, monkeypatch) == sr.NOT_A_READING
