"""trace_reduce on a hand-made trace with numbers worked by hand, and on a
small trace recorded on the chip (one decode program of phi3m-serve-rollout,
cut to its first ops) cross-checked by a brute-force timeline."""

import json
from pathlib import Path

import numpy as np
import pytest

from benchmarks import trace_reduce as tr

FIXTURE = Path(__file__).parent / "fixtures" / "serve_trace_small.json"

# device 0: a program from 100 to 1100 ns holding a while (container) whose
# body runs a fusion 100..400, a paged_decode kernel 400..700, then an
# all-gather 800..1000; a second program 2000..2300 with one copy. Device 1
# is busy only 100..600.
HAND = {
    "devices": {
        "0": {
            "programs": [["jit_decode_step(1)", 100.0, 1000.0], ["jit_prefill_chunk(2)", 2000.0, 300.0]],
            "ops": [
                ["while.5 s32[]", 100.0, 600.0],
                ["fusion.3 bf16[32,5120]", 100.0, 300.0],
                ["paged_decode.1 bf16[32,10,4,128]", 400.0, 300.0],
                ["all-gather.2 bf16[5120,5120]", 800.0, 200.0],
                ["copy.7 bf16[13,3073,10,16,128]", 2000.0, 300.0],
                ["bitcast.9 bf16[32,40,128]", 2300.0, 0.0],
            ],
        },
        "1": {"programs": [], "ops": [["fusion.3 bf16[32,5120]", 100.0, 500.0]]},
    },
    "host": [
        ["$serve_closed.py:106 step", 0.0, 5000.0],
        ["$engine.py:494 step", 50.0, 4000.0],
        ["$api.py:2641 device_put", 1100.0, 800.0],
        ["DevicePutWithSharding", 1200.0, 500.0],
    ],
}


def test_own_name_drops_operands_and_layouts():
    text = "%copy.7 = bf16[13,3073,10,16,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%paged_decode.1)"
    assert tr.own_name(text) == "copy.7 bf16[13,3073,10,16,128]"
    assert tr.own_name("%fusion = (u32[1]{0:T(128)}, u32[1]{0}) fusion(%x), kind=kLoop") == "fusion u32[1]"


def test_hand_made_trace_gives_the_hand_worked_numbers():
    assert tr.window_ns(HAND) == (100.0, 2300.0)
    # device 0 busy: 100..700 (the while covers its body), 800..1000, 2000..2300
    assert tr.busy_s(HAND)["0"] == pytest.approx(1100e-9)
    assert tr.busy_s(HAND)["1"] == pytest.approx(500e-9)
    assert tr.idle_pct(HAND, worst=True) == pytest.approx(100 * (1 - 500 / 2200))
    assert tr.idle_pct(HAND, worst=False) == pytest.approx(100 * (1 - 800 / 2200))
    # a consumer named after the kernel in its operands would not count: own names only
    assert tr.time_by_name(HAND["devices"]["0"]["ops"], r"paged_decode") == (pytest.approx(300e-9), 1)
    assert tr.program_device_ms(HAND, r"decode_step") == pytest.approx(800e-6)
    assert tr.program_device_ms(HAND, r"prefill_chunk") == pytest.approx(300e-6)
    assert tr.program_device_ms(HAND, r"train_step") is None
    assert tr.exposed_collective_s(HAND) == pytest.approx(200e-9)
    top = dict(tr.top_ops(HAND))
    assert "while s32[]" not in top and top["fusion bf16[32,5120]"] == pytest.approx(300e-9)
    gaps = dict(tr.idle_gaps(HAND, min_gap_ns=50))
    # 700..800 under engine.step, 1000..2000 under device_put's native call
    assert gaps["$api.py:2641 device_put > DevicePutWithSharding"] == pytest.approx(1000e-9)
    assert gaps["$serve_closed.py:106 step > $engine.py:494 step"] == pytest.approx(100e-9)


def brute_busy(events, lo, hi, tick=1.0):
    line = np.zeros(int((hi - lo) / tick) + 1, bool)
    for _, start, dur in events:
        line[int((start - lo) / tick): int((start + dur - lo) / tick)] = True
    return line.sum() * tick


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace in this checkout")
def test_recorded_trace_agrees_with_a_brute_force_timeline():
    trace = json.loads(FIXTURE.read_text())
    ops = trace["devices"]["0"]["ops"]
    lo, hi = tr.window_ns(trace)
    assert tr.busy_s(trace)["0"] * 1e9 == pytest.approx(brute_busy(ops, lo, hi), rel=1e-3)
    seconds, calls = tr.time_by_name(ops, r"paged_decode")
    kernel = [e for e in ops if e[0].startswith("paged_decode")]
    assert calls == len(kernel) > 0 and seconds * 1e9 == pytest.approx(sum(e[2] for e in kernel))
    assert trace["meta"]["paged_decode_calls"] == calls
    assert 0.0 < tr.idle_pct(trace) < 100.0
