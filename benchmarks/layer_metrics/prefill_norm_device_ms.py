"""Device time a `prefill_chunk` execution spends under `rms_norm` (llm_training_tpu/ops/rms_norm.py). Logs its longest
ops with their whole result types. A program with no `rms_norm` anywhere reads -1: not a reading."""
from benchmarks import step_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "itl_p95_ms"


def read(trace, counters, cell):
    return step_reduce.norm_device_ms(cell, r"prefill_chunk")
