"""Device time a `decode_step` execution spends under `rms_norm` (llm_training_tpu/ops/rms_norm.py: every norm of every
family; a part of whatever block reader held it before, `decode_rest_device_ms` for a layer's own norms). Logs its longest
ops with their whole result types. A program with no `rms_norm` anywhere reads -1: not a reading."""
from benchmarks import step_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    return step_reduce.norm_device_ms(cell, r"decode_step")
