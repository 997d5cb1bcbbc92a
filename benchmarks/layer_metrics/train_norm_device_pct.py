"""Share of the train step's device time on device 0 spent under `rms_norm` (llm_training_tpu/ops/rms_norm.py: every norm
of every family), forward, backward and recomputation together; `step_reduce.train_table` logs it by pass, with the
bucket's longest ops and their whole result types. What the compiler fused into a neighbouring matmul carries that
matmul's scope and is not here. A program with neither `rms_norm` nor `optimizer` anywhere reads -1: not a reading."""
from benchmarks import step_reduce

LAYER, UNIT, MOVES = "model (models/phi3, train step)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    return step_reduce.new_scope_share_pct(cell, "rms_norm")
