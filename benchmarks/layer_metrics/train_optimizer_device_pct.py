"""Share of the train step's device time on device 0 spent under `optimizer` (trainer/trainer.py: `tx.update`,
`optax.apply_updates`, the gradient norm). A program with neither `rms_norm` nor `optimizer` anywhere reads -1: not a
reading."""
from benchmarks import step_reduce

LAYER, UNIT, MOVES = "model (models/phi3, train step)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    return step_reduce.new_scope_share_pct(cell, "optimizer")
