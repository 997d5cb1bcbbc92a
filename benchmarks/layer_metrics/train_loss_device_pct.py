"""Share of device 0's busy time spent under `loss_ce`, the chunked head and cross-entropy (llm_training_tpu/ops/cross_entropy.py):
forward, backward and recomputation together."""
from benchmarks import span_reduce

LAYER, UNIT, MOVES = "model (models/phi3, train step)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    if (older := span_reduce.older_program(spans)) is not None:
        return older
    return span_reduce.train_share_pct(spans, "loss_ce")
