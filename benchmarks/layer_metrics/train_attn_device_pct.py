"""Share of device 0's busy time spent under the attention block's scope (`/self_attn/`):
forward, backward and recomputation together."""
from benchmarks import span_reduce

LAYER, UNIT, MOVES = "model (models/phi3, train step)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    return span_reduce.train_share_pct(span_reduce.for_cell(cell), span_reduce.ATTN)
