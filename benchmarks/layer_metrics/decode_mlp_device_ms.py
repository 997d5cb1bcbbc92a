"""Device time a `decode_step` execution spends under the MLP block's scope (`/mlp/`), the sparse block's too,
from the scope each device op was traced under."""
from benchmarks import span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    split = span_reduce.decode_split_ms(span_reduce.for_cell(cell))
    return split and (split["mlp"] or None)  # no time under it: the scope is gone
