"""Device time a `decode_step` execution spends under the attention block's scope (`/self_attn/`),
from the scope each device op was traced under."""
from benchmarks import span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    split = span_reduce.decode_split_ms(span_reduce.for_cell(cell))
    return split and (split["attn"] or None)  # no time under it: the scope is gone
