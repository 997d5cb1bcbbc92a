"""1 less the union of device operations over the traced window, worst device."""
from benchmarks import trace_reduce

LAYER, UNIT, MOVES = "device", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    return trace_reduce.idle_pct(trace, worst=True)
