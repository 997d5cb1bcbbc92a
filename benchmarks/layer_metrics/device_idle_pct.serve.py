"""1 less the union of device operations over the traced window."""
from benchmarks import trace_reduce

LAYER, UNIT, MOVES = "device", "%", "serve_tok_s"


def read(trace, counters, cell):
    return trace_reduce.idle_pct(trace, worst=False)
