"""Device-busy time of the decode program over its calls, from the trace."""
from benchmarks import trace_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    return trace_reduce.program_device_ms(trace, r"decode_step")
