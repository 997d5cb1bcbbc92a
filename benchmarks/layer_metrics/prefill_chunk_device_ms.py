"""Device-busy time of the prefill-chunk program over its calls, from the trace."""
from benchmarks import trace_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "itl_p95_ms"


def read(trace, counters, cell):
    return trace_reduce.program_device_ms(trace, r"prefill_chunk")
