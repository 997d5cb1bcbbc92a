"""Time on device 0 in which a collective runs and no compute does, over the
traced window."""
from benchmarks import trace_reduce

LAYER, UNIT, MOVES = "parallel (parallel/mesh.py)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    if len(trace["devices"]) < 2:
        return None
    lo, hi = trace_reduce.window_ns(trace)
    return 100.0 * trace_reduce.exposed_collective_s(trace) / ((hi - lo) * trace_reduce.NS)
