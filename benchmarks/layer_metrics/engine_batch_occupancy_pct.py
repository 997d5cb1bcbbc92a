"""Decoding rows over max_batch, mean over the traced decode steps, from the
engine's own counts: the closing args of its `serve/engine_step` spans."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "serving (serve/engine.py, serve/scheduler.py)", "%", "serve_tok_s"


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    if (older := span_reduce.older_program(spans)) is not None:
        return older
    mine = span_reduce.step_counts(spans)
    common.log(f"engine_step counts {mine}; counted from outside over the same steps {counters.get('traced')}")
    if not mine["decode_steps"]:
        return None
    return 100.0 * mine["decode_rows"] / (mine["decode_steps"] * cell.traffic["engine"]["max_batch"])
