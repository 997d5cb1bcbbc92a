"""Device time a `decode_step` execution spends routing and moving tokens
around the experts: the ops under `moe_route`, `moe_sort`, `moe_gather` and
`moe_scatter` (llm_training_tpu/models/moe.py); `moe_experts` is logged beside."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    if (older := span_reduce.older_program(spans)) is not None:
        return older
    ops, calls = span_reduce.scoped_ops(spans, program=r"decode_step")
    if not calls:
        return None
    by_scope = {s: 1e3 * span_reduce.seconds_under(ops, s) / calls for s in span_reduce.MOE_SCOPES}
    common.log("moe device ms a decode_step: " + ", ".join(f"{k} {v:.4f}" for k, v in by_scope.items()))
    return sum(by_scope[s] for s in span_reduce.MOE_DISPATCH) or None  # no time under them: the scopes are gone
