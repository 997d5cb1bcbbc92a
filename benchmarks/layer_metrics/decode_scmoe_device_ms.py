"""Device time a `decode_step` execution spends in the shortcut-connected MoE branch (the ops under `scmoe`; a grouped
product whose scope the compiler stripped is counted by its op name, as `decode_mlp_device_ms` counts it): router,
dispatch, the held experts' grouped products, the zero-compute experts' identity term. Logs the parts beside."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"
SCOPE = "scmoe"
PARTS = span_reduce.MOE_SCOPES + ("moe_zero",)


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    if not calls or not span_reduce.seconds_under(ops, SCOPE):
        return None  # no time under it: the scope is gone
    parts = {p: 1e3 * span_reduce.seconds_under(ops, p) / calls for p in PARTS}
    common.log("scmoe device ms a decode_step: " + ", ".join(f"{k} {v:.4f}" for k, v in parts.items()))
    return 1e3 * span_reduce.seconds_under(ops, SCOPE, "moe_experts") / calls
