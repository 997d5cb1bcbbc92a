"""Device time a `decode_step` execution spends under `moe_shared` (the shared expert's SwiGLU, computed in full on every
row beside the held experts' grouped products; `models/deepseek/model.py:DeepseekMoE`). Logs the routed side beside it
(`moe_route`, `moe_sort`, `moe_gather`, `moe_experts`, `moe_scatter`). A program with no `moe_shared` scope reads
`span_reduce.NOT_A_READING`, -1, logged."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"
SCOPE = "moe_shared"


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    if not calls:
        return None
    shared = 1e3 * span_reduce.seconds_under(ops, SCOPE) / calls
    if not shared:
        common.log(f"no op under {SCOPE!r} in decode_step: a program without the scope, "
                   f"{span_reduce.NOT_A_READING} is not a reading")
        return span_reduce.NOT_A_READING
    routed = {p: 1e3 * span_reduce.seconds_under(ops, p) / calls for p in span_reduce.MOE_SCOPES}
    common.log(
        f"experts, device ms a decode_step: {SCOPE} {shared:.4f}, "
        + ", ".join(f"{k} {v:.4f}" for k, v in routed.items())
    )
    return shared
