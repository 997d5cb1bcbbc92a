"""Device time a `prefill_chunk` execution spends under the attention blocks' scope (`/self_attn/`): projections and
qk-norm, the append and the chunk's attention over the row's pages (`attn_window` against the window group's short table,
`attn_global` against the whole row), the output gate (`attn_gate`) and the output projection. Logs the parts, and the
whole chunk's device ops beside them."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "itl_p95_ms"
PARTS = ("attn_window", "attn_global", "attn_gate")


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"prefill_chunk")
    if not calls:
        return None
    mine = [e for e in ops if span_reduce.ATTN in e[3]]
    total = 1e3 * span_reduce.seconds_under(mine, span_reduce.ATTN) / calls
    parts = {p: 1e3 * span_reduce.seconds_under(mine, p) / calls for p in PARTS}
    common.log(
        "attention device ms a prefill_chunk: " + ", ".join(f"{k} {v:.4f}" for k, v in parts.items())
        + f", projections and the rest {total - sum(parts.values()):.4f}"
        + f"; of {1e3 * span_reduce.NS * sum(e[2] for e in ops) / calls:.4f} ms of device ops a chunk"
    )
    return total or None  # no time under it: the scope is gone
