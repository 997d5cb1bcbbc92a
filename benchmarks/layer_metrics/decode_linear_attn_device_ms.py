"""Device time a `decode_step` execution spends under the linear-attention (KDA) blocks' scope
(`/linear_attn/`): projections, conv, gates, the recurrence on the state slab, the output projection."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"
SCOPE = "/linear_attn/"
PARTS = ("kda_conv", "kda_gates", "kda_recurrence", "kda_chunk")


def under_ms(cell, program: str):
    """(ms a call under `SCOPE`, by named part) inside executions of `program`; None where no op holds the scope."""
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=program)
    if not calls:
        return None
    mine = [e for e in ops if SCOPE in e[3]]
    parts = {p: 1e3 * span_reduce.seconds_under(mine, p) / calls for p in PARTS}
    common.log(f"linear_attn device ms a {program}: " + ", ".join(f"{k} {v:.4f}" for k, v in parts.items() if v))
    return 1e3 * span_reduce.seconds_under(mine, SCOPE) / calls or None


def read(trace, counters, cell):
    return under_ms(cell, r"decode_step")
