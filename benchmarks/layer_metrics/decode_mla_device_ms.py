"""Device time a `decode_step` execution spends under the latent-attention (MLA) blocks' scope (`/self_attn/`) of a stack
whose blocks name their parts (`models/deepseek/model.py:MLAttention`). Logs the parts beside it: projections and norms
(`mla_q`, `mla_kv`), the query's fold through `W_uk` (`mla_absorb`), the kernel over the rows' pages and the `W_uv` product
after it (`mla_attend`), the output projection (`mla_out`). A program with no `mla_q` scope reads
`span_reduce.NOT_A_READING`, -1, logged."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"
SCOPE = "/self_attn/"
PARTS = ("mla_q", "mla_kv", "mla_absorb", "mla_attend", "mla_out")


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    if not calls:
        return None
    mine = [e for e in ops if SCOPE in e[3]]
    parts = {p: 1e3 * span_reduce.seconds_under(mine, p) / calls for p in PARTS}
    if not parts["mla_q"]:
        common.log(f"no op under 'mla_q' in decode_step: a program without the scope, "
                   f"{span_reduce.NOT_A_READING} is not a reading")
        return span_reduce.NOT_A_READING
    block = 1e3 * span_reduce.seconds_under(mine, SCOPE) / calls
    common.log(
        "mla device ms a decode_step: " + ", ".join(f"{k} {v:.4f}" for k, v in parts.items())
        + f", the rest of {SCOPE} {block - sum(parts.values()):.4f} of {block:.4f}"
    )
    return block
