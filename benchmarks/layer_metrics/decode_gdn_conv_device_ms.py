"""Device time a `decode_step` execution spends under `gdn_conv` (the gated-delta-rule layers' tail read, depthwise
convolutions, SiLU and tail write; `llm_training_tpu/models/olmo_hybrid/model.py`). Logs the mixer's other named parts
beside it: `gdn_gates`, `gdn_recurrence`, `gdn_out`, and what of `/linear_attn/` none of them holds (the q, k, v
projections and the q/k normalisation). A program with no `gdn_conv` scope reads `span_reduce.NOT_A_READING`, -1, logged."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"
SCOPE = "gdn_conv"
PARTS = ("gdn_conv", "gdn_gates", "gdn_recurrence", "gdn_out")
BLOCK = "/linear_attn/"


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    if not calls:
        return None
    per_call = 1e3 / calls
    parts = {p: span_reduce.seconds_under(ops, p) * per_call for p in PARTS}
    if not parts[SCOPE]:
        common.log(f"no op under {SCOPE!r} in decode_step: a program without the scope, "
                   f"{span_reduce.NOT_A_READING} is not a reading")
        return span_reduce.NOT_A_READING
    block = span_reduce.seconds_under(ops, BLOCK) * per_call
    common.log(
        "gated delta rule, device ms a decode_step: " + ", ".join(f"{k} {v:.4f}" for k, v in parts.items())
        + f", the rest of {BLOCK} {block - sum(parts.values()):.4f} of {block:.4f}"
    )
    return parts[SCOPE]
