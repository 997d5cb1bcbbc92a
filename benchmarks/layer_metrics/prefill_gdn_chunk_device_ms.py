"""Device time a `prefill_chunk` execution spends under `gdn_chunk` (the gated-delta-rule layers' chunked rule from the
slot's state to the slot's state, and the state's write back; `llm_training_tpu/models/olmo_hybrid/model.py:GatedDeltaNet`).
Logs beside it the mixer's other named parts (`gdn_conv`, `gdn_gates`, `gdn_out`), what of `/linear_attn/` none of them
holds (the q, k, v projection and the q/k normalisation) and, where the stack has latent-attention layers too, their
chunk attention (`mla_attend`). At capacity a chunk rides a decode step, so its time moves the rate. A program with no
`gdn_chunk` scope reads `span_reduce.NOT_A_READING`, -1, logged; nothing where no `prefill_chunk` ran."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"
SCOPE = "gdn_chunk"
PARTS = ("gdn_conv", "gdn_gates", "gdn_chunk", "gdn_out")
BLOCK, BESIDE = "/linear_attn/", "mla_attend"


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"prefill_chunk")
    if not calls:
        return None
    per_call = 1e3 / calls
    parts = {p: span_reduce.seconds_under(ops, p) * per_call for p in PARTS}
    if not parts[SCOPE]:
        common.log(f"no op under {SCOPE!r} in prefill_chunk: a program without the scope, "
                   f"{span_reduce.NOT_A_READING} is not a reading")
        return span_reduce.NOT_A_READING
    block = span_reduce.seconds_under(ops, BLOCK) * per_call
    common.log(
        f"gated delta rule, device ms a prefill_chunk ({calls} chunks): "
        + ", ".join(f"{k} {v:.4f}" for k, v in parts.items())
        + f", the rest of {BLOCK} {block - sum(parts.values()):.4f} of {block:.4f}"
        + f"; {BESIDE} {span_reduce.seconds_under(ops, BESIDE) * per_call:.4f}"
    )
    return parts[SCOPE]
