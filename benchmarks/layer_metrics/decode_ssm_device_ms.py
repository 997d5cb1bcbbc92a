"""Device time a `decode_step` execution spends in the Mamba layers' state-space path: under `ssm_conv` (the tail read, the
depthwise convolution with its bias, SiLU, the tail write) and under `ssm_step` (the one-token selective scan on the slab,
written in place; `llm_training_tpu/models/phi4flash/model.py`). Logs both, and what of `/mamba/` neither holds (the in, x,
dt and out projections, softplus, the gate). A program with no `ssm_step` scope reads `span_reduce.NOT_A_READING`, -1, logged."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"
PARTS = ("ssm_conv", "ssm_step")
BLOCK = "/mamba/"


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    if not calls:
        return None
    per_call = 1e3 / calls
    parts = {p: span_reduce.seconds_under(ops, p) * per_call for p in PARTS}
    if not parts["ssm_step"]:
        common.log(f"no op under 'ssm_step' in decode_step: a program without the scope, "
                   f"{span_reduce.NOT_A_READING} is not a reading")
        return span_reduce.NOT_A_READING
    block = span_reduce.seconds_under(ops, BLOCK) * per_call
    common.log(
        "mamba, device ms a decode_step: " + ", ".join(f"{k} {v:.4f}" for k, v in parts.items())
        + f", the rest of {BLOCK} {block - sum(parts.values()):.4f} of {block:.4f}"
    )
    return sum(parts.values())
