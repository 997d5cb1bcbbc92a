"""Device time a `decode_step` execution spends under the gated memory units' scope (`/gmu/`): the gate's projection, SiLU,
the product with the memory and the output projection (`llm_training_tpu/models/phi4flash/model.py`). A program with no such
scope reads `span_reduce.NOT_A_READING`, -1, logged."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"
SCOPE = "/gmu/"


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    if not calls:
        return None
    under = 1e3 * span_reduce.seconds_under(ops, SCOPE) / calls
    if not under:
        common.log(f"no op under {SCOPE!r} in decode_step: a program without the scope, "
                   f"{span_reduce.NOT_A_READING} is not a reading")
        return span_reduce.NOT_A_READING
    return under
