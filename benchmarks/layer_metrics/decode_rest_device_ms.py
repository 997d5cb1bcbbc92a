"""Device time a `decode_step` execution spends under neither block's scope: pool copies, stacked-weight slices, norms, embedding, head, `sample`,
from the scope each device op was traced under. Logs `sample`'s part of it."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    split = span_reduce.decode_split_ms(span_reduce.for_cell(cell))
    if split is None:
        return None
    common.log(f"of decode_rest_device_ms {split['rest']:.4f}, under `sample` {split['sample']:.4f}")
    return split["rest"] or None  # no time under it: the scope is gone
