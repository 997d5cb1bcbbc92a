"""Share of the train step's device time on device 0 that lies in no bucket of `step_reduce.BUCKETS`: 100 less the
collectives, `loss_ce`, `rms_norm`, `/self_attn/`, `/mlp/`, `optimizer` and `embed_tokens`. The table logs the ops that
hold it. Its meaning rests on the two scopes this reader came with: a program with neither reads -1, not a reading."""
from benchmarks import step_reduce

LAYER, UNIT, MOVES = "model (models/phi3, train step)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    found = step_reduce.train_table(cell)
    if found is None:
        return None
    if (older := step_reduce.older_program(step_reduce.for_cell(cell))) is not None:
        return older
    return step_reduce.share_pct(found, "none")
