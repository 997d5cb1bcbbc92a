"""Required operations per token x tokens/s/chip of the traced window over
the chip's bf16 peak. Not a kernel's roofline share."""
from benchmarks import required_ops

LAYER, UNIT, MOVES = "model (models/phi3, train step)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    if "traced_tok_s_chip" not in counters:
        return None
    peak = cell.peaks(cell.device["kind"])["bf16_flops_per_s"]
    per_token = required_ops.train_flops_per_token(cell.config, cell.traffic["documents"])
    return 100.0 * per_token * counters["traced_tok_s_chip"] / peak
