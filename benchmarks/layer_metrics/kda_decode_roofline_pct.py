"""Least time the chip could take for the KDA decode recurrences of the traced window (the ops under
`kda_recurrence` inside `decode_step`, Pallas kernel or XLA fusions alike) over the time they took.
Bytes: each decoding row's state read once and written once, and the token's vectors (`costs/kda_decode.py`)."""
from benchmarks import common, span_reduce
from benchmarks.costs import kda_decode

LAYER, UNIT, MOVES = "kernels (models/solar_open2/kda.py)", "%", "serve_tok_s"
SCOPE = "kda_recurrence"


def read(trace, counters, cell):
    traced = counters.get("traced") or {}
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    seconds = span_reduce.seconds_under(ops, SCOPE)
    if not calls or not seconds or not traced.get("decode_steps"):
        return None
    cfg, peaks = cell.config, cell.peaks(cell.device["kind"])
    linear = cfg["linear_attn_config"]
    gqa = cfg["gqa_layers"]
    layers = sum(1 for i in range(cfg["num_hidden_layers"]) if i not in gqa)
    one = kda_decode.cost(
        traced["decode_rows"] / traced["decode_steps"], linear["num_heads"], linear["head_dim"], linear["head_dim"]
    )
    by_flops = one["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = one["bytes"] / peaks["hbm_bytes_per_s"]
    common.log(
        f"kda_recurrence: {calls} decode steps x {layers} layers, {1e3 * seconds / (calls * layers):.4f} ms a layer, "
        f"{one['bytes'] / 1e6:.1f} MB and {one['flops'] / 1e9:.3f} GFLOP a layer, "
        f"bound by {'bytes' if by_bytes >= by_flops else 'operations'}"
    )
    return 100.0 * max(by_flops, by_bytes) * calls * layers / seconds
