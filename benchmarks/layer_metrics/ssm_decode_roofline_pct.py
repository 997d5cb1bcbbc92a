"""Least time the chip could take for the Mamba layers' decode recurrences of the traced window (the ops under `ssm_step`
inside `decode_step`, Pallas kernel or XLA fusions alike) over the time they took. Bytes: each decoding row's LOGICAL float32
state read once and written once, and the token's vectors (`costs/ssm_decode.py`); a stored layout that pads the state, or a
form that passes over it more than twice, shows as roofline lost. Logs ms a layer and MB a layer. A program with no
`ssm_step` scope reads `span_reduce.NOT_A_READING`, -1, logged."""
from benchmarks import common, span_reduce
from benchmarks.costs import ssm_decode

LAYER, UNIT, MOVES = "kernels (ops/selective_scan.py)", "%", "serve_tok_s"
SCOPE = "ssm_step"


def read(trace, counters, cell):
    traced = counters.get("traced") or {}
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    if not calls or not traced.get("decode_steps"):
        return None
    seconds = span_reduce.seconds_under(ops, SCOPE)
    if not seconds:
        common.log(f"no op under {SCOPE!r} in decode_step: a program without the scope, "
                   f"{span_reduce.NOT_A_READING} is not a reading")
        return span_reduce.NOT_A_READING
    cfg, peaks = cell.config, cell.peaks(cell.device["kind"])
    layers = cfg["layer_types"][: cfg["num_hidden_layers"]].count("mamba")
    one = ssm_decode.cost(
        traced["decode_rows"] / traced["decode_steps"], cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    )
    by_flops = one["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = one["bytes"] / peaks["hbm_bytes_per_s"]
    common.log(
        f"ssm_step: {calls} decode steps x {layers} layers, {1e3 * seconds / (calls * layers):.4f} ms a layer, "
        f"{one['bytes'] / 1e6:.1f} MB and {one['flops'] / 1e9:.3f} GFLOP a layer, "
        f"bound by {'bytes' if by_bytes >= by_flops else 'operations'}"
    )
    return 100.0 * max(by_flops, by_bytes) * calls * layers / seconds
