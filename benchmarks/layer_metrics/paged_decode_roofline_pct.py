"""Least time the chip could take for the `paged_decode` calls of the traced
window over the time they took. Bytes are those of the live tokens."""
from benchmarks import common, trace_reduce
from benchmarks.costs import paged_decode

LAYER, UNIT, MOVES = "kernels (ops/pallas/paged_attention.py)", "%", "serve_tok_s"


def read(trace, counters, cell):
    traced = counters.get("traced") or {}
    seconds, calls = trace_reduce.time_by_name(trace["devices"]["0"]["ops"], r"paged_decode")
    if not calls or not traced.get("decode_steps"):
        return None
    cfg, peaks = cell.config, cell.peaks(cell.device["kind"])
    heads = cfg["num_attention_heads"]
    one = paged_decode.cost(
        traced["live_tokens"] / traced["decode_steps"],
        traced["decode_rows"] / traced["decode_steps"],
        heads, cfg["num_key_value_heads"], cfg.get("head_dim") or cfg["hidden_size"] // heads, 2,
    )
    by_flops = one["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = one["bytes"] / peaks["hbm_bytes_per_s"]
    common.log(
        f"paged_decode: {calls} calls, {1e3 * seconds / calls:.4f} ms a call, "
        f"{one['bytes'] / 1e6:.1f} MB and {one['flops'] / 1e9:.2f} GFLOP a call, "
        f"bound by {'bytes' if by_bytes >= by_flops else 'operations'}"
    )
    return 100.0 * max(by_flops, by_bytes) * calls / seconds
